"""What a run prints: its set-up phases, the numbers compared, its last line."""

from __future__ import annotations

import importlib
import sys
import time

from . import manifest, trace


def say(msg: str) -> None:
    print(f"bench: {msg}", flush=True)


def say_compared(msg: str) -> None:
    """A number compared beside its limit: on standard output with the rest,
    and on standard error, whose end the driver keeps of a run that is not
    correct."""
    say(f"compared {msg}")
    print(f"bench: compared {msg}", file=sys.stderr, flush=True)


class Phases:
    """Prints each phase of set-up as it ends."""

    def __init__(self, t_start: float):
        self.t_last = t_start

    def mark(self, name: str) -> None:
        now = time.monotonic()
        say(f"setup phase {name}: {now - self.t_last:.2f} s")
        self.t_last = now


def runner_for(cell):
    """The module whose ``run`` drives a cell, by its traffic mix's kind."""
    return importlib.import_module(f"benchmarks.harness.{cell.path}_cell")


def device_record(memory: list, compiled_bytes: int = 0) -> dict:
    import jax

    devs = jax.devices()
    peak = max([m.get("peak_bytes_in_use", 0) for m in memory] + [compiled_bytes])
    return {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs), "memory_peak_bytes": int(peak),
    }


def result(cell, outcome: dict, traced: bool) -> dict:
    """``outcome`` is what a cell runner returns: ``correct``, ``attempted``,
    ``failed``, ``end_to_end`` values and the run's ``evidence``."""
    ev = outcome["evidence"]
    device = device_record(ev["memory"], ev.get("compiled_bytes") or 0)
    line = {
        "correct": outcome["correct"], "attempted": outcome["attempted"],
        "failed": outcome["failed"],
    }
    if traced:
        ev["peaks"] = manifest.peak_for(device["kind"])
        line["metrics"] = manifest.read_per_layer(cell, ev)
        busy_s, window_s = trace.busy(ev["trace"])
        if busy_s == 0:
            say(f"the traced window was idle: no operation ran on a device in its "
                f"{window_s:.2f} s, so no metric of a step or a launch is reported")
        device["busy_s"], device["window_s"] = busy_s, window_s
        line["breakdown"] = {
            "device_ops": trace.device_ops(ev["trace"]),
            "idle_gaps": trace.idle_gaps(ev["trace"]),
        }
    else:
        line["metrics"] = {}
        for e in cell.end_to_end:
            value = outcome["end_to_end"].get(e["name"])
            if value is None:
                raise RuntimeError(f"the run gave no {e['name']}")
            line["metrics"][e["name"]] = {"value": float(value), "unit": e["unit"]}
    line["device"] = device
    return line
