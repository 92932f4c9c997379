"""A serving cell: a registry-pinned replica in this process, the load
generator in a child, the window, the trace, the comparison.  What it serves
it asks of the configuration's family (``families/<model>/serve.py``).

Phases of set-up are printed as they end.  ``setup_s`` runs from the start
of the process to the first measured instant; the reference comparison runs
after the window, when the replica has been stopped and freed.
"""

from __future__ import annotations

import contextlib
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

import numpy as np

from . import manifest, stats, trace, traffic as traffic_lib
from .report import Phases, say_compared

MODEL_NAME = "bench_model"
HORIZON_SLACK_S = 5.0


def start_replica(cell, seed: int, registry_dir: str, phases: Phases):
    """Weights from the seed on the device, published, loaded by a pinned
    ``ModelReplicaServer``, which it returns."""
    import jax

    from benchmarks.reference import weights
    from distributed_tensorflow_examples_tpu import serve
    from distributed_tensorflow_examples_tpu.serve.registry import ModelRegistry
    from distributed_tensorflow_examples_tpu.train.checkpoint import flat_params_of

    phases.mark("import_program")
    family = cell.family
    cfg, tree_fn = family.build(cell.config)
    key = weights.base_key(seed)
    shapes = jax.eval_shape(tree_fn, key)
    make = jax.jit(tree_fn).lower(key).compile()
    phases.mark("weights_compile")
    params = make(key)
    jax.block_until_ready(params)
    phases.mark("weights_on_device")
    flat = flat_params_of(params)
    del params
    version = ModelRegistry(registry_dir).publish(
        MODEL_NAME, flat, step=0, source="benchmark"
    )
    del flat
    phases.mark("publish")
    s = cell.traffic["server"]
    server = serve.ModelReplicaServer(
        # Only shapes are read from what init_fn returns.
        lambda _rng: shapes,
        family.apply_fn(cfg),
        [], registry_dir=registry_dir, model_name=MODEL_NAME,
        model_version=version, decode_fns=family.decode_fns(cfg),
        decode_slots=s["decode_slots"], decode_max_len=s["decode_max_len"],
        decode_max_sessions=s["decode_max_sessions"], role="bench_serve0",
    )
    phases.mark("replica_load")
    return server


def warm_up(server, phases: Phases) -> None:
    """The one shape the engine has: a two-token request compiles it."""
    from distributed_tensorflow_examples_tpu import serve

    client = serve.ServeClient("127.0.0.1", server.port, role="bench_warm")
    try:
        out = client.generate(np.asarray([1, 2], np.int32), 2, deadline_s=1100.0)
    finally:
        client.close()
    if len(out) != 2:
        raise RuntimeError(f"warm-up returned {len(out)} tokens, wanted 2")
    phases.mark("compile_and_warm_up")


def start_generator(run_dir: str):
    """Start the child; it imports while the replica is set up.  Returns
    ``(process, result_path)``."""
    result_path = os.path.join(run_dir, "samples.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, os.path.join(os.path.dirname(__file__), "loadgen.py"),
         result_path],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env, text=True,
    )
    return proc, result_path


def hand_schedule(proc, schedule: dict, run_dir: str) -> None:
    """Wait until the child is ready and tell it where the schedule is."""
    line = proc.stdout.readline()
    if line.strip() != "ready":
        raise RuntimeError(f"load generator did not start: {line!r}")
    schedule_path = os.path.join(run_dir, "schedule.json")
    with open(schedule_path, "w") as f:
        json.dump(schedule, f)
    proc.stdin.write(schedule_path + "\n")
    proc.stdin.flush()


def schedule_for(cell, port: int, seed: int, seconds: float) -> dict:
    """What the generator plays: the mix's requests for the lead-in, the
    window and some slack, their tokens drawn from the family's vocabulary."""
    tr = cell.traffic
    return {
        "kind": tr["kind"], "poll_s": tr["poll_s"], "host": "127.0.0.1",
        "port": port,
        "requests": traffic_lib.serve_schedule(
            tr, cell.family.token_vocab(cell.config), seed,
            float(tr["lead_s"]) + seconds + HORIZON_SLACK_S),
    }


def measure(cell, server, generator, schedule: dict, seconds: float,
            traced: bool, run_dir: str, phases: Phases, t_proc0: float) -> dict:
    """Lead-in, window, trace.  Returns the evidence of the run."""
    import jax

    tr = cell.traffic
    lead = float(tr["lead_s"])
    proc, result_path = generator
    try:
        hand_schedule(proc, schedule, run_dir)
        phases.mark("generator_ready")
        t0 = time.monotonic() + 0.2
        w0, w1 = t0 + lead, t0 + lead + seconds
        proc.stdin.write(f"{t0!r} {w1!r}\n")
        proc.stdin.flush()
        trace_dir = os.path.join(run_dir, "trace")
        trace_s = min(float(tr["trace_s"]), seconds)
        time.sleep(max(0.0, w0 - time.monotonic()))
        counters0, t_c0 = server.stats(), time.monotonic()
        phases.mark("lead_in")
        setup_s = t_c0 - t_proc0
        span = contextlib.nullcontext()
        if traced:
            time.sleep(max(0.0, w1 - trace_s - time.monotonic()))
            trace.start(trace_dir)
            span = jax.profiler.TraceAnnotation(trace.WINDOW_SPAN)
        with span:
            time.sleep(max(0.0, w1 - time.monotonic()))
        counters1, t_c1 = server.stats(), time.monotonic()
        if traced:
            jax.profiler.stop_trace()
        proc.wait(timeout=120)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    if proc.returncode != 0:
        raise RuntimeError(f"load generator exited {proc.returncode}")
    with open(result_path) as f:
        records = json.load(f)["records"]
    return {
        "records": records, "w0": t_c0, "w1": t_c1, "setup_s": setup_s,
        "counters": {"start": counters0, "end": counters1},
        "trace_dir": trace_dir if traced else None,
    }


def client_series(records: list, w0: float, w1: float) -> dict:
    """What the clients saw in the window."""
    firsts = [r for r in records if r["times"] and w0 <= r["times"][0] < w1]
    sent = [r for r in records if r["sent"] is not None and w0 <= r["sent"] < w1]
    return {
        "ttft_ms": [(r["times"][0] - r["due"]) * 1e3 for r in firsts],
        "itl_ms": [g * 1e3 for g in stats.gaps_ending_in_window(
            [r["times"] for r in records], w0, w1)],
        "tokens": stats.tokens_in_window(
            [t for r in records for t in r["times"]], w0, w1),
        "gen_late_ms": [(r["sent"] - r["due"]) * 1e3 for r in sent],
        "attempted": len(sent),
        "failed": sum(1 for r in sent if r["status"] == "failed"),
        "completed": sum(1 for r in records
                         if r["status"] == "done" and w0 <= r["times"][-1] < w1),
    }


def sample_for_check(records: list, schedule: dict, k: int, seed: int):
    """``k`` finished requests drawn from the seed, the longest among them."""
    prompts = {r["id"]: r for r in schedule["requests"]}
    done = [r for r in records if r["status"] == "done"
            and len(r["tokens"]) == prompts[r["id"]]["n"]]
    if not done:
        return []
    longest = max(done, key=lambda r: len(prompts[r["id"]]["prompt"]) + len(r["tokens"]))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng(int(seed) + 1)
    picks = [rest[i] for i in rng.permutation(len(rest))[: max(0, k - 1)]]
    return [(prompts[r["id"]]["prompt"], r["tokens"]) for r in [longest] + picks]


def widest_gap(config: dict, seed: int, sample: list, mode: str = "float32",
               tokens_from: str = "served") -> dict:
    """The widest gap by which a token's float32-reference logit lies below
    the reference's best, over every generated position of the sample.
    ``tokens_from="served"`` reads the served tokens; ``"mode"`` reads the
    token that the reference computed in ``mode`` puts first (the control).
    """
    family = manifest.family(config["model"], "serve")
    longest = max(len(p) + len(t) - 1 for p, t in sample)
    L = min(-(-longest // 256) * 256, family.max_len(config))
    toks = np.zeros((len(sample), L), np.int32)
    rows, cols, served = [], [], []
    for i, (p, t) in enumerate(sample):
        seq = list(p) + list(t[:-1])
        toks[i, : len(seq)] = seq
        rows += [i] * len(t)
        cols += list(range(len(p) - 1, len(p) - 1 + len(t)))
        served += list(t)
    n = len(rows)
    pad = -(-n // 256) * 256 - n
    rows_p, cols_p = np.asarray(rows + [0] * pad), np.asarray(cols + [0] * pad)
    ref = family.reference_logits_at(config, seed, toks, rows_p, cols_p)[:n]
    if tokens_from == "mode":
        low = family.reference_logits_at(config, seed, toks, rows_p, cols_p, mode)[:n]
        chosen = np.argmax(low, axis=-1)
    else:
        chosen = np.asarray(served)
    gaps = ref.max(axis=-1) - ref[np.arange(n), chosen]
    return {
        "widest_gap": float(gaps.max()), "positions": n,
        "disagree": int((gaps > 0).sum()), "requests": len(sample),
    }


def run(cell, seed: int, seconds: float, traced: bool, t_proc0: float,
        control: str | None = None) -> dict:
    """One run of the cell.  ``control`` (a precision) also reads the control's
    gap on the same sample: for setting limits, never in a benchmark run."""
    import jax

    phases = Phases(t_proc0)
    phases.mark("runtime_start")
    run_dir = tempfile.mkdtemp(prefix="bench_run_")
    registry_dir = os.path.join(run_dir, "registry")
    server = None
    generator = start_generator(run_dir)
    try:
        server = start_replica(cell, seed, registry_dir, phases)
        warm_up(server, phases)
        tr = cell.traffic
        schedule = schedule_for(cell, server.port, seed, seconds)
        ev = measure(cell, server, generator, schedule, seconds, traced, run_dir, phases, t_proc0)
        memory = [d.memory_stats() or {} for d in jax.local_devices()[: cell.chips]]
        server.stop()
        server = None
        shutil.rmtree(registry_dir, ignore_errors=True)
        series = client_series(ev["records"], ev["w0"], ev["w1"])
        window_s = ev["w1"] - ev["w0"]
        evidence = {
            "cell": cell, "series": series, "counters": ev["counters"],
            "window_s": window_s, "memory": memory,
            "records": ev["records"], "w0": ev["w0"], "w1": ev["w1"],
            "schedule": schedule, "setup_s": ev["setup_s"],
            "trace_s": min(float(tr["trace_s"]), seconds),
            "trace": trace.load(trace.find_xplane(ev["trace_dir"])) if traced else None,
        }
        end_to_end = {
            "setup_s": ev["setup_s"],
            "ttft_p50_ms": stats.percentile(series["ttft_ms"], 50) if series["ttft_ms"] else None,
            "itl_p95_ms": stats.percentile(series["itl_ms"], 95) if series["itl_ms"] else None,
            "served_tokens_per_s": series["tokens"] / window_s,
        }
        t_ref = time.monotonic()
        sample = sample_for_check(
            ev["records"], schedule, int(tr["correct"]["sample_requests"]), seed)
        check = {"widest_gap": None, "positions": 0, "requests": 0}
        if sample:
            check = widest_gap(cell.config, seed, sample)
            for mode in (control or "").split(",") if control else ():
                check[f"control_{mode}"] = widest_gap(
                    cell.config, seed, sample, mode, "mode")["widest_gap"]
        limit = tr["correct"]["limits"]["widest_gap"]
        say_compared(f"widest_gap {check['widest_gap']} limit {limit} "
            f"(positions {check['positions']}, requests {check['requests']}, "
            f"reference {time.monotonic() - t_ref:.1f} s)")
        correct = (
            check["widest_gap"] is not None and limit is not None
            and check["widest_gap"] <= limit
        )
        return {
            "correct": bool(correct), "attempted": series["attempted"],
            "failed": series["failed"], "end_to_end": end_to_end,
            "evidence": evidence, "check": check,
        }
    finally:
        if generator[0].poll() is None:
            generator[0].kill()
        generator[0].wait()
        if server is not None:
            server.stop()
        shutil.rmtree(run_dir, ignore_errors=True)
