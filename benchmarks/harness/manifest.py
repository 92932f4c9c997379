"""Finding a cell's files by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own under ``benchmarks/``; nothing here
knows a cell's name.  A later PR adds entries and files and edits none.
"""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return _load(os.path.join(ROOT, "BENCHMARK.json"))


def peaks() -> dict:
    return _load(os.path.join(BENCH_DIR, "harness", "peaks.json"))


def peak_for(device_kind: str) -> dict:
    """The published peaks of a device; an unknown kind is an error."""
    table = peaks()
    if device_kind not in table:
        raise KeyError(f"no published peaks for device_kind {device_kind!r}")
    return table[device_kind]


class Cell:
    """One entry of ``workloads`` with its configuration, traffic mix and
    the metrics it has to report."""

    def __init__(self, name: str, manifest: dict | None = None):
        m = manifest if manifest is not None else benchmark()
        by_name = {w["name"]: w for w in m["workloads"]}
        if name not in by_name:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        self.manifest = m
        self.workload = by_name[name]
        self.name = name
        self.chips = int(self.workload["chips"])
        cfg_entry = {c["name"]: c for c in m["configs"]}[self.workload["config"]]
        self.config = _load(os.path.join(ROOT, cfg_entry["file"]))
        self.traffic = _load(
            os.path.join(BENCH_DIR, "traffic", self.workload["traffic"] + ".json")
        )
        self.end_to_end = [
            e for e in m["end_to_end"] if name in e.get("workloads", [name])
        ]
        reported = {e["name"] for e in self.end_to_end}
        self.per_layer = [
            p for p in m["per_layer"]
            if name in p.get("workloads", [name]) and p["moves"] in reported
        ]


def layer_metric(name: str) -> dict:
    """``layer_metrics/<name>.json``: the reader a metric uses and its
    arguments."""
    return _load(os.path.join(BENCH_DIR, "layer_metrics", name + ".json"))


def reader(name: str):
    """``layer_metrics/readers/<name>.py``'s ``read(evidence, **args)``."""
    path = os.path.join(BENCH_DIR, "layer_metrics", "readers", name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_reader_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_per_layer(cell: Cell, evidence: dict) -> dict:
    """Every per-layer metric of the cell whose reader finds something to
    read, as ``{name: {"value", "unit"}}``."""
    out = {}
    for p in cell.per_layer:
        spec = layer_metric(p["name"])
        value = reader(spec["reader"])(evidence, **spec.get("args", {}))
        if value is not None:
            out[p["name"]] = {"value": float(value), "unit": p["unit"]}
    return out
