"""Finding a cell's files by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own under ``benchmarks/``; nothing here
knows a cell's name.  A later PR adds entries and files and edits none.
"""

from __future__ import annotations

import functools
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


#: Which path of a family a cell takes, by its traffic mix's ``kind``; the
#: runner of the path is ``harness/<path>_cell.py``.
PATH_OF_KIND = {"serve-open": "serve", "serve-closed": "serve", "train": "train"}
#: What ``families/<model>/<path>.py`` has to define (benchmarks/README.md,
#: "A model family", says what each takes and returns).
FAMILY_INTERFACE = {
    "serve": ("build", "apply_fn", "decode_fns", "max_len", "token_vocab",
              "reference_logits_at", "decode_step_bytes", "tiny"),
    "train": ("build", "loss_fn", "sharding_rules", "batches",
              "train_flops_per_example", "reference_train", "compared_kernels",
              "tiny"),
}


def _module_from(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@functools.lru_cache(maxsize=None)
def family(model: str, path: str):
    """``families/<model>/<path>.py``: what a runner needs to know of a model
    family (``model`` is the configuration's; ``path`` is ``serve`` or
    ``train``).  A missing file or function is named."""
    file = os.path.join(BENCH_DIR, "families", model, path + ".py")
    if not os.path.exists(file):
        raise FileNotFoundError(
            f"a configuration with \"model\": {model!r} in a {path} cell needs "
            f"{os.path.relpath(file, ROOT)}, which is not there "
            "(benchmarks/README.md, \"A model family\")")
    mod = _module_from(file, f"bench_family_{model}_{path}")
    missing = [f for f in FAMILY_INTERFACE[path] if not callable(getattr(mod, f, None))]
    if missing:
        raise AttributeError(
            f"{os.path.relpath(file, ROOT)} lacks {', '.join(missing)} "
            "(benchmarks/README.md, \"A model family\")")
    return mod


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

    @property
    def path(self) -> str:
        kind = self.traffic["kind"]
        if kind not in PATH_OF_KIND:
            raise ValueError(f"unknown traffic kind {kind!r}")
        return PATH_OF_KIND[kind]

    @property
    def family(self):
        """The configuration's model family on this cell's path."""
        return family(self.config["model"], self.path)


def layer_metric(name: str) -> dict:
    """``layer_metrics/<name>.json``: the reader a metric uses and its
    arguments."""
    return _load(os.path.join(BENCH_DIR, "layer_metrics", name + ".json"))


def reader(name: str):
    """``layer_metrics/readers/<name>.py``'s ``read(evidence, **args)``."""
    path = os.path.join(BENCH_DIR, "layer_metrics", "readers", name + ".py")
    return _module_from(path, f"bench_reader_{name}").read


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
