"""A runner finds a model family by the configuration's ``model``, by file
name: the interface of every family file that is there, each cell rehearsed
through its family at the tiny size, and a stand-in family that comes as
files and entries only - a program PR that renames what a family calls, or a
harness that needs an edit for a new family, fails here and not on the chip."""

import filecmp
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmarks.harness import manifest

M = manifest.benchmark()
FAMILY_FILES = sorted(
    (model, path[:-3])
    for model in os.listdir(os.path.join(manifest.BENCH_DIR, "families"))
    for path in os.listdir(os.path.join(manifest.BENCH_DIR, "families", model))
    if path.endswith(".py")
)


def rehearse(root: str, workload: str, seed: int) -> dict:
    """``rehearse.py`` of the tree at ``root`` in a process of its own; the
    program comes from this checkout."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=manifest.ROOT)
    run = subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "rehearse.py"),
         "--workload", workload, "--seed", str(seed), "--seconds", "3"],
        env=env, capture_output=True, text=True, timeout=600)
    assert run.returncode == 0, run.stdout[-2000:] + run.stderr[-4000:]
    return json.loads(run.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("model,path", FAMILY_FILES)
def test_every_family_file_has_its_paths_whole_interface(model, path):
    assert path in manifest.FAMILY_INTERFACE, f"families/{model}/{path}.py is no path"
    mod = manifest.family(model, path)
    for name in manifest.FAMILY_INTERFACE[path]:
        assert callable(getattr(mod, name)), f"families/{model}/{path}.py lacks {name}"


@pytest.mark.parametrize("workload", [w["name"] for w in M["workloads"]])
def test_tiny_keeps_the_configuration_and_states_the_rehearsals_limits(workload):
    cell = manifest.Cell(workload)
    before = json.dumps(cell.config, sort_keys=True)
    tiny = cell.family.tiny(cell.config)
    assert json.dumps(cell.config, sort_keys=True) == before, "tiny() changed its argument"
    assert tiny["model"] == cell.config["model"]
    assert set(cell.traffic["correct"]["limits"]) <= set(tiny["rehearsal"]["limits"])


@pytest.mark.parametrize("workload", [w["name"] for w in M["workloads"]])
def test_every_cell_rehearses_correct_through_its_family(workload):
    cell = manifest.Cell(workload)
    out = rehearse(manifest.ROOT, workload, seed=7)
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    assert set(out["would_report"]) == {e["name"] for e in cell.end_to_end}


# -- a stand-in family: files and entries only --------------------------------

TOY_CONFIG = {
    "name": "toy-mixer", "source": "benchmarks/tests/test_families.py", "model": "toy",
    "published": {"vocab": 200},
    "program": {"vocab": 200, "dim": 32, "layers": 2, "max_len": 128},
    "precision": {"params": "float32", "compute": "float32", "control": "bfloat16"},
    "reduced": [],
}

TOY_REFERENCE = '''
"""Plain reference of the stand-in: two layers, each adding tanh(W . the
running mean of its inputs so far); no attention, no MLP, a tree of its own."""
import jax
import jax.numpy as jnp
import numpy as np

from . import precision, weights


def tree(c, key):
    def leaf(i, shape, std):
        return std * jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)

    D = c["dim"]
    out = {"embed": leaf(0, (c["vocab"], D), 1.0), "out": leaf(1, (D, c["vocab"]), D ** -0.5)}
    for i in range(c["layers"]):
        out[f"mix_{i}"] = {"w": leaf(10 + i, (D, D), D ** -0.5)}
    return out


def logits_at(c, seed, tokens, rows, cols, mode="float32"):
    p = tree(c, weights.base_key(seed))
    x = p["embed"][jnp.asarray(tokens)]
    count = jnp.arange(1, x.shape[1] + 1, dtype=jnp.float32)[None, :, None]
    for i in range(c["layers"]):
        x = x + jnp.tanh(precision.matmul(jnp.cumsum(x, axis=1) / count, p[f"mix_{i}"]["w"], mode))
    return np.asarray(precision.matmul(x[np.asarray(rows), np.asarray(cols)], p["out"], mode))
'''

TOY_FAMILY = '''
"""The stand-in family, served: a step over a cache of each layer's inputs."""
import copy

import jax.numpy as jnp

from benchmarks.reference import toy_ref


def build(config, overrides=None):
    c = dict(config["program"], **(overrides or {}))
    return c, lambda key: toy_ref.tree(c, key)


def decode_fns(c):
    def init_cache_fn(slots, max_len):
        return jnp.zeros((c["layers"], slots, max_len, c["dim"]), jnp.float32)

    def step_fn(params, cache, tokens, pos):
        x = params["embed"][tokens]
        seen = jnp.arange(cache.shape[2])[None, :, None] <= pos[:, None, None]
        slot = jnp.arange(tokens.shape[0])
        for i in range(c["layers"]):
            cache = cache.at[i, slot, pos].set(x)
            mean = jnp.where(seen, cache[i], 0.0).sum(1) / (pos[:, None] + 1.0)
            x = x + jnp.tanh(mean @ params[f"mix_{i}"]["w"])
        return x @ params["out"], cache

    return init_cache_fn, step_fn


def apply_fn(c):
    init_cache_fn, step_fn = decode_fns(c)

    def predict(params, batch):
        tokens = batch["x"]
        cache = init_cache_fn(tokens.shape[0], tokens.shape[1])
        out = []
        for t in range(tokens.shape[1]):
            logits, cache = step_fn(params, cache, tokens[:, t], jnp.full(tokens.shape[:1], t))
            out.append(logits)
        return jnp.stack(out, axis=1)

    return predict


def max_len(config):
    return config["program"]["max_len"]


def token_vocab(config):
    return config["published"]["vocab"]


def reference_logits_at(config, seed, tokens, rows, cols, mode="float32"):
    return toy_ref.logits_at(config["program"], seed, tokens, rows, cols, mode)


def decode_step_bytes(config, *, slots, cache_rows):
    c, width = config["program"], jnp.dtype(config["precision"]["params"]).itemsize
    read = c["layers"] * c["dim"] ** 2 + c["dim"] * c["vocab"] + slots * c["dim"]
    return read * width + cache_rows * c["layers"] * c["dim"] * 4


def tiny(config):
    out = copy.deepcopy(config)
    out["rehearsal"] = {"limits": {"widest_gap": 1e-4}}
    return out
'''


def tree_with_the_toy(tmp_path) -> str:
    """A copy of what the benchmark commits, with the stand-in ADDED: three
    files, and entries in ``BENCHMARK.json``."""
    root = str(tmp_path / "tree")
    shutil.copytree(
        manifest.BENCH_DIR, os.path.join(root, "benchmarks"),
        ignore=shutil.ignore_patterns("__pycache__", ".pytest_cache"))
    bench = os.path.join(root, "benchmarks")
    with open(os.path.join(bench, "configs", "toy-mixer.json"), "w") as f:
        json.dump(TOY_CONFIG, f)
    with open(os.path.join(bench, "reference", "toy_ref.py"), "w") as f:
        f.write(TOY_REFERENCE)
    os.makedirs(os.path.join(bench, "families", "toy"))
    with open(os.path.join(bench, "families", "toy", "serve.py"), "w") as f:
        f.write(TOY_FAMILY)
    m = manifest.benchmark()
    m["configs"].append({"name": "toy-mixer", "source": TOY_CONFIG["source"],
                         "file": "benchmarks/configs/toy-mixer.json", "reduced": [],
                         "why": "a family of another tree"})
    m["workloads"].append({"name": "toy-serve-batch", "config": "toy-mixer",
                           "traffic": "batch-closed-16", "chips": 1, "why": "the door"})
    for metric in m["end_to_end"] + m["per_layer"]:
        if "cgpt13b-serve-batch" in metric.get("workloads", []):
            metric["workloads"].append("toy-serve-batch")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(m, f)
    return root


def differing(a: str, b: str, rel: str = "") -> list:
    """Files under ``a`` that ``b`` lacks or holds otherwise."""
    cmp = filecmp.dircmp(os.path.join(a, rel), os.path.join(b, rel),
                         ignore=["__pycache__", ".pytest_cache"])
    _same, diff, odd = filecmp.cmpfiles(cmp.left, cmp.right, cmp.common_files, shallow=False)
    out = [os.path.join(rel, f) for f in cmp.left_only + diff + odd]
    for sub in cmp.common_dirs:
        out += differing(a, b, os.path.join(rel, sub))
    return out


def test_a_new_family_is_files_and_entries_only(tmp_path):
    root = tree_with_the_toy(tmp_path)
    out = rehearse(root, "toy-serve-batch", seed=5)
    assert out["workload"] == "toy-serve-batch"
    assert out["correct"] is True and out["failed"] == 0
    assert out["compared"]["positions"] > 0 and out["compared"]["widest_gap"] <= 1e-4
    assert set(out["would_report"]) == {"served_tokens_per_s", "setup_s"}
    # Every file the benchmark had is there as it was.
    assert differing(manifest.BENCH_DIR, os.path.join(root, "benchmarks")) == []
