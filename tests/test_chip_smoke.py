"""chip_smoke.py on the CPU: the smoke's body at tiny size, and the guards
that keep a run that did not reach the chip from reading as one that did
(the smoke's own refusal, the benchmark runner's, build_mesh's accelerator
rule, the compile-cache placement rule).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import jax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _cpu_env() -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return env


# -- the smoke ---------------------------------------------------------------


def test_smoke_body_tiny_on_cpu(tmp_path):
    """Train -> publish -> serve -> decode at dim 64 / 2 layers / T=128 with
    the Pallas kernels asked for explicitly (interpret mode on the CPU);
    served tokens must equal ``generate`` here."""
    import chip_smoke

    from distributed_tensorflow_examples_tpu import models

    cfg = models.transformer.Config(
        vocab_size=512, dim=64, n_layers=2, n_heads=4, max_seq_len=128,
        attention="flash",
    )
    rec = chip_smoke.run_smoke(
        cfg, platform="cpu", out_dir=str(tmp_path), seq_len=128, batch=8,
        steps=4, decode_slots=2, n_requests=3, prompt_len=5, new_tokens=8,
    )
    assert rec["ok"], rec["failures"]
    json.dumps(rec)  # the printed record: no numpy scalars in it
    # A CPU record can never be read as a chip pass.
    assert rec["device"]["platform"] == "cpu" and rec["device"]["device_kind"]
    assert rec["train"]["kernels"]["compiled_tpu_custom_calls"] == 0
    losses = rec["train"]["losses"]
    assert len(losses) == 4 and np.all(np.isfinite(losses))
    assert losses[-1] < losses[0]
    serve = rec["serve"]
    assert serve["answered"] == serve["requests"] == 3
    assert serve["requests"] > serve["decode_slots"]
    assert serve["tokens"] == 3 * 8
    assert serve["served_equals_generate"] is True
    # The model-sized registry blob is scratch: gone when the run ends.
    assert not os.path.exists(tmp_path / "registry")


def test_smoke_body_refuses_the_wrong_platform(tmp_path):
    import chip_smoke

    t0 = time.perf_counter()
    with pytest.raises(RuntimeError, match="not 'tpu'"):
        chip_smoke.run_smoke(
            None, platform="tpu", out_dir=str(tmp_path / "o"), seq_len=128,
            batch=4, steps=1,
        )
    assert time.perf_counter() - t0 < 5
    assert not os.path.exists(tmp_path / "o")  # nothing was built


def test_smoke_cli_fails_fast_without_a_chip():
    t0 = time.perf_counter()
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        capture_output=True, text=True, env=_cpu_env(), cwd=ROOT, timeout=120,
    )
    assert p.returncode != 0
    assert time.perf_counter() - t0 < 20
    assert p.stdout.strip() == ""  # no result of any kind
    assert "not 'tpu'" in p.stderr.strip().splitlines()[-1]


# -- benchmarks/run.py -------------------------------------------------------


def test_benchmark_runner_prints_no_result_off_tpu():
    """No CPU run can print a metric: the one runner refuses before it
    builds anything, with its own exit code and an empty standard output."""
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "resnet50-train-b256", "--seed", "0",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=_cpu_env(), cwd=ROOT, timeout=120,
    )
    assert p.returncode == 3, p.stderr[-2000:]  # run.py's NO_CHIP
    assert p.stdout.strip() == ""
    assert "nothing was run" in p.stderr.strip().splitlines()[-1]


# -- build_mesh --------------------------------------------------------------


class _FakeDevice:
    def __init__(self, platform: str, i: int):
        self.platform, self.id = platform, i


def test_build_mesh_reraises_on_an_accelerator(monkeypatch):
    from distributed_tensorflow_examples_tpu.parallel import mesh as mesh_lib

    def refuse(*a, **k):
        raise NotImplementedError("no such topology")

    monkeypatch.setattr(mesh_lib.mesh_utils, "create_device_mesh", refuse)
    spec = mesh_lib.MeshSpec(data=2, model=2)
    with pytest.raises(NotImplementedError, match="no such topology"):
        mesh_lib.build_mesh(
            spec, devices=[_FakeDevice("tpu", i) for i in range(4)]
        )
    # CPU device lists keep the topology-unaware reshape.
    mesh = mesh_lib.build_mesh(spec, devices=jax.devices("cpu")[:4])
    assert mesh.shape["data"] == 2 and mesh.shape["model"] == 2


# -- the compile cache -------------------------------------------------------


def test_compile_cache_env_setting_wins(monkeypatch, tmp_path):
    from distributed_tensorflow_examples_tpu.utils import compile_cache

    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # set nothing


def test_compile_cache_default_only_on_tpu(monkeypatch):
    from distributed_tensorflow_examples_tpu.utils import compile_cache

    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() is None  # cpu: the checkout stays clean
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    try:
        assert compile_cache.enable() == compile_cache.DEFAULT_DIR
        assert jax.config.jax_compilation_cache_dir == compile_cache.DEFAULT_DIR
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert compile_cache.DEFAULT_DIR == os.path.join(ROOT, ".jax_cache")


def test_compile_cache_default_is_fixed_across_processes_and_cwds(tmp_path):
    code = (
        f"import sys; sys.path.insert(0, {ROOT!r})\n"
        "import importlib.util as u\n"
        "s = u.spec_from_file_location('cc', "
        f"{os.path.join(ROOT, 'distributed_tensorflow_examples_tpu', 'utils', 'compile_cache.py')!r})\n"
        "m = u.module_from_spec(s); s.loader.exec_module(m)\n"
        "print(m.DEFAULT_DIR)"
    )
    seen = {
        subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            env=_cpu_env(), cwd=cwd, timeout=60, check=True,
        ).stdout.strip()
        for cwd in (ROOT, str(tmp_path))
    }
    assert seen == {os.path.join(ROOT, ".jax_cache")}


def test_only_compile_cache_sets_a_cache_path():
    """Exactly one place in the tree may place the compilation cache."""
    hits = []
    for base, dirs, files in os.walk(ROOT):
        dirs[:] = [
            d for d in dirs
            if not d.startswith(".")
            and d not in ("__pycache__", "chiprun_out", "_checkout", "tests")
        ]
        for f in files:
            if not f.endswith(".py"):
                continue
            path = os.path.join(base, f)
            with open(path, encoding="utf-8") as fh:
                text = fh.read()
            if "jax_compilation_cache_dir" in text or "set_cache_dir" in text:
                hits.append(os.path.relpath(path, ROOT))
    assert hits == [
        os.path.join("distributed_tensorflow_examples_tpu", "utils", "compile_cache.py")
    ]
