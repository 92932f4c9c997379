"""The controls, at a size a test run can hold: the reference put in the
program's place and computed in int8 has to come out as not correct, and the
timed path, broken underneath, too.  The chip readings at the cells' own
sizes are in PERF.md; the limits here are the tiny sizes' own."""

import time

import numpy as np
import pytest

from benchmarks import rehearse
from benchmarks.harness import manifest, serve_cell, train_cell

manifest.peak_for = lambda kind: {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}


def tiny(workload):
    return rehearse.shrink(manifest.Cell(workload))


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_int8_control_fails_the_served_gap_and_bf16_passes(seed):
    cell = tiny("cgpt13b-serve-chat")
    rng = np.random.default_rng(seed)
    sample = [(rng.integers(0, 250, size=20).tolist(), rng.integers(0, 250, size=100).tolist())
              for _ in range(6)]
    limit = cell.traffic["correct"]["limits"]["widest_gap"]
    sound = serve_cell.widest_gap(cell.config, seed, sample, "bfloat16", "mode")
    control = serve_cell.widest_gap(cell.config, seed, sample, "int8", "mode")
    assert sound["widest_gap"] <= limit < control["widest_gap"]


def test_an_altered_token_is_caught(monkeypatch):
    from distributed_tensorflow_examples_tpu.serve import model_server

    real = np.argmax
    monkeypatch.setattr(
        model_server.np, "argmax", lambda x, *a, **k: (real(x, *a, **k) + 1) % 250)
    out = serve_cell.run(tiny("cgpt13b-serve-batch"), 4, 3.0, False, time.monotonic())
    monkeypatch.undo()
    assert out["check"]["positions"] > 0
    assert out["correct"] is False


def test_the_sound_serving_path_passes():
    out = serve_cell.run(tiny("cgpt13b-serve-batch"), 4, 3.0, False, time.monotonic())
    assert out["failed"] == 0 and out["correct"] is True


def test_the_lower_precision_fails_a_training_number_and_the_sound_step_passes():
    cell = tiny("resnet50-train-b256")
    out = train_cell.run(cell, 5, 2.0, False, time.monotonic(),
                         control=cell.config["precision"]["control"])
    limits = cell.traffic["correct"]["limits"]
    assert out["correct"] is True
    c = out["check"]["control_bfloat16"]
    assert c["grad_gap_kernels"] > limits["grad_gap_kernels"]
    assert c["grad_cosine_median"] < limits["grad_cosine_median"]


def test_a_step_that_returns_its_state_unchanged_is_caught(monkeypatch):
    import jax
    import jax.numpy as jnp

    real = train_cell.make_step

    def broken(exp, state, first):
        step, nbytes = real(exp, state, first)

        def unchanged(state, batch):
            _new, metrics = step(jax.tree.map(jnp.copy, state), batch)
            return state, metrics

        return unchanged, nbytes

    monkeypatch.setattr(train_cell, "make_step", broken)
    out = train_cell.run(tiny("resnet50-train-b256"), 5, 2.0, False, time.monotonic())
    assert out["check"]["delta_gap_kernels"] == pytest.approx(1.0)
    assert out["correct"] is False
