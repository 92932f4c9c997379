"""The jamba family's counts beside their expected values, its configuration
against the source's keys, its control at the rehearsal's size, and the two
readers that come with it."""

import json

import numpy as np
import pytest

from benchmarks import rehearse
from benchmarks.harness import manifest, serve_cell

family = manifest.family("jamba", "serve")
CELL = "jamba2-3b-serve-chat-busy"
CONFIG = manifest.Cell(CELL).config

#: The source's keys (huggingface.co/ai21labs/AI21-Jamba2-3B config.json, as
#: the catalog of public architectures holds them).
SOURCE = {
    "attn_layer_offset": 7, "attn_layer_period": 14, "expert_layer_offset": 1,
    "expert_layer_period": 2, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 8192, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_state": 16, "mamba_dt_rank": 160, "mamba_expand": 2,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "model_type": "jamba", "num_attention_heads": 20, "num_experts": 1,
    "num_experts_per_tok": 1, "num_hidden_layers": 28, "num_key_value_heads": 1,
    "num_logits_to_keep": 1, "rms_norm_eps": 1e-06, "sliding_window": None,
    "tie_word_embeddings": True, "use_mamba_kernels": True, "vocab_size": 65536,
}


def test_the_configuration_holds_the_sources_keys_unchanged():
    assert CONFIG["published"] == SOURCE
    # At the top level too, under the same keys: what a check of the file
    # against the source compares.
    assert {k: CONFIG[k] for k in SOURCE} == SOURCE
    assert CONFIG["reduced"] == [] and CONFIG["assumed"]["head_dim"] == 128
    assert CONFIG["precision"] == {
        "params": "bfloat16", "compute": "bfloat16", "control": "fp8"}
    assert CONFIG["deployment"] == "one replica on one v5e chip"
    entry = {c["name"]: c for c in manifest.benchmark()["configs"]}["ai21-jamba2-3b"]
    assert entry["source"] == CONFIG["source"] and entry["reduced"] == []


def test_a_configuration_the_program_does_not_build_is_refused():
    other = json.loads(json.dumps(CONFIG))
    other["published"]["num_experts"] = 16
    with pytest.raises(ValueError, match="num_experts"):
        family.sizes(other)


def test_parameter_counts_by_hand():
    D, F, Di, N, R, K = 2560, 8192, 5120, 16, 160, 4
    per = family.param_counts(CONFIG)
    mixer = (D * 2 * Di + K * Di + Di + Di * (R + 2 * N) + (R + 2 * N)
             + R * Di + Di + N * Di + Di + Di * D)
    assert per["mamba"] == 2 * D + 3 * D * F + mixer
    assert mixer == pytest.approx(41.2e6, rel=0.005)       # the issue's count
    assert per["attention"] == 2 * D + 3 * D * F + 2 * D * 20 * 128 + 2 * D * 128
    assert per["attention"] == pytest.approx(76.7e6, rel=0.005)
    assert per["top"] == 65536 * D + D
    assert family.layer_counts(CONFIG) == {"mamba": 26, "attention": 2}
    total = 26 * per["mamba"] + 2 * per["attention"] + per["top"]
    assert total == pytest.approx(3.03e9, rel=0.005)       # 6.06 GB in bfloat16
    # The seeded tree has exactly these leaves.
    import jax

    from benchmarks.reference import weights

    cfg, tree_fn = family.build(CONFIG)
    shapes = jax.eval_shape(tree_fn, weights.base_key(1))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) == total
    assert {str(a.dtype) for a in jax.tree.leaves(shapes)} == {"bfloat16"}
    assert cfg.layer_kinds.count("attention") == 2


def test_decode_step_bytes_by_hand():
    per = family.param_counts(CONFIG)
    total = 26 * per["mamba"] + 2 * per["attention"] + per["top"]
    state = 26 * (16 + 3) * 5120 * 4     # a session's state and conv tails
    assert family.state_bytes_per_slot(CONFIG) == state == 10_117_120
    rows = 5000.0
    want = total * 2 + rows * (2 * 2 * 128 * 2) + 2 * 32 * state
    assert family.decode_step_bytes(CONFIG, slots=32, cache_rows=rows) == want
    # 6.06 GB of parameters + 0.65 GB of state + 5 MB of keys and values:
    # 8.2 ms at 819 GB/s.
    assert 6.70e9 < want < 6.73e9


def test_scan_chunk_bytes_and_vector_operations_by_hand():
    C, Di, N = 512, 5120, 16
    assert family.scan_chunk_bytes(CONFIG, C) == 4 * (
        2 * C * Di + C * Di + 2 * C * N + N * Di + Di + 2 * N * Di)
    assert family.scan_chunk_bytes(CONFIG, C) == pytest.approx(32.5e6, rel=0.01)
    ops = family.scan_chunk_vector_ops(CONFIG, C)
    assert ops == {"exp": C * Di * N, "mul_add": C * Di * (6 * N + 2)}


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_fp8_control_fails_the_served_gap_and_bf16_passes(seed):
    """At the rehearsal's size: the reference in bfloat16 (what the program
    computes in) stays under the tiny limit, the fp8 control goes over it."""
    cell = rehearse.shrink(manifest.Cell(CELL))
    rng = np.random.default_rng(seed)
    sample = [(rng.integers(0, 250, size=20).tolist(), rng.integers(0, 250, size=100).tolist())
              for _ in range(6)]
    limit = cell.traffic["correct"]["limits"]["widest_gap"]
    sound = serve_cell.widest_gap(cell.config, seed, sample, "bfloat16", "mode")
    control = serve_cell.widest_gap(cell.config, seed, sample, "fp8", "mode")
    assert sound["widest_gap"] <= limit < control["widest_gap"], (sound, control)


def _evidence(ops, modules):
    tr = {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
          "spans": [(0.0, 10.0, "bench.window")], "host": []}
    return {"trace": tr, "cell": manifest.Cell(CELL),
            "peaks": {"hbm_bytes_per_s": 819e9}}


def test_the_scan_readers_on_a_made_up_trace():
    scan = "mamba_selective_scan.3 (f32[512,40,128],...)"
    ops = [(1.0 + i * 0.01, 1.0 + i * 0.01 + 0.0004, scan) for i in range(52)]
    ops.append((3.0, 3.5, "fusion.1 f32[32,65536]"))
    modules = [(1.0, 1.3, "jit_prefill_fn(123)"), (1.3, 1.6, "jit_prefill_fn(123)"),
               (3.0, 3.5, "jit_step_fn(456)")]
    ev = _evidence(ops, modules)
    per_chunk = manifest.reader("trace_op_ms_per_launch")(
        ev, op="mamba_selective_scan", module="jit_prefill_fn")
    assert per_chunk == pytest.approx(26 * 0.4)            # 26 kernels of 0.4 ms a chunk
    share = manifest.reader("scan_roofline")(ev, op="mamba_selective_scan", chunk=512)
    assert share == pytest.approx(100 * (family.scan_chunk_bytes(CONFIG, 512) / 819e9) / 0.0004)
    assert 0 < share < 100
    # A program without the kernel (the parent's): nothing to read.
    none = _evidence(ops[-1:], modules)
    assert manifest.reader("trace_op_ms_per_launch")(
        none, op="mamba_selective_scan", module="jit_prefill_fn") is None
    assert manifest.reader("scan_roofline")(none, op="mamba_selective_scan", chunk=512) is None
