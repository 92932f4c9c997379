"""The longcat family's counts beside their expected values, its
configuration against the source's keys, its control at the rehearsal's
size, and the two readers that come with it."""

import json

import numpy as np
import pytest

from benchmarks import rehearse
from benchmarks.harness import manifest, serve_cell

family = manifest.family("longcat", "serve")
CELL = "longcat-omni-serve-longctx"
CONFIG = manifest.Cell(CELL).config

#: The source's keys (huggingface.co/meituan-longcat/LongCat-Flash-Omni
#: config.json, as the catalog of public architectures holds them).
SOURCE = {
    "attention_bias": False, "vocab_size": 131072, "hidden_size": 6144,
    "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048, "num_layers": 28,
    "num_attention_heads": 64, "kv_lora_rank": 512, "q_lora_rank": 1536,
    "qk_rope_head_dim": 64, "v_head_dim": 128, "qk_nope_head_dim": 128,
    "mla_scale_q_lora": True, "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
    "n_routed_experts": 512, "max_position_embeddings": 131072, "rms_norm_eps": 1e-05,
    "rope_theta": 10000000, "attention_method": "MLA", "zero_expert_num": 256,
    "zero_expert_type": "identity", "moe_topk": 12,
}
#: The three keys ``reduced`` lists, as this chip holds them.
CUT = {"num_layers": 4, "n_routed_experts": 16, "vocab_size": 16384}


def test_the_configuration_holds_the_sources_keys_and_states_its_cuts():
    assert CONFIG["published"] == SOURCE
    # At the top level too, under the same keys, but for the three cuts.
    assert {k: CONFIG[k] for k in SOURCE} == {**SOURCE, **CUT}
    assert set(CONFIG["reduced"]) == set(CUT)
    assert CONFIG["published_counts"] == {k: SOURCE[k] for k in CUT}
    assert CONFIG["program"] == {
        "max_seq_len": 8192, "num_layers": 4, "experts_held": 16, "expert_first": 0,
        "vocab_rows": 16384}
    assert CONFIG["precision"] == {
        "params": "bfloat16", "compute": "bfloat16", "control": "fp8"}
    assert CONFIG["deployment"].startswith("rank 0 of 32 chips that share each layer")
    entry = {c["name"]: c for c in manifest.benchmark()["configs"]}["longcat-flash-omni"]
    assert entry["source"] == CONFIG["source"] and set(entry["reduced"]) == set(CUT)
    # No width is cut: the share's sizes are the source's but for the three.
    sizes = family.sizes(CONFIG)
    assert {k: sizes[k] for k in family.KEYS if k != "num_layers"} == {
        k: SOURCE[k] for k in family.KEYS if k != "num_layers"}
    assert family.token_vocab(CONFIG) == 16384 and family.max_len(CONFIG) == 8192


def test_a_configuration_the_program_does_not_build_is_refused():
    other = json.loads(json.dumps(CONFIG))
    other["published"]["zero_expert_type"] = "copy"
    with pytest.raises(ValueError, match="zero_expert_type"):
        family.sizes(other)
    two = json.loads(json.dumps(CONFIG))
    two["n_routed_experts"] = 8
    with pytest.raises(ValueError, match="state two shares"):
        family.sizes(two)


def test_parameter_counts_by_hand():
    D, H = 6144, 64
    per = family.param_counts(CONFIG)
    assert per["mla"] == (D * 1536 + 1536 + 1536 * H * 192 + D * 576 + 512
                          + 512 * H * 256 + H * 128 * D)
    assert per["mla"] == pytest.approx(90.57e6, rel=0.0005)      # the issue's counts
    assert per["dense"] == 3 * D * 12288 == pytest.approx(226.49e6, rel=0.0005)
    assert per["router"] == 768 * D + 768 == pytest.approx(4.72e6, rel=0.001)
    assert per["expert"] == 3 * D * 2048 == pytest.approx(37.75e6, rel=0.0005)
    assert per["layer"] == pytest.approx(638.9e6, rel=0.0005)
    share = family.share_counts(CONFIG)
    assert share["non_expert"] == 4 * per["layer"] + 2 * 16384 * D + D
    assert share["experts"] == 4 * 16 * per["expert"] == pytest.approx(2415.9e6, rel=0.0005)
    total = share["non_expert"] + share["experts"]
    assert total == pytest.approx(5172.8e6, rel=0.0005) and 2 * total == pytest.approx(10.35e9, rel=0.001)
    # A position leaves 576 values a sub-layer: 9,216 B over the 8; expanded
    # keys and values would be 327,680 B.
    assert share["cache_bytes_per_position"] == 9216
    assert 8 * 64 * (192 + 128) * 2 == 327680
    # The whole model by the same counts: 560.7 B, 27.9 B of them active.
    whole = 28 * (per["layer"] + 512 * per["expert"]) + 2 * 131072 * D + D
    assert whole == pytest.approx(560.7e9, rel=0.001)
    active = 28 * (per["layer"] + 8 * per["expert"]) + 2 * 131072 * D
    assert active == pytest.approx(27.9e9, rel=0.01)
    # The seeded tree has exactly these leaves.
    import jax

    from benchmarks.reference import weights

    cfg, tree_fn = family.build(CONFIG)
    shapes = jax.eval_shape(tree_fn, weights.base_key(1))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) == total
    assert {str(a.dtype) for a in jax.tree.leaves(shapes)} == {"bfloat16"}
    assert shapes["layer_3"]["moe"]["gate"].shape == (16, 6144, 2048)
    assert cfg.share.n_experts == 512 and cfg.share.held == 16 and cfg.latent == 576


def test_decode_step_bytes_is_a_floor_with_no_expert_in_it():
    per = family.param_counts(CONFIG)
    rows = 100_000.0
    want = (4 * per["layer"] + 16384 * 6144 + 6144 + 32 * 6144) * 2 + rows * 9216
    assert family.decode_step_bytes(CONFIG, slots=32, cache_rows=rows) == want
    # 5.31 GB of parameters whatever the routing, 6.5 ms at 819 GB/s.
    assert family.decode_step_bytes(CONFIG, slots=32, cache_rows=0) == pytest.approx(5.31e9, rel=0.002)


def test_expert_call_bytes_and_operations_by_hand():
    assert family.expert_call_bytes(CONFIG, 1, 0) == 3 * 6144 * 2048 * 2 == pytest.approx(75.5e6, rel=0.001)
    assert family.expert_call_bytes(CONFIG, 4.5, 5.0) == 4.5 * 75497472 + 5 * 6144 * 6
    assert family.expert_call_flops(CONFIG, 128) == 128 * 2 * 3 * 6144 * 2048
    # Memory-bound until an expert has some 270 rows.
    rows = 273
    assert family.expert_call_flops(CONFIG, rows) / 197e12 == pytest.approx(
        family.expert_call_bytes(CONFIG, 1, rows) / 819e9, rel=0.05)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_fp8_control_fails_the_served_gap_and_bf16_passes(seed):
    """At the rehearsal's size (the router as published: 768 outputs, 12
    choices), over tokens that the reference computed in each precision
    puts first: in bfloat16 (what the program computes in) the widest gap
    reads 0.009-0.07 over five seeds, in fp8 0.45-0.59; the tiny limit of
    0.2 stands at nearly three times the first's largest and under half the
    second's smallest.  (With two dozen experts a choice weighs 0.9, not 0.06, and
    one near-tie that bfloat16 turns reads 0.93 beside fp8's 1.38.)"""
    cell = rehearse.shrink(manifest.Cell(CELL))
    rng = np.random.default_rng(seed)
    sample = [(rng.integers(0, 250, size=20).tolist(), rng.integers(0, 250, size=100).tolist())
              for _ in range(3)]
    limit = cell.traffic["correct"]["limits"]["widest_gap"]
    sound = serve_cell.widest_gap(cell.config, seed, sample, "bfloat16", "mode")
    control = serve_cell.widest_gap(cell.config, seed, sample, "fp8", "mode")
    assert sound["widest_gap"] <= limit < control["widest_gap"], (sound, control)


def _evidence(ops, modules, counters=None):
    tr = {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
          "spans": [(0.0, 10.0, "bench.window")], "host": []}
    return {"trace": tr, "cell": manifest.Cell(CELL), "counters": counters,
            "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}}


def test_the_expert_readers_on_a_made_up_trace():
    kernel = "moe_grouped_ffn.3 f32[896,6144]"
    # Two steps of four kernels of 0.4 ms, one chunk of three of 2 ms.
    ops = [(1.0 + 0.02 * i, 1.0 + 0.02 * i + 0.0004, kernel) for i in range(4)]
    ops += [(1.2 + 0.02 * i, 1.2 + 0.02 * i + 0.0004, kernel) for i in range(4)]
    ops += [(2.0 + 0.05 * i, 2.0 + 0.05 * i + 0.002, kernel) for i in range(3)]
    ops.append((3.0, 3.5, "fusion.1 f32[32,16384]"))
    modules = [(1.0, 1.1, "jit_step_fn(1)"), (1.2, 1.3, "jit_step_fn(1)"),
               (2.0, 2.2, "jit_prefill_fn(2)"), (3.0, 3.5, "jit_other(3)")]
    # Over the window: 1,000 calls, 600 of them the chunks' with all 16
    # experts touched and 20 rows; the steps' 400 touch 2 with 2.5 rows.
    start = {"decode_model_moe_calls": 100, "decode_model_moe_experts_touched": 300,
             "decode_model_moe_choices_held": 400, "decode_model_moe_choices": 20000,
             "decode_model_moe_choices_zero": 6000, "decode_model_moe_chunk_calls": 50,
             "decode_model_moe_chunk_experts_touched": 200,
             "decode_model_moe_chunk_choices_held": 300}
    end = {"decode_model_moe_calls": 1100, "decode_model_moe_experts_touched": 10700,
           "decode_model_moe_choices_held": 13400, "decode_model_moe_choices": 404000,
           "decode_model_moe_choices_zero": 134000, "decode_model_moe_chunk_calls": 650,
           "decode_model_moe_chunk_experts_touched": 9800,
           "decode_model_moe_chunk_choices_held": 12300}
    ev = _evidence(ops, modules, {"start": start, "end": end})
    in_module = manifest.reader("trace_op_ms_in_module")
    assert in_module(ev, op="moe_grouped_ffn", module="jit_step_fn") == pytest.approx(4 * 0.4)
    assert in_module(ev, op="moe_grouped_ffn", module="jit_prefill_fn") == pytest.approx(3 * 2.0)
    assert in_module(ev, op="moe_grouped_ffn", module="jit_other") is None
    assert in_module(ev, op="moe_grouped_ffn", module="jit_absent") is None
    args = manifest.layer_metric("expert_roofline_share")["args"]
    share = manifest.reader("expert_roofline")(ev, **args)
    # The traced stretch's 8 step calls and 3 chunk calls, each at its own
    # program's means over the window.
    least = (8 * family.expert_call_bytes(CONFIG, 2.0, 2.5)
             + 3 * family.expert_call_bytes(CONFIG, 16.0, 20.0)) / 819e9
    assert share == pytest.approx(100 * least / (8 * 0.0004 + 3 * 0.002)) and 0 < share < 100
    # A stretch with no chunk in it reads the steps' calls against the
    # steps' least, not against the window's mix.
    steps_only = _evidence(ops[:8], modules[:2], {"start": start, "end": end})
    assert manifest.reader("expert_roofline")(steps_only, **args) == pytest.approx(
        100 * family.expert_call_bytes(CONFIG, 2.0, 2.5) / 819e9 / 0.0004)
    for name, want in (("held_choice_share", 100 * 13000 / 384000),
                       ("zero_choice_share", 100 * 128000 / 384000),
                       ("experts_touched_per_call", 10.4)):
        spec = manifest.layer_metric(name)
        assert manifest.reader(spec["reader"])(ev, **spec["args"]) == pytest.approx(want)
    # A program without the kernel or the counters (the parent's): nothing.
    none = _evidence(ops[-1:], modules, {"start": {}, "end": {}})
    assert in_module(none, op="moe_grouped_ffn", module="jit_step_fn") is None
    assert manifest.reader("expert_roofline")(none, **args) is None
    for name in ("held_choice_share", "zero_choice_share", "experts_touched_per_call"):
        spec = manifest.layer_metric(name)
        assert manifest.reader(spec["reader"])(none, **spec["args"]) is None
