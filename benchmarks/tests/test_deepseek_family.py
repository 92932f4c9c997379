"""The deepseek family's counts beside their expected values, its
configuration against the source's keys, its control at the rehearsal's
size, and its cell's metric files on made-up counters."""

import json

import numpy as np
import pytest

from benchmarks import rehearse
from benchmarks.harness import manifest, serve_cell

family = manifest.family("deepseek", "serve")
CELL = "deepseek-v2-serve-gen"
CONFIG = manifest.Cell(CELL).config

#: The source's keys (huggingface.co/deepseek-ai/DeepSeek-V2 config.json,
#: as the catalog of public architectures holds them).
SOURCE = {
    "attention_bias": False, "first_k_dense_replace": 1, "hidden_act": "silu",
    "hidden_size": 5120, "intermediate_size": 12288, "kv_lora_rank": 512,
    "max_position_embeddings": 163840, "model_type": "deepseek_v2",
    "moe_intermediate_size": 1536, "moe_layer_freq": 1, "n_group": 8,
    "n_routed_experts": 160, "n_shared_experts": 2, "norm_topk_prob": False,
    "num_attention_heads": 128, "num_experts_per_tok": 6, "num_hidden_layers": 60,
    "num_key_value_heads": 128, "q_lora_rank": 1536, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_scaling": {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
        "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096, "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 16, "scoring_func": "softmax",
    "seq_aux": True, "tie_word_embeddings": False, "topk_group": 3,
    "topk_method": "group_limited_greedy", "v_head_dim": 128, "vocab_size": 102400,
}
#: The three keys ``reduced`` lists, as this chip holds them.
CUT = {"num_hidden_layers": 8, "n_routed_experts": 20, "vocab_size": 12800}


def test_the_configuration_holds_the_sources_keys_and_states_its_cuts():
    assert CONFIG["published"] == SOURCE
    # At the top level too, under the same keys, but for the three cuts.
    assert {k: CONFIG[k] for k in SOURCE} == {**SOURCE, **CUT}
    assert set(CONFIG["reduced"]) == set(CUT)
    assert CONFIG["published_counts"] == {k: SOURCE[k] for k in CUT}
    assert CONFIG["program"] == {
        "max_seq_len": 4096, "num_hidden_layers": 8, "experts_held": 20,
        "expert_first": 0, "vocab_rows": 12800}
    assert CONFIG["precision"] == {
        "params": "bfloat16", "compute": "bfloat16", "control": "fp8"}
    assert CONFIG["deployment"].startswith(
        "rank 0 of the 8 devices that share each layer, one routing group a device")
    assert len(CONFIG["deployment"]) > 0 and len(CONFIG["source"]) < 200
    entry = {c["name"]: c for c in manifest.benchmark()["configs"]}["deepseek-v2"]
    assert entry["source"] == CONFIG["source"] and set(entry["reduced"]) == set(CUT)
    # No width is cut: the share's sizes are the source's but for the depth.
    sizes = family.sizes(CONFIG)
    assert {k: sizes[k] for k in family.KEYS if k != "num_hidden_layers"} == {
        k: SOURCE[k] for k in family.KEYS if k != "num_hidden_layers"}
    assert {k: sizes[f"rope_{k}"] for k in family.ROPE_KEYS} == {
        k: SOURCE["rope_scaling"][k] for k in family.ROPE_KEYS}
    assert family.token_vocab(CONFIG) == 12800 and family.max_len(CONFIG) == 4096
    # The held range is one whole routing group of the published 8.
    assert sizes["experts_held"] == SOURCE["n_routed_experts"] // SOURCE["n_group"]


def test_a_configuration_the_program_does_not_build_is_refused():
    other = json.loads(json.dumps(CONFIG))
    other["published"]["scoring_func"] = "sigmoid"
    with pytest.raises(ValueError, match="scoring_func"):
        family.sizes(other)
    linear = json.loads(json.dumps(CONFIG))
    linear["published"]["rope_scaling"]["type"] = "linear"
    with pytest.raises(ValueError, match="rope_scaling.type"):
        family.sizes(linear)
    two = json.loads(json.dumps(CONFIG))
    two["n_routed_experts"] = 40
    with pytest.raises(ValueError, match="state two shares"):
        family.sizes(two)
    # A held range across a group boundary is no device's share.
    split = json.loads(json.dumps(CONFIG))
    split["program"]["expert_first"] = 10
    with pytest.raises(ValueError, match="not whole groups"):
        family.build(split)


def test_parameter_counts_by_hand():
    D, H = 5120, 128
    per = family.param_counts(CONFIG)
    assert per["mla"] == (D * 1536 + 1536 + 1536 * H * 192 + D * 576 + 512
                          + 512 * H * 256 + H * 128 * D) == 149_227_520
    assert per["shared"] == 3 * D * 3072 == 47_185_920
    assert per["router"] == 160 * D == 819_200
    assert per["expert"] == 3 * D * 1536 == 23_592_960
    assert per["moe_layer"] == 197_242_880
    assert per["dense_layer"] == per["mla"] + 3 * D * 12288 + 2 * D == 337_981_440
    share = family.share_counts(CONFIG)
    assert share["non_expert"] == 337_981_440 + 7 * 197_242_880 + 2 * 12800 * D + D
    assert share["experts"] == 7 * 20 * 23_592_960
    total = share["non_expert"] + share["experts"]
    assert total == 5_152_773_120 and 2 * total == pytest.approx(10.31e9, rel=0.001)
    assert 2 * share["non_expert"] == pytest.approx(3.70e9, rel=0.002)
    assert 2 * share["experts"] == pytest.approx(6.61e9, rel=0.001)
    # A position leaves 576 values a layer: 9,216 B over the 8; expanded
    # keys and values would be 128 x 320 = 40,960 values a layer.
    assert share["cache_bytes_per_position"] == 9216
    assert H * (192 + 128) == 40960
    # The whole model by the same counts: 235.74 B, 21.4 B of them active.
    whole = 59 * (per["moe_layer"] + 160 * per["expert"]) + per["dense_layer"] + 2 * 102400 * D + D
    assert whole == pytest.approx(235.74e9, rel=0.0001)
    active = 59 * (per["moe_layer"] + 6 * per["expert"]) + per["dense_layer"] + 2 * 102400 * D
    assert active == pytest.approx(21.4e9, rel=0.005)
    # The seeded tree has exactly these leaves.
    import jax

    from benchmarks.reference import weights

    cfg, tree_fn = family.build(CONFIG)
    shapes = jax.eval_shape(tree_fn, weights.base_key(1))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) == total
    assert {str(a.dtype) for a in jax.tree.leaves(shapes)} == {"bfloat16"}
    assert shapes["layer_7"]["moe"]["gate"].shape == (20, 5120, 1536)
    assert "ffn" in shapes["layer_0"] and "moe" not in shapes["layer_0"]
    assert cfg.share.n_experts == 160 and cfg.share.held == 20 and cfg.latent == 576
    assert (cfg.share.n_group, cfg.share.top_groups, cfg.share.top_k) == (8, 3, 6)
    assert cfg.softmax_scale == pytest.approx(0.11472, rel=1e-4)


def test_decode_step_bytes_is_a_floor_with_no_routed_expert_in_it():
    rows = 100_000.0
    want = (337_981_440 + 7 * 197_242_880 + 12800 * 5120 + 5120 + 64 * 5120) * 2 + rows * 9216
    assert family.decode_step_bytes(CONFIG, slots=64, cache_rows=rows) == want
    # 3.57 GB of parameters whatever the routing, 4.4 ms at 819 GB/s.
    assert family.decode_step_bytes(CONFIG, slots=64, cache_rows=0) == pytest.approx(3.57e9, rel=0.002)


def test_expert_call_bytes_and_operations_by_hand():
    assert family.expert_call_bytes(CONFIG, 1, 0) == 3 * 5120 * 1536 * 2 == pytest.approx(47.19e6, rel=0.001)
    assert family.expert_call_bytes(CONFIG, 18.0, 48.0) == 18 * 47185920 + 48 * 5120 * 6
    assert family.expert_call_flops(CONFIG, 1) == pytest.approx(47.19e6, rel=0.001)
    # Memory-bound until an expert has some 270 rows.
    rows = 273
    assert family.expert_call_flops(CONFIG, rows) / 197e12 == pytest.approx(
        family.expert_call_bytes(CONFIG, 1, rows) / 819e9, rel=0.05)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_fp8_control_fails_the_served_gap_and_bf16_passes(seed):
    """At the rehearsal's size (16 experts in 4 groups, 2 choices), over
    tokens that the reference computed in each precision puts first: in
    bfloat16 (what the program computes in) the widest gap reads 0.002-0.011
    over eight seeds, in fp8 0.39-0.86; the tiny limit of 0.1 stands at nine
    times the first's largest and at a quarter of the second's smallest.
    (With an expert's ``down`` NOT scaled like the other writes into the
    residual stream, one turned choice read 0.249 beside fp8's 0.44.)"""
    cell = rehearse.shrink(manifest.Cell(CELL))
    rng = np.random.default_rng(seed)
    sample = [(rng.integers(0, 250, size=20).tolist(), rng.integers(0, 250, size=100).tolist())
              for _ in range(3)]
    limit = cell.traffic["correct"]["limits"]["widest_gap"]
    sound = serve_cell.widest_gap(cell.config, seed, sample, "bfloat16", "mode")
    control = serve_cell.widest_gap(cell.config, seed, sample, "fp8", "mode")
    assert sound["widest_gap"] <= limit < control["widest_gap"], (sound, control)


def test_the_cells_metric_files_on_made_up_counters():
    cell = manifest.Cell(CELL)
    names = {p["name"] for p in cell.per_layer}
    assert {"batch_group_reach_share", "batch_held_choice_share",
            "batch_experts_touched_per_call", "batch_expert_roofline_share",
            "batch_decode_roofline_share", "batch_prefill_chunk_ms",
            "batch_prefill_step_share", "batch_expert_ffn_ms_per_step",
            "batch_expert_ffn_ms_per_chunk", "batch_window_compiles",
            "batch_decode_step_ms", "batch_device_idle_share"} <= names
    assert {e["name"] for e in cell.end_to_end} == {"served_tokens_per_s", "setup_s"}
    # 1,000 live token-layers: 6,000 choices, 375 tokens reach this device
    # with 750 choices, 7 calls a step touch 18 of 20 each.
    start = {"decode_model_moe_choices": 600, "decode_model_moe_tokens_reaching": 40,
             "decode_model_moe_choices_held": 80, "decode_model_moe_experts_touched": 180,
             "decode_model_moe_calls": 10}
    end = {"decode_model_moe_choices": 6600, "decode_model_moe_tokens_reaching": 415,
           "decode_model_moe_choices_held": 830, "decode_model_moe_experts_touched": 1440,
           "decode_model_moe_calls": 80}
    ev = {"counters": {"start": start, "end": end}}
    for name, want in (("batch_group_reach_share", 37.5), ("batch_held_choice_share", 12.5),
                       ("batch_experts_touched_per_call", 18.0)):
        spec = manifest.layer_metric(name)
        assert manifest.reader(spec["reader"])(ev, **spec["args"]) == pytest.approx(want)
        # A program without the counters (the parent's): nothing, and no raise.
        none = {"counters": {"start": {}, "end": {}}}
        assert manifest.reader(spec["reader"])(none, **spec["args"]) is None
