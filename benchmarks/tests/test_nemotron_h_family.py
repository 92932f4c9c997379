"""The nemotron_h family's counts beside their expected values, its
configuration against the source's keys, its control at the rehearsal's size,
and the two readers that come with it."""

import json

import numpy as np
import pytest

from benchmarks import rehearse
from benchmarks.harness import manifest, serve_cell

family = manifest.family("nemotron_h", "serve")
CELL = "nemotron3-super-serve-agent"
CONFIG = manifest.Cell(CELL).config
REDUCED = {"num_hidden_layers": (11, 88), "n_routed_experts": (128, 512),
           "vocab_size": (32768, 131072)}


def test_the_configuration_holds_the_sources_keys_but_for_the_share():
    pub = CONFIG["published"]
    assert (pub["model_type"], pub["hidden_size"], pub["moe_latent_size"],
            pub["num_experts_per_tok"], pub["mlp_hidden_act"]) == (
        "nemotron_h", 4096, 1024, 22, "relu2")
    # At the top level too, under the same keys, but for the three that
    # ``reduced`` lists: what a check of the file against the source compares.
    assert {k: CONFIG[k] for k in pub if k not in REDUCED} == {
        k: v for k, v in pub.items() if k not in REDUCED}
    for key, (here, published) in REDUCED.items():
        assert (CONFIG[key], pub[key], CONFIG["published_counts"][key]) == (
            here, published, published)
    assert CONFIG["reduced"] == list(REDUCED)
    assert pub["hybrid_override_pattern"][:11] == CONFIG["stage_pattern"] == "MEMEMEM*EME"
    assert CONFIG["program"] == {
        "max_seq_len": 32768, "held_layers": list(range(11)), "experts_held": 128,
        "expert_first": 0, "vocab_rows": 32768}
    assert CONFIG["precision"] == {
        "params": "bfloat16", "compute": "bfloat16", "control": "fp8"}
    assert "4 chips share each layer" in CONFIG["deployment"]
    assert any("multi-token prediction" in d for d in CONFIG["departures"])
    entry = {c["name"]: c for c in manifest.benchmark()["configs"]}[
        "nemotron-3-super-120b-a12b"]
    assert entry["source"] == CONFIG["source"] and entry["reduced"] == CONFIG["reduced"]
    assert family.token_vocab(CONFIG) == 32768 and family.max_len(CONFIG) == 32768


@pytest.mark.parametrize("where,key,value", [
    ("published", "mlp_hidden_act", "silu"), ("published", "n_group", 8),
    ("top", "n_routed_experts", 512), ("top", "num_hidden_layers", 88)])
def test_a_configuration_the_program_does_not_build_or_two_shares_are_refused(
        where, key, value):
    other = json.loads(json.dumps(CONFIG))
    (other["published"] if where == "published" else other)[key] = value
    with pytest.raises(ValueError, match=key):
        family.sizes(other)


def test_parameter_counts_by_hand():
    D, Di, Cd, H = 4096, 8192, 10240, 128
    per = family.param_counts(CONFIG)
    assert per["mamba"] == D + D * (Di + Cd + H) + 4 * Cd + Cd + 3 * H + Di + Di * D
    assert per["mamba"] == pytest.approx(109.64e6, rel=1e-4)   # the issue's counts
    assert per["attn"] == D + 2 * D * 32 * 128 + 2 * D * 2 * 128
    assert per["attn"] == pytest.approx(35.66e6, rel=1e-3)
    assert per["moe_layer"] == D + D * 512 + 512 + 2 * D * 1024 + 2 * D * 5376
    assert per["moe_layer"] == pytest.approx(54.53e6, rel=1e-4)
    assert per["expert"] == 2 * 1024 * 2688 == 5_505_024
    assert per["top"] == 2 * 32768 * D + D
    assert family.layer_counts(CONFIG) == {"M": 5, "E": 5, "*": 1}
    share = family.share_counts(CONFIG)
    total = share["non_expert"] + share["experts"]
    assert total == pytest.approx(4648e6, rel=1e-3)            # 9.30 GB in bfloat16
    assert share["cache_bytes_per_position"] == 1024
    assert share["state_bytes_per_slot"] == 5 * (128 * 64 * 128 + 3 * Cd) * 4 == 21_585_920
    # The whole model from the same shapes: the published 120 B.
    whole = (40 * per["mamba"] + 8 * per["attn"] + 40 * (per["moe_layer"] + 512 * per["expert"])
             + 2 * 131072 * D + D)
    assert whole == pytest.approx(120.67e9, rel=1e-3)
    # The seeded tree has exactly these leaves.
    import jax

    from benchmarks.reference import weights

    cfg, tree_fn = family.build(CONFIG)
    shapes = jax.eval_shape(tree_fn, weights.base_key(1))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) == total
    assert {str(a.dtype) for a in jax.tree.leaves(shapes)} == {"bfloat16"}
    assert cfg.share.held == 128 and cfg.share.activation == "relu2"


def test_the_bytes_and_operations_of_the_kernels_by_hand():
    per = family.param_counts(CONFIG)
    rows = 5000.0
    params = (5 * per["mamba"] + per["attn"] + 5 * per["moe_layer"]
              + 32768 * 4096 + 4096 + 32 * 4096)
    assert family.decode_step_bytes(CONFIG, slots=32, cache_rows=rows) == (
        params * 2 + rows * 1024)
    # An ungated expert is TWO matrices of 1024 x 2688.
    assert family.expert_call_bytes(CONFIG, 70, 100) == 70 * 5_505_024 * 2 + 100 * 1024 * 6
    assert family.expert_call_flops(CONFIG, 100) == 100 * 2 * 5_505_024
    H, P, N, G = 128, 64, 128, 8
    # The carried state each way, whatever the width: all that must cross HBM.
    assert family.ssd_chunk_bytes(CONFIG, 512) == family.ssd_chunk_bytes(CONFIG, 256) == (
        4 * 2 * H * P * N)
    assert family.ssd_chunk_bytes(CONFIG, 512) == pytest.approx(8.39e6, rel=0.01)
    # At 512 positions the operations bound the call, at 256 the state does.
    least = lambda w: (family.ssd_chunk_flops(CONFIG, w) / 197e12,
                       family.ssd_chunk_bytes(CONFIG, w) / 819e9)
    assert least(512)[0] > least(512)[1] > least(256)[0]
    assert least(512)[0] == pytest.approx(17.0e-6, rel=0.01)
    assert family.ssd_chunk_flops(CONFIG, 512) == 4 * 2 * (
        G * 128 * 128 * N + H * P * 128 * (128 + 2 * N))
    assert family.ssd_chunk_flops(CONFIG, 256) * 2 == family.ssd_chunk_flops(CONFIG, 512)
    assert family.state_step_bytes(CONFIG, 20) == 20 * 4 * (
        2 * H * P * N + 2 * H * P + 2 * G * N + H)
    assert family.state_step_bytes(CONFIG, 20) == pytest.approx(169e6, rel=0.01)


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


def _evidence(ops, modules, counters, records=()):
    tr = {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
          "spans": [(0.0, 10.0, "bench.window")], "host": []}
    return {"trace": tr, "cell": manifest.Cell(CELL), "counters": counters,
            "records": list(records), "w1": 100.0, "trace_s": 4.0,
            "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}}


def test_the_two_readers_on_a_made_up_trace():
    chunk = "mamba2_ssd_chunk.3 (f32[128,64,512],...)"
    step = "mamba2_state_step.2 (f32[32,64,128],...)"
    ops = [(1.0 + i * 0.01, 1.0 + i * 0.01 + 0.0005, chunk) for i in range(10)]
    ops += [(3.0 + i * 0.01, 3.0 + i * 0.01 + 0.0003, step) for i in range(15)]
    modules = [(1.0, 1.05, "jit_prefill_fn(1)"), (1.05, 1.1, "jit_prefill_fn(1)"),
               (3.0, 3.06, "jit_step_fn(2)"), (3.06, 3.2, "jit_step_fn(2)"),
               (3.2, 3.3, "jit_step_fn(2)")]
    # 40 calls at 512 and 10 at 256 in the window: a mean width of 460.8.
    counters = {
        "start": {"decode_model_ssd_calls": 5, "decode_model_ssd_positions": 2560},
        "end": {"decode_model_ssd_calls": 55, "decode_model_ssd_positions": 2560 + 23040},
    }
    # The trace holds device work from 1.0 to 3.3: a stretch of 2.3 s that ends
    # with the window at 100.0 on the host's clock.  The clients received 54
    # tokens in it, over three launches of the step: 18 live rows a step -
    # whatever they received before it.
    records = [{"times": [97.0 + 0.01 * i for i in range(60)]},
               {"times": [97.71 + 0.1 * i for i in range(20)]},
               {"times": [98.0 + 0.05 * i for i in range(34)]}]
    ev = _evidence(ops, modules, counters, records)
    args = lambda name: manifest.layer_metric(name)["args"]
    read = lambda name: manifest.reader(manifest.layer_metric(name)["reader"])(ev, **args(name))
    assert read("ssd_ms_per_chunk") == pytest.approx(5 * 0.5)
    assert read("state_step_ms_per_step") == pytest.approx(5 * 0.3)
    least = max(family.ssd_chunk_bytes(CONFIG, 460.8) / 819e9,
                family.ssd_chunk_flops(CONFIG, 460.8) / 197e12)
    assert read("ssd_roofline_share") == pytest.approx(100 * least / 0.0005)
    assert read("state_step_roofline_share") == pytest.approx(
        100 * (family.state_step_bytes(CONFIG, 18) / 819e9) / 0.0003)
    assert 0 < read("ssd_roofline_share") < 100 and 0 < read("state_step_roofline_share") < 100
    # A program without the kernels or the counters (the parent's): nothing
    # to read, and nothing raised.
    bare = _evidence([(3.0, 3.5, "fusion.1 f32[32,32768]")], modules,
                     {"start": {}, "end": {}}, records)
    for name in ("ssd_ms_per_chunk", "ssd_roofline_share", "state_step_ms_per_step",
                 "state_step_roofline_share"):
        spec = manifest.layer_metric(name)
        assert manifest.reader(spec["reader"])(bare, **spec["args"]) is None
