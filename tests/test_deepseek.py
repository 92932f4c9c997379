"""models/deepseek.py against the plain float32 reference
(benchmarks/reference/deepseek_ref.py) on seeded weights, at a tiny width:
a dense layer and two expert layers, 32 routed experts in 4 groups of which
a token keeps 2, group 1 (experts 8-15) held, two shared experts, half the
vocabulary, and rotary positions scaled from an ``original_max`` of 16 so
that the sequences' 48 positions lie well past it.

The tolerances and their reasons.  Both sides compute with the same
bfloat16-rounded leaves.  In the FLOAT32 tests the program holds them as
float32 and multiplies in float32, as the reference does, so nothing but
the order of the sums differs and a routing flip is no excuse: the logits
(largest about 4, std 1) agree to ``TOL_F32`` = 2e-3, hundreds of times
what is read (4e-6) and hundreds of times under what the reference with fp8
products reads (over 0.5).  The chunk-then-step test holds the same
tolerance against the same full forward, though its attention is the
ABSORBED form over a latent cache and the reference's the expanded one
written the source's way (rope halves, not interleaved pairs).  In the
BFLOAT16 test the program multiplies bfloat16 operands, and a choice - or a
whole third group - at a near-tie now and then falls the other way than in
the float32 reference; with 32 experts a choice weighs 0.5 here, not the
0.1 of the real size, so the largest gap of a run may be a flip's (0.04,
0.05, 0.87, 0.90 over four seeds) and the test holds the bulk instead: the
median over positions of the largest gap reads 0.019-0.021 and is bound by
``TOL_BF16`` = 0.05, where fp8 products read 0.44-0.56; at most 8 of the 96
positions (0-1 were read) may lie beyond 0.15, where fp8 has all 96.  That the SCALED rotary pairs
matter at these positions is tests/test_mla.py's to show.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.reference import deepseek_ref, weights  # noqa: E402
from distributed_tensorflow_examples_tpu.models import deepseek  # noqa: E402

C_TINY = dict(
    vocab_size=500, hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
    num_hidden_layers=3, first_k_dense_replace=1, moe_layer_freq=1,
    num_attention_heads=4, kv_lora_rank=32, q_lora_rank=48, qk_rope_head_dim=16,
    qk_nope_head_dim=16, v_head_dim=16, n_routed_experts=32, n_shared_experts=2,
    n_group=4, topk_group=2, num_experts_per_tok=4, routed_scaling_factor=16.0,
    rms_norm_eps=1e-6, rope_theta=100.0, rope_factor=8.0,
    rope_original_max_position_embeddings=16, rope_beta_fast=1.0, rope_beta_slow=0.05,
    rope_mscale=0.707, rope_mscale_all_dim=0.707,
    experts_held=8, expert_first=8, vocab_rows=250, init_std=0.125, router_std_factor=0.25,
)
SHAPE = {k: v for k, v in C_TINY.items() if k not in ("init_std", "router_std_factor")}
CFG32 = deepseek.Config(**SHAPE, param_dtype="float32")
CFG16 = deepseek.Config(**SHAPE, param_dtype="bfloat16")
TOL_F32, TOL_BF16 = 2e-3, 0.05
SEED = 2**31 + 5  # beyond 31 bits, as the driver's seeds are


@pytest.fixture(scope="module")
def params16():
    return jax.jit(lambda k: deepseek_ref.tree(C_TINY, k))(weights.base_key(SEED))


@pytest.fixture(scope="module")
def params32(params16):
    return jax.tree.map(lambda a: a.astype(jnp.float32), params16)


@pytest.fixture(scope="module")
def tokens():
    return np.asarray(jax.random.randint(jax.random.key(4), (2, 48), 0, 250))


@pytest.fixture(scope="module")
def reference(tokens):
    return deepseek_ref.logits(C_TINY, SEED, tokens)


@pytest.fixture(scope="module")
def fp8(tokens):
    return deepseek_ref.logits(C_TINY, SEED, tokens, "fp8")


def test_config_tree_and_cache(params16):
    full = deepseek.Config()
    assert full.latent == 576 and full.held == 160
    assert full.layer_kinds == ("dense",) + ("moe",) * 59
    assert full.softmax_scale == pytest.approx(0.11472, rel=1e-4)
    assert full.share.n_group == 8 and full.share.top_groups == 3 and full.share.n_zero == 0
    assert CFG16.layer_kinds == ("dense", "moe", "moe")
    assert deepseek.Config(num_hidden_layers=4, first_k_dense_replace=2,
                           moe_layer_freq=2).layer_kinds == ("dense", "dense", "moe", "dense")
    own = jax.eval_shape(lambda: deepseek.init(CFG16, jax.random.key(0)))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), own) == jax.tree.map(
        lambda a: (a.shape, a.dtype), params16)
    assert "bias" not in params16["layer_1"]["moe"]["router"]
    assert "moe" not in params16["layer_0"] and "ffn" not in params16["layer_1"]
    assert params16["layer_1"]["shared"]["gate"]["kernel"].shape == (64, 2 * 32)
    cache = deepseek.init_cache(CFG16, 3, 32)
    assert cache["layer_2"]["attn"].shape == (3, 32, 48) and len(cache) == 4
    assert set(cache["counters"]) == {
        "moe_choices", "moe_choices_held", "moe_experts_touched", "moe_calls",
        "moe_tokens_reaching",
        "moe_chunk_choices_held", "moe_chunk_experts_touched", "moe_chunk_calls"}
    with pytest.raises(ValueError, match="run past"):
        deepseek.Config(n_routed_experts=32, n_group=4, experts_held=16, expert_first=24)
    with pytest.raises(ValueError, match="not whole groups"):
        deepseek.Config(n_routed_experts=32, n_group=4, experts_held=8, expert_first=4)
    with pytest.raises(ValueError, match="mscale"):
        deepseek.Config(rope_mscale=1.0)


def test_apply_against_the_references_full_forward(params32, tokens, reference, fp8):
    got = np.asarray(jax.jit(lambda p, t: deepseek.apply(CFG32, p, t))(params32, tokens))
    assert got.shape == reference.shape == (2, 48, 250)
    assert 0.5 < reference.std() < 2 and np.abs(reference).max() > 3
    assert np.abs(got - reference).max() < TOL_F32
    assert np.abs(fp8 - reference).max() > 0.5


def test_apply_in_bfloat16_stays_within_its_bound(params16, tokens, reference, fp8):
    got = np.asarray(jax.jit(lambda p, t: deepseek.apply(CFG16, p, t))(params16, tokens))
    gap = np.abs(got - reference).max(axis=-1).ravel()
    assert np.median(gap) < TOL_BF16 and (gap > 0.15).sum() <= 8
    gap8 = np.abs(fp8 - reference).max(axis=-1).ravel()
    assert np.median(gap8) > 5 * TOL_BF16 and (gap8 > 0.15).sum() >= 80


@pytest.mark.parametrize(
    "prompt_len,chunk,floor",
    [
        # One width: the floor is over half the chunk.
        (21, 8, 128),   # three chunks, the last padded (20 = 8 + 8 + 4)
        (17, 16, 128),  # one whole chunk, none padded
        (10, 16, 128),  # one padded chunk
        (1, 8, 128),    # a one-token prompt: no chunk at all
        # The engine's widths, ``floor`` .. ``chunk``.
        (21, 16, 4),    # 20 = 16 + 4 in a chunk of 4: none padded
        (23, 16, 4),    # 22 = 16 + 6 in a chunk of 8
        (4, 16, 4),     # 3 in a chunk of 4, the narrowest
        (27, 8, 2),     # 26 = 8 + 8 + 8 + 2 in a chunk of 2
    ],
)
def test_prefill_by_chunks_then_absorbed_decode_against_the_full_forward(
    params32, tokens, reference, engine_chunks, prompt_len, chunk, floor,
):
    """A prompt enters slot 1 of a USED cache by chunks (as the engine cuts
    it: whole chunks, then the narrowest of its widths that holds the
    rest), then the tokens
    that follow are decoded through the latent cache one by one beside two
    rows that are not live, to position 39 (``original_max`` is 16); every
    step's logits are the full forward's at that position, and the counters
    count the live rows alone."""
    pre = jax.jit(lambda p, c, t, s, o, n: deepseek.prefill_chunk(CFG32, p, c, t, s, o, n))
    step = jax.jit(lambda p, c, t, pos, live: deepseek.decode_step_batch(CFG32, p, c, t, pos, live))
    cache = deepseek.init_cache(CFG32, 3, 64)
    cache = {k: jax.tree.map(lambda a: jnp.full(a.shape, 0.37, a.dtype), v)
             if k != "counters" else v for k, v in cache.items()}
    row = tokens[0]
    chunks = 0
    for off, n, width in engine_chunks(prompt_len - 1, chunk, floor):
        buf = np.zeros(width, np.int32)
        buf[:n] = row[off:off + n]
        cache = pre(params32, cache, buf, 1, off, n)
        chunks += 1
    worst = 0.0
    for pos in range(prompt_len - 1, 40):
        logits, cache = step(
            params32, cache, np.array([5, row[pos], 9], np.int32),
            np.array([3, pos, 0], np.int32), np.array([False, True, False]))
        worst = max(worst, float(np.abs(np.asarray(logits[1]) - reference[0, pos]).max()))
    assert worst < TOL_F32
    counts = {k: int(v) for k, v in cache["counters"].items()}
    steps = 40 - (prompt_len - 1)
    # Two expert layers; the chunk's last layer calls none.
    assert counts["moe_calls"] == chunks * 1 + steps * 2
    assert counts["moe_choices"] == 4 * ((prompt_len - 1) * 1 + steps * 2)
    assert 0 < counts["moe_choices_held"] < counts["moe_choices"]
    # A token that reaches this device brings 1 to 4 of its choices.
    assert 0 < counts["moe_tokens_reaching"] <= counts["moe_choices_held"]
    assert counts["moe_choices_held"] <= 4 * counts["moe_tokens_reaching"]
    assert counts["moe_tokens_reaching"] < counts["moe_choices"] // 4
    assert 0 < counts["moe_experts_touched"] <= counts["moe_choices_held"]
    # What the chunks did is counted a second time, apart.
    assert counts["moe_chunk_calls"] == chunks
    assert counts["moe_chunk_choices_held"] <= counts["moe_choices_held"]
    assert (counts["moe_chunk_experts_touched"] > 0) == (prompt_len > 1)


def test_the_cache_holds_latents_and_a_row_is_the_positions_own(params32, tokens):
    """What a step leaves at a position is 48 values (32 + 16), the same
    whether the chunk or the step wrote them."""
    pre = jax.jit(lambda p, c, t, s, o, n: deepseek.prefill_chunk(CFG32, p, c, t, s, o, n))
    step = jax.jit(lambda p, c, t, pos, live: deepseek.decode_step_batch(CFG32, p, c, t, pos, live))
    row = tokens[1]
    by_chunk = pre(params32, deepseek.init_cache(CFG32, 1, 16), row[:8], 0, 0, 8)
    by_step = deepseek.init_cache(CFG32, 1, 16)
    for pos in range(8):
        _, by_step = step(params32, by_step, row[pos:pos + 1], np.array([pos], np.int32),
                          np.array([True]))
    for i in range(CFG32.num_hidden_layers):
        a = np.asarray(by_chunk[f"layer_{i}"]["attn"][0, :8])
        b = np.asarray(by_step[f"layer_{i}"]["attn"][0, :8])
        assert a.shape == (8, 48)
        np.testing.assert_allclose(a, b, atol=TOL_F32)


def test_generate_is_the_references_greedy_continuation(params16, tokens):
    """Tokens are compared through the reference's logits, not one for one
    (with seeded weights the largest logit changes on rounding): each
    generated token's reference logit lies within 0.15 of the best (a
    flipped choice's gap apart: the module docstring; none here)."""
    out = np.asarray(deepseek.generate(CFG16, params16, tokens[:, :9], max_new_tokens=6))
    assert out.shape == (2, 15) and np.array_equal(out[:, :9], tokens[:, :9])
    ref = deepseek_ref.logits(C_TINY, SEED, out)
    for b in range(2):
        for t in range(8, 14):
            assert ref[b, t].max() - ref[b, t, out[b, t + 1]] < 0.15
