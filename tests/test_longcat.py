"""models/longcat.py against the plain float32 reference
(benchmarks/reference/longcat_ref.py) on seeded weights, at a tiny width:
two double layers, 8 of 16 routed experts held beside 8 zero-compute ones,
half the vocabulary.

The tolerances and their reasons.  Both sides compute with the same
bfloat16-rounded leaves.  In the FLOAT32 tests the program holds them as
float32 and multiplies in float32, as the reference does, so nothing but
the order of the sums differs and a routing flip is no excuse: the logits
(largest about 4, std 1) agree to ``TOL_F32`` = 2e-3, some tens of times
what is read (under 1e-4) and hundreds of times under what the reference
with fp8 products reads (over 0.5).  The chunk-then-step test holds the same
tolerance against the same full forward, though its attention is the
ABSORBED form over a latent cache and the reference's the expanded one.  In
the BFLOAT16 test the program multiplies bfloat16 operands, and a choice at
a near-tie now and then falls the other way than in the float32 reference.
At this size that is no small matter - 24 experts make a chosen score some
0.04 and a zero-compute choice's weight a quarter of ``u`` - so the largest
gap of a run IS a flipped choice's (0.45-1.07 over four seeds, and the
reference computed with bfloat16 operands reads the same 0.14-1.06, flip
for flip), and the test holds the bulk instead: the median over positions
of the largest gap reads 0.015-0.018 and is bound by ``TOL_BF16`` = 0.05,
where fp8 products read 0.31-0.38; at most 8 of the 96 positions (3 were
read) may lie beyond 0.15, where fp8 has 80 or more.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.reference import longcat_ref, weights  # noqa: E402
from distributed_tensorflow_examples_tpu.models import layers, longcat  # noqa: E402

C_TINY = dict(
    vocab_size=500, hidden_size=64, ffn_hidden_size=128, expert_ffn_hidden_size=32,
    num_layers=2, num_attention_heads=4, kv_lora_rank=32, q_lora_rank=48,
    qk_rope_head_dim=8, qk_nope_head_dim=16, v_head_dim=16, mla_scale_q_lora=True,
    mla_scale_kv_lora=True, routed_scaling_factor=6, n_routed_experts=16,
    zero_expert_num=8, moe_topk=4, rms_norm_eps=1e-5, rope_theta=1e7,
    experts_held=8, expert_first=4, vocab_rows=250, init_std=0.125,
)
SHAPE = {k: v for k, v in C_TINY.items() if k != "init_std"}
CFG32 = longcat.Config(**SHAPE, param_dtype="float32")
CFG16 = longcat.Config(**SHAPE, param_dtype="bfloat16")
TOL_F32, TOL_BF16 = 2e-3, 0.05
SEED = 2**31 + 5  # beyond 31 bits, as the driver's seeds are


@pytest.fixture(scope="module")
def params16():
    return jax.jit(lambda k: longcat_ref.tree(C_TINY, k))(weights.base_key(SEED))


@pytest.fixture(scope="module")
def params32(params16):
    return jax.tree.map(lambda a: a.astype(jnp.float32), params16)


@pytest.fixture(scope="module")
def tokens():
    return np.asarray(jax.random.randint(jax.random.key(4), (2, 48), 0, 250))


@pytest.fixture(scope="module")
def reference(tokens):
    return longcat_ref.logits(C_TINY, SEED, tokens)


def test_config_tree_and_cache(params16):
    assert longcat.Config().latent == 576 and longcat.Config().held == 512
    own = jax.eval_shape(lambda: longcat.init(CFG16, jax.random.key(0)))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), own) == jax.tree.map(
        lambda a: (a.shape, a.dtype), params16)
    cache = longcat.init_cache(CFG16, 3, 32)
    assert cache["layer_1"]["attn_0"].shape == (3, 32, 40)
    assert set(cache["counters"]) == {
        "moe_choices", "moe_choices_held", "moe_choices_zero",
        "moe_experts_touched", "moe_calls",
        "moe_chunk_choices_held", "moe_chunk_experts_touched", "moe_chunk_calls"}
    with pytest.raises(ValueError, match="run past"):
        longcat.Config(n_routed_experts=16, experts_held=8, expert_first=12)


def test_rotary_pairs_are_interleaved_and_keep_the_dot_product_relative():
    cos, sin = layers.rope_angles(jnp.array([0, 3, 7]), 8, 1e4)
    x = jax.random.normal(jax.random.key(0), (3, 8))
    y = layers.rope_interleaved(x, cos, sin)
    np.testing.assert_allclose(y[0], x[0], atol=1e-6)  # position 0: no turn
    # Pair 0 is elements (0, 1), turned by pos x 1.
    a = 3.0
    np.testing.assert_allclose(
        y[1, :2], [x[1, 0] * np.cos(a) - x[1, 1] * np.sin(a),
                   x[1, 0] * np.sin(a) + x[1, 1] * np.cos(a)], rtol=1e-5)
    # q.k depends on the positions' difference only.
    q, k = x[0], x[1]
    rot = lambda v, p: layers.rope_interleaved(v, *layers.rope_angles(jnp.array(p), 8, 1e4))
    assert float(rot(q, 9) @ rot(k, 5)) == pytest.approx(float(rot(q, 14) @ rot(k, 10)), rel=1e-4)


def test_apply_against_the_references_full_forward(params32, tokens, reference):
    got = np.asarray(jax.jit(lambda p, t: longcat.apply(CFG32, p, t))(params32, tokens))
    assert got.shape == reference.shape == (2, 48, 250)
    assert 0.5 < reference.std() < 2 and np.abs(reference).max() > 3
    assert np.abs(got - reference).max() < TOL_F32
    fp8 = longcat_ref.logits(C_TINY, SEED, tokens, "fp8")
    assert np.abs(fp8 - reference).max() > 0.5


def test_apply_in_bfloat16_stays_within_its_bound(params16, tokens, reference):
    got = np.asarray(jax.jit(lambda p, t: longcat.apply(CFG16, p, t))(params16, tokens))
    gap = np.abs(got - reference).max(axis=-1).ravel()
    assert np.median(gap) < TOL_BF16 and (gap > 0.15).sum() <= 8
    fp8 = longcat_ref.logits(C_TINY, SEED, tokens, "fp8")
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
    rows that are not live; every step's logits are the full forward's at
    that position, and the counters count the live rows alone."""
    pre = jax.jit(lambda p, c, t, s, o, n: longcat.prefill_chunk(CFG32, p, c, t, s, o, n))
    step = jax.jit(lambda p, c, t, pos, live: longcat.decode_step_batch(CFG32, p, c, t, pos, live))
    cache = longcat.init_cache(CFG32, 3, 64)
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
    # The chunk calls the expert layer of every layer but the last.
    assert counts["moe_calls"] == chunks * (CFG32.num_layers - 1) + steps * CFG32.num_layers
    assert counts["moe_choices"] == 4 * (
        (prompt_len - 1) * (CFG32.num_layers - 1) + steps * CFG32.num_layers)
    assert 0 < counts["moe_choices_held"] < counts["moe_choices"]
    assert 0 < counts["moe_choices_zero"] < counts["moe_choices"]
    assert 0 < counts["moe_experts_touched"] <= counts["moe_choices_held"]
    # What the chunks did is counted a second time, apart.
    assert counts["moe_chunk_calls"] == chunks * (CFG32.num_layers - 1)
    assert counts["moe_chunk_choices_held"] <= counts["moe_choices_held"]
    assert (counts["moe_chunk_experts_touched"] > 0) == (prompt_len > 1)


def test_the_cache_holds_latents_and_a_row_is_the_positions_own(params32, tokens):
    """What a step leaves at a position is 40 values (32 + 8), the same
    whether the chunk or the step wrote them."""
    pre = jax.jit(lambda p, c, t, s, o, n: longcat.prefill_chunk(CFG32, p, c, t, s, o, n))
    step = jax.jit(lambda p, c, t, pos, live: longcat.decode_step_batch(CFG32, p, c, t, pos, live))
    row = tokens[1]
    by_chunk = pre(params32, longcat.init_cache(CFG32, 1, 16), row[:8], 0, 0, 8)
    by_step = longcat.init_cache(CFG32, 1, 16)
    for pos in range(8):
        _, by_step = step(params32, by_step, row[pos:pos + 1], np.array([pos], np.int32),
                          np.array([True]))
    for i in range(CFG32.num_layers):
        for j in (0, 1):
            a = np.asarray(by_chunk[f"layer_{i}"][f"attn_{j}"][0, :8])
            b = np.asarray(by_step[f"layer_{i}"][f"attn_{j}"][0, :8])
            assert a.shape == (8, 40)
            np.testing.assert_allclose(a, b, atol=TOL_F32)


def test_generate_is_the_references_greedy_continuation(params16, tokens):
    """Tokens are compared through the reference's logits, not one for one
    (with seeded weights the largest logit changes on rounding): each
    generated token's reference logit lies within 0.15 of the best (a
    flipped choice's gap apart: the module docstring; none here)."""
    out = np.asarray(longcat.generate(CFG16, params16, tokens[:, :9], max_new_tokens=6))
    assert out.shape == (2, 15) and np.array_equal(out[:, :9], tokens[:, :9])
    ref = longcat_ref.logits(C_TINY, SEED, out)
    for b in range(2):
        for t in range(8, 14):
            assert ref[b, t].max() - ref[b, t, out[b, t + 1]] < 0.15
