"""models/mla.py, the latent-attention sub-layer two models share, on its
own: its two forms against each other, its cache writes, the count of rows
a step reads - and the scaled (YaRN) rotary frequencies of models/layers.py
one of the two hands it.

The two specs are the two models' in small: ``RANK_SCALED`` with LongCat's
rank scales, unscaled frequencies and ``1 / sqrt(nope + rope)``;
``YARN`` with DeepSeek-V2's no scales, scaled frequencies and the softmax's
``m^2``.  In float32 the absorbed and the expanded form differ by the order
of their sums alone: 1e-4 is a hundred times what is read (under 1e-6 x the
values' size) and far under what a wrong scale, frequency or mask gives
(the cases below check that each of those is seen).
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_examples_tpu.models import layers, mla

D, TOL = 48, 1e-4


def _spec(kind: str, **over):
    base = dict(heads=3, q_lora_rank=20, kv_lora_rank=16, nope=8, rope=8, v_dim=12,
                eps=1e-6, dtype=jnp.float32, decode_block=8, prefill_block=8)
    if kind == "RANK_SCALED":
        base.update(q_scale=math.sqrt(D / 20), kv_scale=math.sqrt(D / 16),
                    softmax_scale=1 / math.sqrt(16),
                    inv_freq=layers.rope_frequencies(8, 1e4))
    else:
        m = layers.yarn_mscale(8.0, 0.707)
        base.update(q_scale=1.0, kv_scale=1.0, softmax_scale=m * m / math.sqrt(16),
                    inv_freq=layers.yarn_frequencies(8, 100.0, 8.0, 16, 1.0, 0.05))
    base.update(over)
    return mla.Spec(**base)


def _params(spec, seed=0):
    return mla.init(spec, D, jax.random.key(seed), std=0.3, out_std=0.3)


def test_yarn_frequencies_at_the_published_values():
    """DeepSeek-V2's ``rope_scaling``: ``low`` 10, ``high`` 23; pairs 0-10
    keep their frequency, pairs 23-31 turn 40 times slower, those between
    are blended in equal steps; ``m(0.707)`` 1.26081."""
    assert layers.yarn_correction_range(64, 1e4, 4096, 32, 1) == (10, 23)
    plain = np.asarray(layers.rope_frequencies(64, 1e4))
    scaled = np.asarray(layers.yarn_frequencies(64, 1e4, 40, 4096, 32, 1))
    np.testing.assert_allclose(plain, 1e4 ** (-np.arange(32) / 32), rtol=1e-5)
    np.testing.assert_array_equal(scaled[:11], plain[:11])
    np.testing.assert_allclose(scaled[23:], plain[23:] / 40, rtol=1e-6)
    ratio = scaled[11:23] / plain[11:23]
    want = 1 - (np.arange(11, 23) - 10) / 13 * (1 - 1 / 40)
    np.testing.assert_allclose(ratio, want, rtol=1e-5)
    assert layers.yarn_mscale(40, 0.707) == pytest.approx(1.26081, abs=1e-5)
    assert layers.yarn_mscale(1, 0.707) == 1.0
    # A factor of 1 stretches nothing.
    np.testing.assert_allclose(
        np.asarray(layers.yarn_frequencies(64, 1e4, 1, 4096, 32, 1)), plain, rtol=1e-6)
    # rope_angles is rope_angles_at the unscaled frequencies.
    cos, sin = layers.rope_angles(jnp.array([0, 5]), 64, 1e4)
    cos2, sin2 = layers.rope_angles_at(jnp.array([0, 5]), jnp.asarray(plain))
    np.testing.assert_allclose(cos, cos2, atol=1e-6)
    np.testing.assert_allclose(sin, sin2, atol=1e-6)


def test_shapes_of_a_sub_layer_and_the_latent_row():
    spec = _spec("YARN")
    p = _params(spec)
    assert spec.latent == 24
    assert {k: v["kernel"].shape for k, v in p.items() if "kernel" in v} == {
        "q_a": (D, 20), "q_b": (20, 3 * 16), "kv_a": (D, 24), "kv_b": (16, 3 * 20),
        "o": (3 * 12, D)}
    h = jax.random.normal(jax.random.key(1), (5, D))
    q_nope, q_rope, row = mla.query_and_latent(spec, p, h, jnp.arange(5))
    assert q_nope.shape == (5, 3, 8) and q_rope.shape == (5, 3, 8) and row.shape == (5, 24)


@pytest.mark.parametrize("plan", [
    [(8, 8), (5, 8)],  # chunks of one width, the second padded
    [(8, 8), (4, 4), (1, 2)],  # the engine's widths 2 / 4 / 8: narrower as less is left
], ids=["one_width", "widths"])
@pytest.mark.parametrize("chunks", ["loop", "kernel"])
@pytest.mark.parametrize("kind", ["RANK_SCALED", "YARN"])
def test_chunks_then_absorbed_steps_are_the_expanded_full_forward(
        kind, chunks, plan, monkeypatch):
    """One sequence of 29 positions (past the YARN spec's original 16): the
    full forward in the expanded form; the same through a cache - the chunks
    of ``plan`` (valid tokens, width) into slot 1 of a used cache, the last
    padded, then absorbed steps beside a row that is not the session's.
    ``kernel``: the chunks and the steps as a TPU runs them - ``mla`` told it
    is not interpreted, so it calls ops/latent_prefill.py and
    ops/latent_decode.py, which here still are."""
    if chunks == "kernel":
        monkeypatch.setattr(mla, "interpret_mode", lambda: False)
    spec = _spec(kind)
    p = _params(spec)
    h = jax.random.normal(jax.random.key(2), (1, 29, D))
    full = np.asarray(mla.forward(spec, p, h))[0]
    assert np.abs(full).max() > 0.3
    cache = jnp.full((2, 32, spec.latent), 0.37)
    got = np.zeros_like(full)
    prefill = jax.jit(lambda *a: mla.prefill(spec, p, *a))
    decode = jax.jit(lambda *a: mla.decode(spec, p, *a))
    at = 0
    for n, width in plan:
        padded = jnp.concatenate([h[0, at:at + n], jnp.zeros((width - n, D))])
        o, cache = prefill(padded, cache, 1, at, n)
        got[at:at + n] = o[:n]
        at += n
    assert at == 13
    np.testing.assert_array_equal(np.asarray(cache[0]), np.float32(0.37))  # slot 0 untouched
    np.testing.assert_array_equal(np.asarray(cache[1, 13:]), np.float32(0.37))
    for pos in range(13, 29):
        rows = jnp.stack([h[0, 3], h[0, pos]])
        # The other row is live in every second step: live or not, it is
        # nothing to the session.
        o, cache = decode(rows, cache, jnp.array([2, pos]), jnp.array([pos % 2 == 0, True]))
        got[pos] = o[1]
        assert np.isfinite(np.asarray(o)).all()
    assert np.abs(got - full).max() < TOL
    # What each of the spec's numbers does is seen at this tolerance.
    for wrong in (dict(softmax_scale=spec.softmax_scale * 1.2),
                  dict(inv_freq=spec.inv_freq * 1.5), dict(kv_scale=spec.kv_scale * 1.2)):
        other = np.asarray(mla.forward(_spec(kind, **wrong), p, h))[0]
        assert np.abs(other - full).max() > 100 * TOL


def test_the_scaled_pairs_matter_past_the_original_context():
    """The YARN spec against the same spec with unscaled frequencies."""
    spec = _spec("YARN")
    plain = _spec("YARN", inv_freq=layers.rope_frequencies(8, 100.0))
    p = _params(spec)
    h = jax.random.normal(jax.random.key(2), (1, 29, D))
    a, b = np.asarray(mla.forward(spec, p, h)), np.asarray(mla.forward(plain, p, h))
    assert np.abs(a - b).max() > 0.01


def test_the_count_of_rows_read_is_each_live_slots_own_whole_blocks():
    """``decode_rows_read``: the mean over the slots of the whole blocks up
    to each live slot's own row, at most the cache, nothing of the others."""
    one = lambda block, pos, max_len: mla.decode_rows_read(
        block, np.array([pos]), np.array([True]), max_len)
    assert one(8, 0, 32) == 8 and one(8, 7, 32) == 8
    assert one(8, 8, 32) == 16 and one(8, 31, 32) == 32
    assert one(1024, 5, 32) == 32  # a block longer than the cache
    assert one(8, 29, 30) == 30  # a last block that the cache cuts short
    pos, live = np.array([9, 4, 20, 0]), np.array([True, True, False, False])
    assert mla.decode_rows_read(8, pos, live, 32) == (16 + 8) / 4
    assert mla.decode_rows_read(8, pos, ~live, 32) == (24 + 8) / 4
    assert mla.decode_rows_read(8, pos, live & False, 32) == 0


def test_the_step_reads_whole_blocks_to_its_deepest_row_and_no_further():
    """The CPU's form (the loop, which ``mla.decode`` runs here)."""
    # Rows past the deepest block are not read: garbage there changes nothing.
    spec = _spec("YARN")
    p = _params(spec)
    h = jax.random.normal(jax.random.key(3), (2, D))
    cache = jax.random.normal(jax.random.key(4), (2, 32, spec.latent))
    pos, live = jnp.array([9, 4]), jnp.array([True, True])
    o, written = mla.decode(spec, p, h, cache, pos, live)
    spoiled = cache.at[:, 16:].set(jnp.nan)
    o2, _ = mla.decode(spec, p, h, spoiled, pos, live)
    np.testing.assert_array_equal(np.asarray(o), np.asarray(o2))
    # The step wrote one row a slot, at its position, and nothing else.
    changed = np.asarray(written != cache).any(axis=-1)
    assert changed.sum() == 2 and changed[0, 9] and changed[1, 4]
    # The deepest LIVE row bounds it: with row 0 not live, block 1 is not
    # read either; row 0 still writes its latent and comes out finite, and
    # row 1's result is what it was.
    o3, written3 = mla.decode(
        spec, p, h, cache.at[:, 8:].set(jnp.nan), pos, jnp.array([False, True]))
    np.testing.assert_array_equal(np.asarray(o3[1]), np.asarray(o[1]))
    assert np.isfinite(np.asarray(o3)).all()
    np.testing.assert_array_equal(np.asarray(written3[0, 9]), np.asarray(written[0, 9]))


def test_a_chunk_at_the_end_of_the_cache_is_written_where_it_belongs():
    """A chunk whose window would overrun the cache is rolled inside it."""
    new = jnp.arange(8 * 4, dtype=jnp.float32).reshape(8, 4) + 1
    cache = jnp.zeros((2, 12, 4))
    out = mla.chunk_write(cache, new, 1, 8, 3)  # rows 8, 9, 10 of slot 1
    np.testing.assert_array_equal(np.asarray(out[1, 8:11]), np.asarray(new[:3]))
    assert not np.asarray(out[1, :8]).any() and not np.asarray(out[1, 11:]).any()
    assert not np.asarray(out[0]).any()
