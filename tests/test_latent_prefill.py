"""ops/latent_prefill.py: the prefill chunk's latent attention as a kernel
(interpreted here), against a plain float64 softmax over the expanded keys
and values written below and against the loop that is the CPU's form of the
same sub-layer (models/mla.py ``attend_expanded``).

In float32 the kernel, the loop and the plain softmax differ by the order
of their sums alone; in bfloat16 the kernel and the loop round the same
operands at the same places, while both stand a bfloat16 rounding of keys,
values and weights off the plain softmax.  The compile for a v5e at the
served widths is in tests/test_selective_scan.py (the one file that loads
the TPU's library).
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_examples_tpu.models import layers, mla
from distributed_tensorflow_examples_tpu.ops import latent_prefill as lp

SCALE = 0.3
SLOT = 1


def _spec(heads, rank, nope, rope, v_dim, dtype, block):
    return mla.Spec(
        heads=heads, q_lora_rank=8, kv_lora_rank=rank, nope=nope, rope=rope,
        v_dim=v_dim, q_scale=1.0, kv_scale=1.0, softmax_scale=SCALE,
        inv_freq=layers.rope_frequencies(rope, 1e4), eps=1e-6, dtype=dtype,
        decode_block=block, prefill_block=block)


def _inputs(spec, C, T, seed=0, slots=3):
    """Queries, ``kv_b`` and a cache of ``slots`` slots, sized so that the
    scores stay a few units wide."""
    k = jax.random.split(jax.random.key(seed), 4)
    H, dt = spec.heads, spec.dtype
    q_nope = jax.random.normal(k[0], (C, H, spec.nope)).astype(dt)
    q_rope = jax.random.normal(k[1], (C, H, spec.rope)).astype(dt)
    kv_b = (jax.random.normal(k[2], (spec.kv_lora_rank, H * (spec.nope + spec.v_dim)))
            / math.sqrt(spec.kv_lora_rank)).astype(dt)
    cache = jax.random.normal(k[3], (slots, T, spec.latent)).astype(dt)
    return q_nope, q_rope, kv_b, cache


def _plain(spec, q_nope, q_rope, kv_b, rows, offset):
    """Query by query: softmax over the slot's positions ``<= offset + q``
    (inside the cache) of the expanded keys, times the expanded values;
    float64 in numpy, nothing rounded.  Every product is a matrix product a
    head (``@``): an ``einsum`` over the same axes takes six times as long
    at the served widths."""
    f = lambda a: np.asarray(a, np.float64)
    q_nope, q_rope, rows = f(q_nope), f(q_rope), f(rows)
    R, H, T = spec.kv_lora_rank, spec.heads, rows.shape[0]
    kv = np.moveaxis((rows[:, :R] @ f(kv_b)).reshape(T, H, spec.nope + spec.v_dim), 1, 0)
    k, v = kv[..., :spec.nope], kv[..., spec.nope:]  # [H, T, d]
    s = np.moveaxis(q_nope, 1, 0) @ np.swapaxes(k, 1, 2) + np.moveaxis(q_rope, 1, 0) @ rows[:, R:].T
    q_pos = offset + np.arange(q_nope.shape[0])
    s = np.where(np.arange(T)[None, None, :] <= q_pos[None, :, None], SCALE * s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))  # [H, Q, T]
    o = np.moveaxis((p / p.sum(-1, keepdims=True)) @ v, 0, 1)
    return o.reshape(o.shape[0], -1)


def _kernel(spec, q_nope, q_rope, kv_b, cache, offset, heads=None):
    return np.asarray(lp.latent_prefill_attention(
        q_nope, q_rope, kv_b, cache, jnp.int32(SLOT), jnp.int32(offset),
        nope=spec.nope, scale=SCALE, block=spec.prefill_block, heads=heads))


def _loop(spec, q_nope, q_rope, kv_b, cache, offset):
    C, T = q_nope.shape[0], cache.shape[1]
    block = min(spec.prefill_block, T)
    return np.asarray(mla.attend_expanded(
        spec, {"kv_b": {"kernel": kv_b}}, q_nope, q_rope, cache[SLOT],
        offset + jnp.arange(C), lp.blocks_read(offset, C, block, T), block))


#: name -> (chunk, cache length, block, offset): every edge a chunk can stand
#: on.  At the cache's end the chunk's last queries lie past it, as the
#: engine's last chunk of a slot may (its padding).
EDGES = {
    "offset_0_one_block": (8, 32, 8, 0),
    "offset_0_chunk_of_two_blocks": (16, 32, 8, 0),
    "inside_a_block": (8, 32, 8, 5),
    "on_a_blocks_edge": (8, 32, 8, 16),
    "the_caches_end": (8, 32, 8, 28),
    "cache_no_multiple_of_the_block": (8, 30, 8, 17),
    "cache_no_multiple_of_the_block_at_its_end": (8, 30, 8, 25),
    "cache_shorter_than_a_block": (4, 6, 8, 1),
    "block_wider_than_the_chunk": (4, 32, 16, 13),
}


@pytest.mark.parametrize("case", sorted(EDGES))
def test_kernel_is_the_plain_softmax_over_the_expanded_rows(case):
    C, T, block, offset = EDGES[case]
    spec = _spec(4, 16, 8, 8, 12, jnp.float32, block)
    args = _inputs(spec, C, T)
    got = _kernel(spec, *args, offset, heads=2)
    assert np.abs(got - _plain(spec, *args[:3], args[3][SLOT], offset)).max() < 2e-5


@pytest.mark.parametrize("case", sorted(EDGES))
def test_kernel_and_loop_agree(case):
    C, T, block, offset = EDGES[case]
    spec = _spec(4, 16, 8, 8, 12, jnp.float32, block)
    args = _inputs(spec, C, T, seed=1)
    assert np.abs(_kernel(spec, *args, offset) - _loop(spec, *args, offset)).max() < 2e-6


@pytest.mark.parametrize("heads,chunk", [(64, 128), (128, 128), (64, 512)])
def test_kernel_at_the_served_widths_cut_small(heads, chunk):
    """The two models' heads over the served latent (rank 512, 128 + 64 a
    query, 128 a value), bfloat16 as served, chunks of 128 and 512 against a
    short cache at an offset inside a block: the kernel and the loop round
    alike (where the order of a float32 sum differs a rounded key, value or
    weight may fall the other way: one part in 256 of one term); the plain
    softmax rounds nothing."""
    spec = _spec(heads, 512, 128, 64, 128, jnp.bfloat16, 128)
    T, offset = 2 * chunk, chunk - 56
    args = _inputs(spec, chunk, T, seed=2, slots=2)
    got = _kernel(spec, *args, offset)
    assert np.abs(got - _loop(spec, *args, offset)).max() < 2e-3
    assert np.abs(got - _plain(spec, *args[:3], args[3][SLOT], offset)).max() < 5e-2


def test_the_heads_an_item_takes_do_not_change_the_result():
    spec = _spec(4, 16, 8, 8, 12, jnp.float32, 8)
    args = _inputs(spec, 8, 32, seed=3)
    one = _kernel(spec, *args, 13, heads=1)
    np.testing.assert_array_equal(one, _kernel(spec, *args, 13, heads=2))
    np.testing.assert_array_equal(one, _kernel(spec, *args, 13, heads=4))


@pytest.mark.parametrize("offset,chunk,T", [(0, 8, 32), (5, 8, 32), (16, 8, 32), (28, 8, 32), (17, 8, 30)])
def test_kernel_reads_whole_blocks_to_the_chunks_end_and_no_further(offset, chunk, T):
    """NaN in every other slot and past the last block that holds a position
    ``< offset + C`` changes no result: the grid ran
    ``mla.prefill_rows_read`` positions, as the loop does, and not one
    more.  A NaN in the last position it counts is seen (a value times a
    zero weight), so it ran no fewer either."""
    block = 8
    spec = _spec(4, 16, 8, 8, 12, jnp.float32, block)
    q_nope, q_rope, kv_b, cache = _inputs(spec, chunk, T, seed=4)
    read = mla.prefill_rows_read(block, offset, chunk, T)
    assert read == min(T, -(-min(offset + chunk, T) // block) * block)
    spoiled = np.full(cache.shape, np.nan, np.float32)
    spoiled[SLOT, :read] = np.asarray(cache[SLOT, :read])
    want = _kernel(spec, q_nope, q_rope, kv_b, cache, offset)
    got = _kernel(spec, q_nope, q_rope, kv_b, jnp.asarray(spoiled), offset)
    np.testing.assert_array_equal(got, want)
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(
        _loop(spec, q_nope, q_rope, kv_b, jnp.asarray(spoiled), offset),
        _loop(spec, q_nope, q_rope, kv_b, cache, offset))
    spoiled[SLOT, read - 1] = np.nan
    assert np.isnan(_kernel(spec, q_nope, q_rope, kv_b, jnp.asarray(spoiled), offset)).any()


def test_rows_read_of_a_chunk():
    one = lambda offset, chunk=8, max_len=32: mla.prefill_rows_read(8, offset, chunk, max_len)
    assert one(0) == 8 and one(1) == 16 and one(8) == 16 and one(24) == 32
    assert one(28) == 32  # a chunk that overruns the cache
    assert one(0, chunk=3) == 8 and one(6, chunk=3) == 16  # a narrower chunk
    assert one(17, max_len=30) == 30  # a last block that the cache cuts short
    assert mla.prefill_rows_read(1024, 5, 8, 32) == 32  # a block longer than the cache
    assert lp.blocks_read(jnp.int32(28), 8, 8, 32) == lp.blocks_read(28, 8, 8, 32) == 4


def test_the_group_of_heads_fits_the_budget():
    """At the served shapes the plan divides the heads, fills whole lanes
    (the rotated part is 64 wide: an even group) and stays inside the VMEM
    the kernel plans for; a budget nothing fits still gives a plan."""
    shape = dict(chunk=512, block=1024, latent=576, per=256, v_dim=128, rank=512, itemsize=2)
    for heads in (64, 128):
        g = lp.heads_per_group(heads, **shape)
        assert heads % g == 0 and g % 2 == 0 and lp.vmem_bytes(g, **shape) <= lp.VMEM_BUDGET
        assert g == heads or lp.vmem_bytes(2 * g, **shape) > lp.VMEM_BUDGET
    assert lp.heads_per_group(6, **{**shape, "chunk": 1 << 20}) == 2
    # Widths that fill no lane (the tests' own): all the heads, or none fit.
    small = dict(chunk=8, block=8, latent=24, per=20, v_dim=12, rank=16, itemsize=4)
    assert lp.heads_per_group(4, **small) == 4
