"""ops/slot_decode.py: the one-token step's attention over a grouped-head
slot cache as a kernel (interpreted here), against a plain float64 softmax
over each slot's own seen rows written below, and against THE LOOP that is
the CPU's form of the same sub-layer (models/ring_cache.py ``step_loop``: a
block of rows of EVERY slot a trip, up to the block that holds the deepest
live slot's row).

The kernel's arithmetic is the loop's, rounding point for rounding point, and
a block the loop folds for a slot beyond that slot's own contributes ``exp(-inf)
= 0``: at the same block the two agree BIT FOR BIT, in float32 and in
bfloat16.  The compile for a v5e at the served shapes is in
tests/test_selective_scan.py (the one file that loads the TPU's library).
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_examples_tpu.models import ring_cache
from distributed_tensorflow_examples_tpu.ops import slot_decode as sd

KV, HD = 2, 16


def _loop(q, ck, cv, pos, live, window, block):
    return ring_cache.step_loop(q, ck, cv, pos, live, window, attn_block=block)


def _plain(q, ck, cv, pos, live, window):
    """Slot by slot, head by head: the softmax over the rows whose held
    position the slot's query sees, zeros for a slot that is not live;
    float64 in numpy."""
    q, ck, cv = (np.asarray(a, np.float64) for a in (q, ck, cv))
    R = ck.shape[2]
    out = np.zeros(q.shape)
    for b in np.flatnonzero(live):
        held = pos[b] - np.mod(pos[b] - np.arange(R), R)
        seen = held >= 0
        if window is not None:
            seen &= pos[b] - held < window
        for h in range(q.shape[1]):
            w = np.exp(q[b, h] @ ck[b, h, seen].T / math.sqrt(q.shape[-1]))
            out[b, h] = (w / w.sum(-1, keepdims=True)) @ cv[b, h, seen]
    return out


def _inputs(S, G, R, dtype=jnp.float32, seed=0):
    """Queries, and a cache whose every row of every slot - the spare one's
    too - holds something: what a slot's earlier sessions left."""
    k = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(k[0], (S, KV, G, HD)).astype(dtype)
    ck, cv = (jax.random.normal(k[i], (S + 1, KV, R, HD)).astype(dtype) for i in (1, 2))
    return q, ck, cv


def _kernel(q, ck, cv, pos, live, window, block):
    o, read = sd.slot_decode_attention(
        q, ck, cv, jnp.asarray(pos, jnp.int32), jnp.asarray(live), window, block=block)
    return np.asarray(o), np.asarray(read)


#: name -> (rows a slot R, window, block, pos a slot, live a slot): a full
#: layer, a ring no position has wrapped on, and a ring that has wrapped,
#: with every edge a slot's depth can stand on and idle slots among them.
CASES = {
    "global": (32, None, 8, [0, 31, 7, 8, 15, 20, 3], [1, 1, 1, 1, 0, 1, 0]),
    "global_rows_no_multiple_of_the_block": (
        30, None, 8, [29, 0, 23, 24, 9, 28], [1, 1, 1, 1, 0, 1]),
    "global_shorter_than_a_block": (6, None, 8, [5, 0, 2, 3], [1, 1, 0, 1]),
    "global_nothing_live": (16, None, 8, [3, 9, 0], [0, 0, 0]),
    "ring_not_wrapped": (24, 16, 8, [0, 7, 8, 23, 15, 12], [1, 1, 1, 1, 0, 1]),
    "ring_wrapped": (24, 16, 8, [24, 100, 47, 5, 31, 71, 23], [1, 1, 1, 1, 0, 1, 1]),
    "ring_wrapped_rows_no_multiple_of_the_block": (
        22, 16, 8, [22, 100, 43, 5, 30, 21], [1, 1, 1, 1, 0, 1]),
    "ring_one_block": (16, 12, 16, [40, 3, 15, 16], [1, 1, 1, 0]),
}
GROUPS = [7, 8, 16]


def _case(name):
    R, window, block, pos, live = CASES[name]
    return R, window, block, np.array(pos, np.int32), np.array(live, bool)


@pytest.mark.parametrize("G", GROUPS)
@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_is_the_plain_softmax_over_each_slots_own_seen_rows(case, G):
    R, window, block, pos, live = _case(case)
    q, ck, cv = _inputs(len(pos), G, R)
    got, _ = _kernel(q, ck, cv, pos, live, window, block)
    assert np.abs(got - _plain(q, ck, cv, pos, live, window)).max() < 2e-6
    assert not got[~live].any()  # zeros, not just finite


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("G", GROUPS)
@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_and_loop_agree_bit_for_bit_at_the_same_block(case, G, dtype):
    R, window, block, pos, live = _case(case)
    q, ck, cv = _inputs(len(pos), G, R, dtype, seed=1)
    got, _ = _kernel(q, ck, cv, pos, live, window, block)
    want, _ = _loop(q, ck, cv, jnp.asarray(pos), jnp.asarray(live), window, block)
    if R % min(block, R):
        # The loop reads the cache's last block SHIFTED BACK inside it, the
        # kernel past its end: the same rows at other places of the sum.
        assert np.abs(got - np.asarray(want)).max() < (2e-6 if dtype == jnp.float32 else 1e-2)
    else:
        np.testing.assert_array_equal(got, np.asarray(want))


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_reads_whole_blocks_to_each_slots_own_row_and_no_further(case, monkeypatch):
    """NaN past EACH live slot's own last block, everywhere in a slot that is
    not live and in the spare slot changes no result and leaves the zeros
    zeros; the rows the kernel says it read are those blocks, a ring's at
    most the ring - and what ``decode_rows_read`` tells the engine of the
    same ``pos`` and ``live`` is their mean where the kernel runs, and the
    loop's own count where the loop does."""
    R, window, block, pos, live = _case(case)
    blk = min(block, R)
    q, ck, cv = _inputs(len(pos), 8, R, seed=3)
    want = np.where(live, np.minimum(-(-(pos + 1) // blk) * blk, R), 0)
    spoiled_k, spoiled_v = np.array(ck), np.array(cv)
    for a in (spoiled_k, spoiled_v):
        a[-1] = np.nan
        for b, rows in enumerate(want):
            a[b, :, rows:] = np.nan
    got, read = _kernel(q, jnp.asarray(spoiled_k), jnp.asarray(spoiled_v), pos, live,
                        window, block)
    np.testing.assert_array_equal(got, _kernel(q, ck, cv, pos, live, window, block)[0])
    assert np.isfinite(got).all() and not got[~live].any()
    np.testing.assert_array_equal(read, want)

    class Layout:
        layers, attn_block = (0,), block
        cache_rows = staticmethod(lambda i, max_len: R)

    # The loop reads every slot to the deepest live row's block: it is the
    # kernel that does not.
    o, loop_read = _loop(q, jnp.asarray(spoiled_k), jnp.asarray(spoiled_v),
                         jnp.asarray(pos), jnp.asarray(live), window, block)
    assert (np.asarray(loop_read) == want.max()).all()
    assert np.isnan(np.asarray(o)).any() == bool((want < want.max()).any())
    assert ring_cache.decode_rows_read(Layout, pos, live, R) == want.max()
    monkeypatch.setattr(ring_cache, "interpret_mode", lambda: False)
    assert ring_cache.decode_rows_read(Layout, pos, live, R) == pytest.approx(read.mean())


@pytest.mark.parametrize("case", ["global", "ring_not_wrapped", "ring_wrapped"])
def test_a_slots_result_does_not_depend_on_the_other_slots(case):
    """A deep neighbour, an idle neighbour that holds another session's rows,
    a neighbour gone: slots 1 and 3 read what they read."""
    R, window, block, pos, live = _case(case)
    q, ck, cv = _inputs(len(pos), 7, R, seed=4)
    a, read_a = _kernel(q, ck, cv, pos, live, window, block)
    others = np.array([i not in (1, 3) for i in range(len(pos))])
    pos_b = np.where(others, (pos * 5 + 11) % (R if window is None else 4 * R), pos)
    live_b = np.where(others, ~live, live)
    ck_b, cv_b = (c.at[jnp.asarray(np.flatnonzero(others))].multiply(-3.0) for c in (ck, cv))
    b, read_b = _kernel(q, ck_b, cv_b, pos_b.astype(np.int32), live_b, window, block)
    np.testing.assert_array_equal(a[[1, 3]], b[[1, 3]])
    np.testing.assert_array_equal(read_a[[1, 3]], read_b[[1, 3]])


def test_kernel_at_the_served_widths_cut_small():
    """Head size 128 on the lanes, 4 key / value heads of 7 query heads,
    bfloat16 as served, a ring of three blocks of 128 that one slot has
    wrapped: kernel and loop round alike; the plain softmax does not round
    its weights."""
    pos, live = np.array([700, 0, 383, 129], np.int32), np.array([1, 0, 1, 1], bool)
    k = jax.random.split(jax.random.key(5), 3)
    q = jax.random.normal(k[0], (4, 4, 7, 128)).astype(jnp.bfloat16)
    ck, cv = (jax.random.normal(k[i], (5, 4, 384, 128)).astype(jnp.bfloat16) for i in (1, 2))
    got, read = _kernel(q, ck, cv, pos, live, 320, 128)
    want, _ = _loop(q, ck, cv, jnp.asarray(pos), jnp.asarray(live), 320, 128)
    np.testing.assert_array_equal(got, np.asarray(want))
    assert np.abs(got - _plain(q, ck, cv, pos, live, 320)).max() < 2e-2
    assert read.tolist() == [384, 0, 384, 256] and not got[1].any()
