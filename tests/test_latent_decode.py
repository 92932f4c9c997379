"""ops/latent_decode.py: the one-token step's latent attention as a kernel
(interpreted here), against a plain float32 softmax written below and
against the loop that is the CPU's form of the same sub-layer
(models/mla.py ``_absorbed_loop``).

In float32 the kernel, the loop and the plain softmax differ by the order
of their sums alone (1e-6 x the values' size); in bfloat16 the kernel and
the loop round the same operands at the same places and differ the same
way, while both stand a bfloat16 rounding of the weights (4e-3) off the
plain softmax.  The compile for a v5e at the served widths is in
tests/test_selective_scan.py (the one file that loads the TPU's library).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_examples_tpu.models import mla
from distributed_tensorflow_examples_tpu.ops import latent_decode as ld

SCALE = 0.3


def _plain(q, cache, n, values):
    """Slot by slot: softmax(SCALE x q . rows^T) . rows[:, :values] over the
    slot's first ``n`` rows, zeros where ``n`` is 0; float64 in numpy."""
    q, cache = np.asarray(q, np.float64), np.asarray(cache, np.float64)
    out = np.zeros(q.shape[:2] + (values,))
    for s, k in enumerate(np.asarray(n)):
        if k:
            w = np.exp(SCALE * q[s] @ cache[s, :k].T)
            out[s] = (w / w.sum(-1, keepdims=True)) @ cache[s, :k, :values]
    return out


def _inputs(S, H, T, latent, dtype=jnp.float32, seed=0):
    k = jax.random.split(jax.random.key(seed), 2)
    q = jax.random.normal(k[0], (S, H, latent)).astype(dtype)
    cache = jax.random.normal(k[1], (S, T, latent)).astype(dtype)
    return q, cache


def _kernel(q, cache, n, values, block):
    return np.asarray(ld.latent_decode_attention(
        q, cache, jnp.asarray(n, jnp.int32), values=values, scale=SCALE, block=block))


def _loop(q, cache, n, values, block):
    return np.asarray(mla._absorbed_loop(
        q, cache, jnp.asarray(n, jnp.int32), values=values, scale=SCALE, block=block))


#: name -> (cache length, block, positions read a slot): every edge a slot's
#: depth can stand on, slots that read nothing among them.
RAGGED = {
    "position_0_last_row_block_edge": (32, 8, [1, 32, 8, 9, 16, 21]),
    "not_live_among_live": (32, 8, [0, 13, 0, 0, 32, 1, 0]),
    "not_live_first_and_last": (24, 8, [0, 0, 24, 5, 0]),
    "cache_no_multiple_of_the_block": (30, 8, [30, 1, 24, 25, 0, 29]),
    "cache_shorter_than_a_block": (6, 8, [6, 1, 0, 3]),
    "one_block_a_slot": (16, 16, [16, 0, 7]),
    "nothing_live": (16, 8, [0, 0, 0]),
}


@pytest.mark.parametrize("case", sorted(RAGGED))
def test_kernel_is_the_plain_softmax_over_each_slots_own_rows(case):
    T, block, n = RAGGED[case]
    q, cache = _inputs(len(n), 3, T, 24)
    got = _kernel(q, cache, n, 16, block)
    assert np.abs(got - _plain(q, cache, n, 16)).max() < 2e-6
    assert not got[np.asarray(n) == 0].any()  # zeros, not just finite


@pytest.mark.parametrize("case", sorted(RAGGED))
def test_kernel_and_loop_agree(case):
    T, block, n = RAGGED[case]
    q, cache = _inputs(len(n), 3, T, 24, seed=1)
    assert np.abs(_kernel(q, cache, n, 16, block) - _loop(q, cache, n, 16, block)).max() < 2e-6


@pytest.mark.parametrize("heads", [64, 128])
def test_kernel_at_the_served_widths_cut_small(heads):
    """The two models' heads over the served latent (512 values + 64
    rotated), bfloat16 as served, a few slots of a short cache: the kernel
    and the loop round alike; the plain softmax does not round its weights."""
    n = [200, 0, 256, 129]
    q, cache = _inputs(4, heads, 256, 576, jnp.bfloat16, seed=2)
    got = _kernel(q, cache, n, 512, 128)
    assert np.abs(got - _loop(q, cache, n, 512, 128)).max() < 1e-5
    assert np.abs(got - _plain(q, cache, n, 512)).max() < 2e-2
    assert not got[1].any()


def test_kernel_reads_whole_blocks_to_each_slots_own_row_and_no_further():
    """The per-slot twin of tests/test_mla.py's test of the loop: NaN past
    EACH slot's own last block, and everywhere in a slot that reads nothing,
    changes no result and leaves the zeros zeros."""
    T, block, n = 32, 8, [10, 0, 1, 32, 16, 0, 17]
    q, cache = _inputs(len(n), 3, T, 24, seed=3)
    spoiled = np.array(cache)
    for s, k in enumerate(n):
        spoiled[s, -(-k // block) * block:] = np.nan
    got = _kernel(q, jnp.asarray(spoiled), n, 16, block)
    np.testing.assert_array_equal(got, _kernel(q, cache, n, 16, block))
    assert np.isfinite(got).all() and not got[[1, 5]].any()
    # The loop reads every slot to the deepest row's block: it is the
    # kernel that does not.
    assert np.isnan(_loop(q, jnp.asarray(spoiled), n, 16, block)).any()


def test_a_slots_result_does_not_depend_on_the_other_slots():
    T, block = 32, 8
    q, cache = _inputs(5, 3, T, 24, seed=4)
    a = _kernel(q, cache, [10, 3, 0, 32, 7], 16, block)
    b = _kernel(q, cache, [0, 3, 25, 0, 7], 16, block)
    np.testing.assert_array_equal(a[[1, 4]], b[[1, 4]])


def test_work_list_packs_the_blocks_that_exist():
    """Slots in order, a slot's blocks in order, one item for a slot that
    reads nothing - which names the block before it that was brought in."""
    slot, block, from_slot, from_block, total = (
        np.asarray(x) for x in ld.work_list(jnp.array([0, 17, 0, 8, 0]), 8, 4))
    assert total == 7 and slot.shape == (20,)
    np.testing.assert_array_equal(slot[:7], [0, 1, 1, 1, 2, 3, 4])
    np.testing.assert_array_equal(block[:7], [0, 0, 1, 2, 0, 0, 0])
    # Slot 0 reads nothing and has nothing before it: it names the first
    # block that will be brought in.
    np.testing.assert_array_equal(from_slot[:7], [1, 1, 1, 1, 1, 3, 3])
    np.testing.assert_array_equal(from_block[:7], [0, 0, 1, 2, 2, 0, 0])
    # What lies past the list (never run; the pipeline may look one ahead)
    # names the last block brought in.
    assert (from_slot[7:] == 3).all() and (from_block[7:] == 0).all()
    *_, total = ld.work_list(jnp.array([0, 0]), 8, 4)
    assert total == 2
    *_, total = ld.work_list(jnp.array([32, 32]), 8, 4)
    assert total == 8


def test_blocks_read():
    n = np.array([0, 1, 8, 9, 32])
    np.testing.assert_array_equal(ld.blocks_read(n, 8), [0, 1, 1, 2, 4])
