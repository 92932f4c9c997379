"""The one-token step's attention over a grouped-head slot cache: one query
a slot over THAT slot's rows, and over no other row.

``q [S, KV, G, hd]`` (``G`` query heads share each of ``KV`` key / value
heads), ``ck, cv [S + 1, KV, R, hd]`` (models/ring_cache.py: a full layer
``R = max_len`` rows a slot, a window layer a RING of ``R`` rows, a
position's row ``pos % R``; the last slot is a spare that nothing reads),
``pos [S]`` int32 (the row's own position, already written), ``live [S]``
bool, ``window`` (an int, or None for a full layer) -> ``([S, KV, G, hd]``
float32, rows read a slot ``[S]`` int32``)``.  A live slot attends over its
positions ``<= pos`` (with ``window``, fewer than ``window`` behind it); a
slot that is not live reads nothing and its result is ZEROS.

Why a kernel.  XLA's form (models/ring_cache.py ``step_loop``, what the CPU
still runs and what tests/test_slot_decode.py holds the kernel to) is a loop
that slices a block of rows of EVERY slot, up to the block that holds the
DEEPEST live slot's row: with 17 of 32 slots live at
depths from 300 to 13,000 it read six times what the sessions hold of a
full layer and three times of a ring (PERF.md section 6, PR 47).  Here the
(slot, block of rows) pairs that EXIST are packed into a work list
(ops/latent_decode.py :func:`~.latent_decode.work_list`, the latent cells'
own) and the grid is that list and no longer: a live slot has ``min(ceil((pos
+ 1) / block), ceil(R / block))`` items, a slot that reads nothing ONE, whose
index map names the block already in VMEM (nothing moves) and whose body
writes the zeros.  An item brings in one block of rows of K and of V for all
``KV`` heads of its slot; the running maximum, sum and accumulator live in
float32 scratch across a slot's items; the grid runs in order.  No block of a
wrapped ring is skipped for lying outside the window: the window covers all
but one of a ring's blocks.

The arithmetic is the loop's, rounding point for rounding point: products of
``ck.dtype`` operands accumulated in float32, the scale on the float32
scores, the mask BY POSITION ARITHMETIC (``ring_cache.held_position``: row
``r`` holds position ``pos - (pos - r) mod R``; unseen below 0 and
``window`` or more behind), a FINITE floor under the running maximum (a
ring's block may hold nothing its query sees), the weights rounded once to
``cv.dtype`` before the product with V, one division at the end.

The cache is read AS IT LIES: ``hd`` on the lanes, rows on the sublanes, a
block ``[KV, block, hd]`` of the array the step's row writes just updated in
place - no transpose, no reshape, no copy (asserted of the compiled step by
tests/test_selective_scan.py).  ``G`` need be no multiple of 8 sublanes: the
QUERY is padded (a few KB), never the cache.

VMEM at the served widths (4 heads, block 512, hd 128, bfloat16), double
buffers counted: K and V 1 MB each, the rest under 0.1 MB.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import compiler_params, interpret_mode
from .latent_decode import work_list

#: The kernel's name, which its operations carry in a device trace.
KERNEL_NAME = "slot_decode_attention"

#: Where a running softmax's maximum starts, here and in models/ring_cache.py's
#: loops: finite, so that a block that holds nothing its query sees folds as
#: ``exp(-inf - FLOOR) = 0``.
FLOOR = -1e30


def _kernel(slot_ref, block_ref, _fs, _fb, count_ref, pos_ref, q_ref, k_ref, v_ref,
            out_ref, m_sc, l_sc, acc_sc, *, block: int, rows: int, window, scale: float):
    w = pl.program_id(0)
    b, j = slot_ref[w], block_ref[w]
    count = count_ref[b]

    @pl.when(count == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when((count > 0) & (j == 0))
    def _():
        m_sc[...] = jnp.full_like(m_sc, FLOOR)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    @pl.when(count > 0)
    def _():
        pos = pos_ref[b]
        at = pos % rows  # the row the slot's own position lies in
        r = j * block + jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
        # held_position(pos, r, rows) with no vector remainder: rows up to
        # ``at`` hold this turn of the ring, those past it the turn before.
        held = r + (pos - at) - jnp.where(r > at, rows, 0)
        seen = held >= 0
        if window is not None:
            seen &= pos - held < window
        if rows % block:
            # The cache's last block overhangs it: what lies past the end is
            # undefined - masked in the scores, zeroed for the values.
            seen &= r < rows
            inside = j * block + jax.lax.broadcasted_iota(
                jnp.int32, (block, 1), 0) < rows
        for h in range(q_ref.shape[0]):
            k, v = k_ref[h], v_ref[h]  # [block, hd]
            if rows % block:
                v = jnp.where(inside, v, jnp.zeros_like(v))
            s = jax.lax.dot_general(
                q_ref[h], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [G, block]
            s = jnp.where(seen, s, -jnp.inf)
            m = m_sc[h]
            m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            a = jnp.exp(m - m_new)
            l_sc[h] = l_sc[h] * a + p.sum(axis=-1, keepdims=True)
            acc_sc[h] = acc_sc[h] * a + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            m_sc[h] = m_new

    @pl.when((count > 0) & (j == count - 1))
    def _():
        l = l_sc[...]
        out_ref[...] = acc_sc[...] / jnp.where(l == 0, 1.0, l)


@functools.partial(jax.jit, static_argnames=("window", "block"))
def slot_decode_attention(q, ck, cv, pos, live, window, *, block: int):
    """See the module docstring.  ``block``: the rows an item brings in (the
    whole of a shorter cache).  Compiles through Mosaic on a TPU,
    interpreted on the CPU."""
    S, KV, G, hd = q.shape
    R = ck.shape[2]
    blk = min(block, R)
    max_blocks = -(-R // blk)
    pos = pos.astype(jnp.int32)
    n = jnp.where(live, pos + 1, 0)
    count = jnp.minimum(-(-n // blk), max_blocks)  # a ring: at most all of it
    slot, item_block, from_slot, from_block, total = work_list(
        jnp.minimum(n, R), blk, max_blocks)
    # Whole sublanes of query heads: SmallThinker's 7 become 8.
    Gp = -(-G // 8) * 8
    q = jnp.pad(q, ((0, 0), (0, 0), (0, Gp - G), (0, 0)))
    row = lambda w, s, b, fs, fb, c, p: (s[w], 0, 0, 0)
    rows_of = lambda w, s, b, fs, fb, c, p: (fs[w], 0, fb[w], 0)
    out = pl.pallas_call(
        functools.partial(_kernel, block=blk, rows=R, window=window,
                          scale=1.0 / math.sqrt(hd)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(total,),
            in_specs=[
                pl.BlockSpec((None, KV, Gp, hd), row),
                pl.BlockSpec((None, KV, blk, hd), rows_of),
                pl.BlockSpec((None, KV, blk, hd), rows_of),
            ],
            out_specs=pl.BlockSpec((None, KV, Gp, hd), row),
            scratch_shapes=[
                pltpu.VMEM((KV, Gp, 1), jnp.float32),
                pltpu.VMEM((KV, Gp, 1), jnp.float32),
                pltpu.VMEM((KV, Gp, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((S, KV, Gp, hd), jnp.float32),
        # In order: the scratch carries a slot's running softmax from item
        # to item, and an item that brings nothing in counts on what the
        # item before it left in VMEM.
        compiler_params=compiler_params(("arbitrary",)),
        interpret=interpret_mode(),
        name=KERNEL_NAME,
    )(slot, item_block, from_slot, from_block, count, pos, q, ck, cv)
    return out[:, :, :G], jnp.minimum(count * blk, R)
