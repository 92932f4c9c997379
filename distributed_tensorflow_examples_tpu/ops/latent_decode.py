"""The one-token step's latent attention: one query a slot over THAT slot's
cached rows, and over no other row.

    out[s] = softmax(scale x q[s] . rows[s, :n[s]]^T) . rows[s, :n[s], :values]

``q [S, H, latent]`` (the absorbed query: ``q_nope . W_uk`` beside the
rotated part), ``cache [S, T, latent]``, ``n [S]`` int32 -> ``[S, H,
values]`` float32.  A slot's first ``values`` columns are its values too
(models/mla.py has the equations).  ``n[s]`` is how many positions slot
``s`` reads: ``pos + 1`` for a live row, 0 for a row that reads nothing,
whose result is ZEROS (finite: the layers after it still compute on it).

Why a kernel.  XLA's form is a loop of a dozen small operations a trip that
slices a block of positions of EVERY slot up to the deepest slot's row: at
64 slots x 4096 rows with a mean session of 1,740 it reads twice what the
sessions hold, at a third of the chip's bandwidth (PERF.md section 6,
PR 35).  Here the (slot, block of positions) pairs that EXIST are packed
into a work list (:func:`work_list`, as ops/grouped_ffn.py packs row blocks
by expert) and the grid is that list and no longer: a live slot has
``ceil(n / block)`` items, a slot that reads nothing ONE, whose index map
names the block already in VMEM (nothing moves) and whose body writes the
zeros.  No grid step is skipped, so the grid costs nothing past the work.
The running maximum, sum and accumulator live in float32 scratch across a
slot's items; the grid runs in order.

The arithmetic is the loop's (models/mla.py ``_absorbed_loop``): products
of ``cache.dtype`` operands accumulated in float32, the scale applied to the
float32 scores, the mask ``t < n[s]``, maximum, exponentials and sums in
float32, the weights rounded once to ``cache.dtype`` before the product with
the rows, one division at the end.  The latent (576 = 512 + 64) is no
multiple of 128 lanes, so the scores are two products: the rows' first
``values`` columns, then the rest.

The cache is read AS IT LIES.  The TPU's compiler gives ``[S, T, 576]`` a
layout with the POSITIONS last (a last dimension of 576 would be padded to
640 lanes), so the kernel takes the cache as ``[S, 576, T]`` - the
transpose is a bitcast in the compiled step, asserted at the served shapes
by tests/test_selective_scan.py - and an item's block is ``[576, block]``:
whole lanes of positions, no padding, scores ``q . block`` and values
``weights . block^T``.

VMEM at the served widths (128 heads, latent 576, block 512, bfloat16),
double buffers counted: rows 1.2 MB, query 0.3 MB, output 0.5 MB, scratch
0.3 MB, the scores and weights of a block 0.4 MB - under the compiler's
default allowance.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import compiler_params, interpret_mode

#: The kernel's name, which its operations carry in a device trace.
KERNEL_NAME = "mla_decode_attention"


def blocks_read(n, block: int):
    """Blocks of ``block`` positions that hold a slot's first ``n``
    positions (numpy or jax, elementwise): what the kernel brings in of that
    slot, none where ``n`` is 0."""
    return (n + block - 1) // block


def work_list(n, block: int, max_blocks: int):
    """The grid's items for ``n [S]``: ``(slot [W], block [W], from_slot
    [W], from_block [W], total)`` with ``W = S x max_blocks``.  Item ``w <
    total`` is block ``block[w]`` of slot ``slot[w]``, slots in order, a
    slot's blocks in order; a slot with ``n == 0`` has one item, which
    brings nothing in.  ``from_*`` is the cache block an item's index map
    names: its own, or for an item that brings nothing in the one before it
    that does (the first one that does, for those ahead of it)."""
    S = n.shape[0]
    W = S * max_blocks
    count = jnp.maximum(blocks_read(n, block), 1)
    ends = jnp.cumsum(count)
    total = ends[-1]
    w = jnp.arange(W, dtype=jnp.int32)
    slot = jnp.minimum((ends[None, :] <= w[:, None]).sum(axis=1), S - 1).astype(jnp.int32)
    blk = w - (ends - count)[slot]
    reads = (n[slot] > 0) & (w < total)
    src = jax.lax.cummax(jnp.where(reads, w, -1))
    src = jnp.where(src < 0, jnp.argmax(reads), src)
    return slot, blk, slot[src], blk[src], total.astype(jnp.int32)


def _kernel(slot_ref, block_ref, _fs, _fb, n_ref, q_ref, rows_ref, out_ref,
            m_sc, l_sc, acc_sc, *, block: int, values: int, scale: float,
            length: int):
    w = pl.program_id(0)
    j = block_ref[w]
    n = n_ref[slot_ref[w]]

    @pl.when(n == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when((n > 0) & (j == 0))
    def _():
        m_sc[...] = jnp.full_like(m_sc, -jnp.inf)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    @pl.when(n > 0)
    def _():
        q, rows = q_ref[...], rows_ref[...]  # [H, latent], [latent, block]
        if length % block:
            # The cache's last block overhangs it: what lies past the end is
            # undefined - masked in the scores below, zeroed for the values.
            t = j * block + jax.lax.broadcasted_iota(jnp.int32, rows.shape, 1)
            rows = jnp.where(t < length, rows, jnp.zeros_like(rows))
        s = jnp.dot(q[:, :values], rows[:values], preferred_element_type=jnp.float32)
        s += jnp.dot(q[:, values:], rows[values:], preferred_element_type=jnp.float32)
        s *= scale
        t = j * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        # A slot's every block holds a position it reads (block 0 position
        # 0): the maximum is finite from the first item on.
        s = jnp.where(t < n, s, -jnp.inf)
        m = m_sc[...]
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        r = jnp.exp(m - m_new)
        l_sc[...] = l_sc[...] * r + p.sum(axis=-1, keepdims=True)
        acc_sc[...] = acc_sc[...] * r + jax.lax.dot_general(
            p.astype(rows.dtype), rows[:values], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_sc[...] = m_new

    @pl.when((n > 0) & (j == blocks_read(n, block) - 1))
    def _():
        out_ref[...] = acc_sc[...] / l_sc[...]


@functools.partial(jax.jit, static_argnames=("values", "scale", "block"))
def latent_decode_attention(q, cache, n, *, values: int, scale: float, block: int):
    """See the module docstring.  ``block``: the positions an item brings in
    (the whole cache where that is shorter); ``n`` is clipped to the cache.
    Compiles through Mosaic on a TPU, interpreted on the CPU."""
    S, H, L = q.shape
    T = cache.shape[1]
    blk = min(block, T)
    max_blocks = -(-T // blk)
    n = jnp.clip(n.astype(jnp.int32), 0, T)
    slot, item_block, from_slot, from_block, total = work_list(n, blk, max_blocks)
    # Positions last: on a TPU that is how a ``[S, T, 576]`` array already
    # lies in memory (576 is no multiple of 128 lanes, so the compiler puts
    # the positions there), and the transpose moves nothing.
    cache = jnp.swapaxes(cache, 1, 2)
    return pl.pallas_call(
        functools.partial(_kernel, block=blk, values=values, scale=scale, length=T),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(total,),
            in_specs=[
                pl.BlockSpec((None, H, L), lambda w, s, b, fs, fb, n: (s[w], 0, 0)),
                pl.BlockSpec((None, L, blk), lambda w, s, b, fs, fb, n: (fs[w], 0, fb[w])),
            ],
            out_specs=pl.BlockSpec((None, H, values), lambda w, s, b, fs, fb, n: (s[w], 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, values), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((S, H, values), jnp.float32),
        # In order: the scratch carries a slot's running softmax from item
        # to item, and an item that brings nothing in counts on what the
        # item before it left in VMEM.
        compiler_params=compiler_params(("arbitrary",)),
        interpret=interpret_mode(),
        name=KERNEL_NAME,
    )(slot, item_block, from_slot, from_block, n, q, cache)
