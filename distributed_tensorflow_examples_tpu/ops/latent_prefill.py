"""The prefill chunk's latent attention: ``C`` queries of ONE slot over that
slot's cached rows, each block of latents expanded into keys and values and
folded into a running softmax without leaving VMEM.

    k[t, h] | v[t, h] = kv_b[:, h] . c[t]                   (rounded to dtype)
    out[q, h] = softmax_t(scale x (q_nope[q, h] . k[t, h] + q_rope[q, h] . kr[t])) . v[t, h]
                over the slot's positions t <= offset + q

``q_nope [C, H, nope]``, ``q_rope [C, H, rope]`` (queries at positions
``offset .. offset + C - 1``), ``kv_b [R, H x (nope + v)]``, ``cache [S, T,
R + rope]`` whose rows are ``c | kr`` (models/mla.py has the equations) ->
``[C, H x v]`` float32.  ``C`` is whatever the query holds.

Why a kernel.  XLA's form (models/mla.py ``attend_expanded``) is a loop of a
dozen operations a trip, each of which writes its result to HBM: at 64 heads
and a block of 512 the float32 scores alone are 67 MB a trip, written,
masked, read, exponentiated, written, rounded, read - the loop ran at 40 %
of the MXU at 64 heads and under a third at 128, where scores wider than 128
positions spilled.  Here the grid is (groups of heads) x (the blocks that
exist): the second extent is traced, so at a block of 1024 a chunk at
offset 0 runs one block and a chunk at 7,168 eight, and nothing past
``offset + C`` is brought in.  An item expands its block for all its heads
in one product, then takes the heads one at a time: two score products, the
scale, the mask, the running maximum, sum and accumulator (float32 scratch,
kept across a head group's blocks), one value product.  Scores and weights
live and die in VMEM.  On a v5e at 64 heads, eight blocks of 1024 under a
chunk of 512 take 2.2 ms where the loop took 5.2: 76 % of the MXU's peak
(PERF.md section 6, PR 38).

The arithmetic is the loop's: products of ``dtype`` operands accumulated in
float32, keys and values rounded to ``dtype``, the scale applied to the
float32 scores, the mask ``t <= offset + q``, maximum, exponentials and sums
in float32, the weights rounded once to ``dtype`` before the value product,
one division at the end - on the chip the result equals the loop's at the
same block bit for bit (PERF.md section 6, PR 38).  A block that lies wholly
at or before the chunk's first query skips the mask.

Everything is read AS IT LIES.  The cache by ``slot`` through the index map:
on a TPU a ``[S, T, 576]`` array has its positions last
(ops/latent_decode.py), the transpose is a bitcast, and a block is ``[576,
block]`` - so the products are ``kv_b^T . block`` (keys and values with
positions in the lanes), ``q . keys`` and ``weights . values^T``, and no
copy of the slot's rows is made.  The queries, ``kv_b`` and the result with
their heads side by side in the lanes, ``[C, H x d]``, a group of heads a
block: a group's first item regroups its queries by head and transposes its
columns of ``kv_b`` in VMEM, its last writes each head's result into its
lanes - where transposes before and after the call cost 0.25 ms a chunk in
copies through HBM.  A cache whose length is no multiple of the block has a
last block that overhangs it: what lies past the end is zeroed and masked.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import compiler_params, interpret_mode

#: The kernel's name, which its operations carry in a device trace.
KERNEL_NAME = "mla_prefill_attention"

#: VMEM the kernel may plan for: a v5e core has 128 MiB, the compiler's own
#: default allowance is 16.
VMEM_BUDGET = 64 << 20


def blocks_read(offset, chunk: int, block: int, max_len: int):
    """Blocks of ``block`` positions a chunk of ``chunk`` queries at
    ``offset`` attends over (``offset`` a plain int or traced): those that
    hold positions ``[0, offset + chunk)``, as far as the cache goes.  The
    grid's second extent."""
    last = offset + chunk - 1
    least = jnp.minimum if isinstance(last, jax.Array) else min
    return least(last, max_len - 1) // block + 1


def vmem_bytes(heads: int, chunk: int, block: int, latent: int, per: int,
               v_dim: int, rank: int, itemsize: int) -> int:
    """What an item of ``heads`` heads holds in VMEM, double buffers and the
    compiler's temporaries counted (a last dimension under 128 lanes is
    padded to them)."""
    lanes = lambda n: -(-n // 128) * 128
    # Queries and ``kv_b``: double buffers as they lie, and regrouped once.
    q = 3 * heads * chunk * 2 * lanes(per - v_dim) * itemsize  # nope and rope, each padded
    w = 3 * heads * per * lanes(rank) * itemsize
    rows = 2 * latent * lanes(block) * itemsize
    out = 2 * heads * chunk * lanes(v_dim) * 4
    scratch = heads * chunk * (2 * 128 + lanes(v_dim)) * 4
    expanded = heads * per * lanes(block) * (itemsize + 4)  # rounded, and the float32 it came from
    scores = 4 * chunk * lanes(block) * 4
    return q + w + rows + out + scratch + expanded + scores


def heads_per_group(heads: int, **shape) -> int:
    """The most heads an item takes inside :data:`VMEM_BUDGET`: a block is
    brought in, and expanded in one product, once a GROUP (4, 8 and 16 heads
    read level on the chip: models/longcat.py).  A group divides the heads, and its queries,
    ``kv_b`` columns and results - the heads side by side - fill whole
    lanes (or it is all the heads); where nothing fits, the least such."""
    widths = (shape["per"] - shape["v_dim"], shape["latent"] - shape["rank"], shape["v_dim"])
    groups = [g for g in range(1, heads + 1) if heads % g == 0
              and (g == heads or all(g * d % 128 == 0 for d in widths))]
    fits = [g for g in groups if vmem_bytes(g, **shape) <= VMEM_BUDGET]
    return max(fits) if fits else groups[0]


def _kernel(at_ref, qn_ref, qr_ref, w_ref, rows_ref, out_ref,
            qn_sc, qr_sc, w_sc, kv_sc, m_sc, l_sc, acc_sc, *, block: int,
            scale: float, length: int):
    j = pl.program_id(1)
    offset, n_blocks = at_ref[1], at_ref[2]
    G, C, nope = qn_sc.shape
    rope, v_dim, rank = qr_sc.shape[2], acc_sc.shape[2], w_sc.shape[1]
    per = nope + v_dim
    dtype = rows_ref.dtype
    f32 = jnp.float32

    @pl.when(j == 0)
    def _():
        # A head group's first item: its queries head by head and ``kv_b``
        # with the rank last, as the products below take them - regrouped
        # here, in VMEM, and not by a copy through HBM before the call.
        w_sc[...] = w_ref[...].T
        for i in range(G):
            qn_sc[i] = qn_ref[:, i * nope:(i + 1) * nope]
            qr_sc[i] = qr_ref[:, i * rope:(i + 1) * rope]
        m_sc[...] = jnp.full_like(m_sc, -jnp.inf)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    rows = rows_ref[...]  # [latent, block]
    if length % block:
        # The cache's last block overhangs it: what lies past the end is
        # undefined - masked in the scores below, zeroed for the values.
        t = j * block + jax.lax.broadcasted_iota(jnp.int32, rows.shape, 1)
        rows = jnp.where(t < length, rows, jnp.zeros_like(rows))
    c, kr = rows[:rank], rows[rank:]
    # Every head's keys and values of this block, positions in the lanes.
    kv_sc[...] = jnp.dot(w_sc[...], c, preferred_element_type=f32).astype(dtype)

    def fold(masked: bool):
        def head(i, carry):
            at = pl.multiple_of(i * per, per)
            k = kv_sc[pl.ds(at, nope), :]
            v = kv_sc[pl.ds(at + nope, v_dim), :]
            s = jnp.dot(qn_sc[i], k, preferred_element_type=f32)
            s += jnp.dot(qr_sc[i], kr, preferred_element_type=f32)
            s *= scale
            if masked:
                t = j * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
                q_pos = offset + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
                # Block 0 holds position 0, which every query sees: the
                # maximum is finite from the first item on.
                s = jnp.where((t <= q_pos) & (t < length), s, -jnp.inf)
            m = m_sc[i]
            m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            r = jnp.exp(m - m_new)
            l_sc[i] = l_sc[i] * r + p.sum(axis=-1, keepdims=True)
            acc_sc[i] = acc_sc[i] * r + jax.lax.dot_general(
                p.astype(dtype), v, (((1,), (1,)), ((), ())),
                preferred_element_type=f32)
            m_sc[i] = m_new
            return carry

        jax.lax.fori_loop(0, G, head, 0)

    # Wholly at or before the chunk's first query (and so inside the cache):
    # every query sees every position of the block.
    seen = (j + 1) * block - 1 <= offset
    pl.when(seen)(lambda: fold(False))
    pl.when(jnp.logical_not(seen))(lambda: fold(True))

    @pl.when(j == n_blocks - 1)
    def _():
        for i in range(G):
            out_ref[:, i * v_dim:(i + 1) * v_dim] = acc_sc[i] / l_sc[i]


@functools.partial(jax.jit, static_argnames=("nope", "scale", "block", "heads"))
def latent_prefill_attention(q_nope, q_rope, kv_b, cache, slot, offset, *,
                             nope: int, scale: float, block: int,
                             heads: int | None = None):
    """See the module docstring.  ``block``: the cached positions an item
    expands (the whole cache where that is shorter); ``heads``: the heads an
    item takes (:func:`heads_per_group` of the shapes when not given).
    ``slot`` and ``offset`` are traced scalars.  Compiles through Mosaic on
    a TPU, interpreted on the CPU."""
    C, H, _ = q_nope.shape
    rope = q_rope.shape[-1]
    T, latent = cache.shape[1:]
    rank = latent - rope
    per = kv_b.shape[1] // H
    v_dim = per - nope
    blk = min(block, T)
    shape = dict(chunk=C, block=blk, latent=latent, per=per, v_dim=v_dim,
                 rank=rank, itemsize=cache.dtype.itemsize)
    G = heads or heads_per_group(H, **shape)
    offset = jnp.asarray(offset, jnp.int32)
    at = jnp.stack([jnp.asarray(slot, jnp.int32), offset,
                    blocks_read(offset, C, blk, T)])
    return pl.pallas_call(
        functools.partial(_kernel, block=blk, scale=scale, length=T),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(H // G, at[2]),
            in_specs=[
                pl.BlockSpec((C, G * nope), lambda g, j, at: (0, g)),
                pl.BlockSpec((C, G * rope), lambda g, j, at: (0, g)),
                pl.BlockSpec((rank, G * per), lambda g, j, at: (0, g)),
                pl.BlockSpec((None, latent, blk), lambda g, j, at: (at[0], 0, j)),
            ],
            out_specs=pl.BlockSpec((C, G * v_dim), lambda g, j, at: (0, g)),
            scratch_shapes=[
                pltpu.VMEM((G, C, nope), cache.dtype),
                pltpu.VMEM((G, C, rope), cache.dtype),
                pltpu.VMEM((G * per, rank), cache.dtype),
                pltpu.VMEM((G * per, blk), cache.dtype),
                pltpu.VMEM((G, C, 1), jnp.float32),
                pltpu.VMEM((G, C, 1), jnp.float32),
                pltpu.VMEM((G, C, v_dim), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((C, H * v_dim), jnp.float32),
        # In order: the scratch carries a head group's running softmax from
        # block to block.
        compiler_params=compiler_params(
            ("arbitrary", "arbitrary"), vmem_bytes(G, **shape) * 5 // 4),
        interpret=interpret_mode(),
        name=KERNEL_NAME,
    )(
        # Everything as it lies: the heads side by side in the lanes, the
        # cache's positions last (how the array already lies on a TPU).
        at, q_nope.reshape(C, H * nope), q_rope.reshape(C, H * rope), kv_b,
        jnp.swapaxes(cache, 1, 2),
    )
