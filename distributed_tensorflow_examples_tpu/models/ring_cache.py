"""A slot cache whose window layers are RINGS, and the two blocked
attentions that read it: what models/afmoe.py, models/smallthinker.py and
models/nemotron_h.py share.  It names no model: a caller hands in its rows
per block (``attn_block``), the type it multiplies in and the name of its scope; what
a model tells the serve engine of its reads (:func:`decode_rows_read`,
:func:`prefill_rows_read`) takes the model's ``Config``, which lays the
cache out by kind.

THE CACHE, per layer BY KIND, arrays ``k`` and ``v`` a layer (a window
layer's keys are kept as the model attends them, rotated where it rotates):

- a full layer ``[slots + 1, kv_heads, max_len, head_dim]`` each, a
  position's row its own, written in place;
- a window layer a RING ``[slots + 1, kv_heads, R, head_dim]`` each of ``R =
  window + slack`` rows (or ``max_len`` where that is fewer: no position then
  wraps), a position's row ``pos % R``.  The ring is WRITTEN BEFORE IT IS
  ATTENDED, as the full layer is: the slack is what lets a chunk of up to
  ``slack`` tokens be written whole and its FIRST query still find its
  ``window - 1`` predecessors (the rows a chunk at ``offset`` overwrites held
  positions below ``offset + C - R <= offset - window``).  A ring of exactly
  ``window`` rows would have to be attended before it is overwritten - the
  chunk against the old ring and against itself, in two pieces; the slack's
  rows a window layer buy one code path for both kinds.  A chunk may lie
  anywhere on the ring, across its end too (:func:`chunk_write`).

What a row of either kind holds is told BY POSITION ARITHMETIC, never by
clearing (:func:`held_position`): with ``last`` the latest position its
session has written, row ``r`` holds position ``last - (last - r) mod R``;
below 0 it holds nothing of this session (whatever the slot's previous
session left there), above a query's own position or ``window`` or more
behind it the query does not see it.

ON A TPU the step reads of each LIVE slot the blocks of ``attn_block`` rows up
to that slot's own row (a ring: at most ``R``) and no other row:
ops/slot_decode.py's kernel over the (slot, block) pairs that exist (PR 47).
Wherever ``ops/common.py`` ``interpret_mode()`` is true it is the loop the
kernel is held to bit for bit (:func:`step_loop`: a block of EVERY slot a
trip, up to the deepest live slot's row) - models/mla.py's precedent, and for
its reason: interpreted, the kernel makes a CPU step two to three times as
long, and the benchmark's rehearsals end on a clock.  What the step counts
and what it tells the engine it read are those of the form that ran.  The
chunk reads its own slot's blocks up to its last query's row, in plain
``jax.numpy``.  A row of the step that is not
LIVE leaves everything its slot owns unchanged (on a ring its write would
land on a row that a chunk of the session being prefilled there still
reads) and reads nothing.  Its key and value go to a SPARE slot, the last of
each layer's array, which no session is seated in and nothing reads: to
leave a row as it was the step would have to read it first, and with a row
read out of it the compiler lays the whole cache out position-major - a copy
of the whole cache in and another out, every step (the compiled step for a
v5e, PR 39).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.common import interpret_mode
from ..ops.slot_decode import FLOOR, slot_decode_attention

#: What the step's attention counts BY KIND of layer, ``[slots]`` int32 each
#: (:func:`step_attention`): the rows read of a slot (the kernel: whole blocks
#: to the slot's own row; the loop: to the deepest live slot's), and the rows
#: the slot's live session needed (a window layer: at most the window).
ATTN_COUNTS = ("attn_window_rows_read", "attn_window_rows_needed",
               "attn_global_rows_read", "attn_global_rows_needed", "attn_rows_read")


def held_position(last, r, rows: int):
    """The position that cache row ``r`` of ``rows`` holds when ``last`` is
    the latest position its session has written (below 0: none of it)."""
    return last - jnp.mod(last - r, rows)


def softmax_fold(carry, s, v, dtype, spec: str):
    """One block folded into a running softmax: ``carry`` = (maximum, sum,
    weighted values) in float32, ``s`` the block's masked scores (``-inf``
    where unseen).  The maximum starts FINITE (ops/slot_decode.py
    ``FLOOR``): a block may hold nothing a query sees - a ring's rows in any
    order - and ``exp(-inf - -inf)`` would poison the sums."""
    m, l, acc = carry
    m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
    w = jnp.exp(s - m_new)
    r = jnp.exp(m - m_new)
    l = l * r + w.sum(axis=-1, keepdims=True)
    acc = acc * r + jnp.einsum(
        spec, w.astype(dtype), v, preferred_element_type=jnp.float32)
    return m_new, l, acc


def blocks_read(deepest, rows: int, block: int):
    """Blocks of ``min(block, rows)`` cache rows that hold everything up to
    the ``deepest``-th row written (a ring: at most all of it); arrays of
    numpy or of the traced program alike."""
    blk = min(block, rows)
    return (jnp if isinstance(deepest, jax.Array) else np).minimum(
        -(-deepest // blk), -(-rows // blk))


def write_rows(cache, new, pos, live):
    """``cache [S + 1, KV, R, hd]`` with ``new[b] [KV, hd]`` written at row
    ``pos[b] % R`` of every LIVE slot ``b`` and no slot's rows changed else:
    one ``dynamic_update_slice`` a slot, each in place in a donated cache
    (models/transformer.py ``_write_rows`` has the chip reading that chose
    this over a scatter), a row that is not live writing into the SPARE
    slot ``S``."""
    S, R = new.shape[0], cache.shape[2]
    for b in range(S):
        cache = jax.lax.dynamic_update_slice(
            cache, new[b][None, :, None], (jnp.where(live[b], b, S), 0, pos[b] % R, 0))
    return cache


def step_loop(q, ck, cv, pos, live, window, *, attn_block: int):
    """:func:`attend_step` in plain ``jax.numpy``, the CPU's form and the
    reference ops/slot_decode.py's kernel is held to: a block of
    ``attn_block`` rows of EVERY slot a trip, up to the block that holds
    the deepest live slot's row (a ring: at most ``R``) - the loop of
    models/mla.py ``_absorbed_loop``, for grouped heads and rings."""
    S, (_, KV, R, hd) = q.shape[0], ck.shape
    blk = min(attn_block, R)
    n = jnp.where(live, pos + 1, 0)
    n_blocks = blocks_read(jnp.max(n), R, attn_block)
    scale = 1.0 / math.sqrt(hd)

    def body(i, carry):
        # Where R is no multiple of the block the last one is read shifted
        # back inside the cache and what it shares with the block before
        # is masked.
        start = jnp.minimum(i * blk, R - blk)
        k, v = (jax.lax.dynamic_slice(c, (0, 0, start, 0), (S, KV, blk, hd))
                for c in (ck, cv))
        s = jnp.einsum("skgd,sktd->skgt", q, k,
                       preferred_element_type=jnp.float32) * scale
        r = start + jnp.arange(blk)
        held = held_position(pos[:, None], r[None, :], R)  # [S, blk]
        seen = (r >= i * blk)[None, :] & (held >= 0) & live[:, None]
        if window is not None:
            seen &= pos[:, None] - held < window
        s = jnp.where(seen[:, None, None, :], s, -jnp.inf)
        return softmax_fold(carry, s, v, cv.dtype, "skgt,sktd->skgd")

    stat = jnp.zeros(q.shape[:3] + (1,), jnp.float32)
    _, l, acc = jax.lax.fori_loop(
        0, n_blocks, body, (stat + FLOOR, stat, jnp.zeros(q.shape, jnp.float32)))
    o = acc / jnp.where(l == 0, 1.0, l)  # a slot that read nothing: zeros
    return o, jnp.broadcast_to(jnp.minimum(n_blocks * blk, R), (S,))


def attend_step(q, ck, cv, pos, live, window, *, attn_block: int, scope: str):
    """One query a slot against that slot's rows: ``q [S, KV, G, hd]``,
    ``ck, cv [S + 1, KV, R, hd]`` (the slot's row at ``pos`` already
    written) -> ``([S, KV, G, hd]`` float32, rows read a slot ``[S]``)``; a
    live slot ``b`` attends over its positions ``<= pos[b]`` (and, with
    ``window``, fewer than ``window`` behind it), one that is not over
    nothing (zeros).  On a TPU ops/slot_decode.py's kernel, which reads each
    live slot to its own row; wherever ``interpret_mode()`` is true
    :func:`step_loop`, which reads every slot to the deepest."""
    with jax.named_scope(scope):
        if interpret_mode():
            return step_loop(q, ck, cv, pos, live, window, attn_block=attn_block)
        return slot_decode_attention(q, ck, cv, pos, live, window, block=attn_block)


def step_attention(q, new, layer, pos, live, window, counters, *, attn_block: int,
                   scope: str):
    """One layer of the step: the rows' keys and values ``new [S, 2, KV, hd]``
    written into ``layer`` (``{"k", "v"}``, :func:`write_rows`), the queries
    ``q [S, KV, G, hd]`` attended over them (:func:`attend_step`), and what
    was read and needed added to ``counters`` (a dict, changed in place:
    :data:`ATTN_COUNTS`) -> ``([S, KV, G, hd]`` float32, the layer written``)``."""
    ck = write_rows(layer["k"], new[:, 0], pos, live)
    cv = write_rows(layer["v"], new[:, 1], pos, live)
    o, read = attend_step(q, ck, cv, pos, live, window, attn_block=attn_block,
                          scope=scope)
    need = jnp.where(live, pos + 1, 0)
    kind = "global" if window is None else "window"
    if window is not None:
        need = jnp.minimum(need, window)
    counters[f"attn_{kind}_rows_read"] += read
    counters[f"attn_{kind}_rows_needed"] += need
    counters["attn_rows_read"] += read
    return o, {"k": ck, "v": cv}


def chunk_write(cache, new, slot, offset, n_valid):
    """``cache [S + 1, KV, R, hd]`` with ``new [KV, C, hd]`` rows ``[0,
    n_valid)`` written at rows ``(offset + i) % R`` of ``slot`` and nothing
    else changed; returns the cache and the slot's rows ``[KV, R, hd]``.
    THE SLOT'S ROWS ARE READ, CHANGED AND WRITTEN BACK WHOLE (with 4 K/V heads
    of 128, 2.6 MB a ring of 2,560 rows, 16.8 MB a full layer of 16,384): the chunk may lie anywhere,
    across a ring's end too, and nothing is cut out of the cache at a traced
    ROW - read a window of rows at one, change it and write it back, and the
    compiler lays the whole cache out position-major, a copy of every slot
    in and another out each chunk (the compiled chunk for a v5e, PR 39)."""
    _, KV, R, hd = cache.shape
    C = new.shape[1]
    first = offset % R
    old = jax.lax.dynamic_slice(cache, (slot, 0, 0, 0), (1, KV, R, hd))[0]
    # Row r is token (r - first) mod R's: the chunk laid out from row 0,
    # then turned to where it starts.
    at_home = jnp.roll(jnp.pad(new, ((0, 0), (0, R - C), (0, 0))), first, axis=1)
    own = (jnp.mod(jnp.arange(R) - first, R) < n_valid)[None, :, None]
    rows = jnp.where(own, at_home, old)
    return jax.lax.dynamic_update_slice(cache, rows[None], (slot, 0, 0, 0)), rows


def attend_chunk(q, k_rows, v_rows, offset, n_valid, window, *, attn_block: int,
                 dtype, scope: str):
    """``q [C, KV, G, hd]`` - the queries at positions ``offset .. offset +
    C - 1`` of a slot, the first ``n_valid`` real - against that slot's rows
    ``k_rows, v_rows [KV, R, hd]`` (the valid ones' own already written) ->
    ``[C, KV, G, hd]`` float32; a block of rows a trip, no further than the
    last query's row.  A padding query sees what the last valid one sees
    (zeros where there is none) and nothing keeps its result."""
    C = q.shape[0]
    KV, R, hd = k_rows.shape
    blk = min(attn_block, R)
    t = offset + jnp.arange(C)
    last = offset + n_valid - 1
    scale = 1.0 / math.sqrt(hd)

    def body(i, carry):
        start = jnp.minimum(i * blk, R - blk)
        k, v = (jax.lax.dynamic_slice_in_dim(rows, start, blk, axis=1)
                for rows in (k_rows, v_rows))
        s = jnp.einsum("ckgd,ktd->kgct", q, k,
                       preferred_element_type=jnp.float32) * scale
        r = start + jnp.arange(blk)
        held = held_position(last, r, R)  # [blk]
        behind = t[:, None] - held[None, :]  # [C, blk]
        seen = ((r >= i * blk) & (held >= 0))[None, :] & (behind >= 0)
        if window is not None:
            seen &= behind < window
        s = jnp.where(seen[None, None], s, -jnp.inf)
        return softmax_fold(carry, s, v, dtype, "kgct,ktd->kgcd")

    G = q.shape[2]
    stat = jnp.zeros((KV, G, C, 1), jnp.float32)
    with jax.named_scope(scope):
        _, l, acc = jax.lax.fori_loop(
            0, blocks_read(offset + C, R, attn_block), body,
            (stat + FLOOR, stat, jnp.zeros((KV, G, C, hd), jnp.float32)))
    return jnp.moveaxis(acc / jnp.where(l == 0, 1.0, l), 2, 0)


def chunk_attention(q, new, layer, slot, offset, n_valid, window, *, slack: int,
                    attn_block: int, dtype, scope: str):
    """One layer of the chunk: the tokens' keys and values ``new [C, 2, KV,
    hd]`` written into ``slot``'s rows of ``layer`` (:func:`chunk_write`) and
    the queries ``q [C, KV, G, hd]`` attended over them
    (:func:`attend_chunk`) -> ``([C, KV, G, hd]`` float32, the layer
    written``)``.  A chunk wider than the ``slack`` of a ring that wraps
    would overwrite rows its first query still reads: refused."""
    C, R = q.shape[0], layer["k"].shape[2]
    if window is not None and R == window + slack and C > slack:
        raise ValueError(
            f"a chunk of {C} tokens would overwrite rows of a ring of "
            f"{R} that its first query still reads: "
            f"ring_slack is {slack}")
    new = jnp.moveaxis(new, 0, 2)  # [2, KV, C, hd]
    ck, k_rows = chunk_write(layer["k"], new[0], slot, offset, n_valid)
    cv, v_rows = chunk_write(layer["v"], new[1], slot, offset, n_valid)
    o = attend_chunk(q, k_rows, v_rows, offset, n_valid, window,
                     attn_block=attn_block, dtype=dtype, scope=scope)
    return o, {"k": ck, "v": cv}


# -- what a model tells the serve engine --------------------------------------
#
# ``cfg`` below is a model's ``Config`` that lays its cache out by kind:
# ``cfg.layers``, ``cfg.cache_rows(i, max_len)`` and ``cfg.attn_block``.


def decode_rows_read(cfg, pos, live, max_len: int) -> float:
    """Cache positions one decode step reads A SLOT IN THE MEAN LAYER, from
    the host's ``pos [S]`` and ``live [S]``, as :func:`attend_step`'s form
    that runs here reads them: the kernel each live slot's own whole blocks
    up to its row and nothing of a slot that is not live, the loop every
    slot's up to the deepest live slot's row; in a ring at most the ring."""
    n = np.where(live, pos + 1, 0)
    return _mean_rows_read(cfg, n.max() if interpret_mode() else n, max_len)


def prefill_rows_read(cfg, offset: int, chunk: int, max_len: int) -> float:
    """Cache positions the attention of one chunk of ``chunk`` queries at
    ``offset`` reads in the mean layer (:func:`attend_chunk`)."""
    return _mean_rows_read(cfg, offset + chunk, max_len)


def _mean_rows_read(cfg, deepest, max_len: int) -> float:
    """Rows that either attention reads A SLOT IN THE MEAN LAYER when the
    deepest row a slot needs is its ``deepest``-th written (a number, or one
    a slot): whole blocks, in a ring at most the ring."""
    blk = cfg.attn_block
    return float(np.mean([
        np.minimum(blocks_read(deepest, rows, blk) * min(blk, rows), rows)
        for rows in (cfg.cache_rows(i, max_len) for i in cfg.layers)]))
