"""Latent attention (MLA): one sub-layer, as the models that have it share it.

``H`` heads, for a normed ``h [.., D]``:

    cq        = N(q_a . h) * q_scale                       [q_lora_rank]
    q         = q_b . cq                 -> H x (nope + rope)
    ckv | kr  = kv_a . h                 [kv_lora_rank + rope]
    c         = N(ckv) * kv_scale
    k_nope|v  = kv_b . c                 -> H x (nope + v_dim)
    k         = [k_nope | rope(kr)]      (ONE kr for all heads)
    o         = o . softmax(softmax_scale x rope-d q . k) v

(scaling ``cq`` scales both parts of ``q``).  Rotary pairs are interleaved
(``layers.rope_interleaved``) and turn by ``pos x inv_freq``.  What differs
between the models is in :class:`Spec`: the widths, the two rank scales, the
rotary frequencies, the softmax's scale, the blocks.

What a position leaves behind is ``c`` (normed and scaled) and the rotated
``kr``: ``kv_lora_rank + rope`` values (:attr:`Spec.latent`).  A sub-layer's
cache is ``[slots, max_len, latent]`` in ``dtype`` and NEVER holds an
expanded key or value.

Two forms of the same attention:

- the one-token step ABSORBS ``kv_b`` into the query and the output
  (:func:`attend_absorbed`): ``q_nope . W_uk`` against ``c``, the weighted
  sum of ``c`` then through ``W_uv`` - ``H`` query heads over ONE latent row
  a position, whose first ``kv_lora_rank`` columns are the values too.  On
  a TPU the running softmax over the cache is ONE kernel a sub-layer
  (ops/latent_decode.py): each LIVE slot is read a block of positions at a
  time up to ITS OWN row, a slot that is not live not at all
  (:func:`decode_rows_read` is the host's count of that).  On the CPU,
  where the kernel would be interpreted, the same softmax is a loop over
  blocks of every slot up to the deepest live row (:func:`_absorbed_loop`).
  The new row is written in place (:func:`write_rows`).
- the prefill chunk and the full forward EXPAND a block of cached latents
  at a time into keys and values (``kv_b . c``) and attend as published
  (:func:`chunk_write` puts a chunk's rows into one slot first).  On a TPU
  the chunk's running softmax over the slot's blocks is ONE kernel a
  sub-layer (ops/latent_prefill.py): it reads the slot's rows where they
  lie in the cache, up to the block that holds the chunk's last query, and
  a block's scores and weights never leave VMEM
  (:func:`prefill_rows_read` is the host's count of the rows).  On the
  CPU, where the kernel would be interpreted, the chunk runs the same
  softmax as a loop over a copy of the slot's rows
  (:func:`attend_expanded`), which is also the kernel's reference and what
  the full forward runs on every platform (under ``vmap``: parity tests and
  ``generate``'s reference).

Precision: parameters in ``dtype``; products in it with float32
accumulation; norms, rotary and softmax in float32.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import latent_decode, latent_prefill
from ..ops.common import interpret_mode
from . import layers


@dataclasses.dataclass(frozen=True)
class Spec:
    """One model's latent attention.  ``inv_freq`` is an array (build the
    spec inside the traced program that uses it); nothing hashes a spec."""

    heads: int
    q_lora_rank: int
    kv_lora_rank: int
    nope: int  # a head's query/key values that carry no position
    rope: int  # ... and those that are rotated
    v_dim: int
    q_scale: float  # multiplies the normed query latent
    kv_scale: float  # multiplies the normed key/value latent
    softmax_scale: float
    inv_freq: Any  # [rope // 2] float32: radians a position, by pair
    eps: float
    dtype: Any
    #: Cache positions the step's attention reads at a time - an item of the
    #: kernel's grid, a trip of the CPU's loop - or the whole cache, where
    #: that is shorter: a larger block trades positions read past a slot's
    #: row (half a block of every live slot) against grid steps.
    decode_block: int
    #: Cached positions a chunk expands and attends over at a time.
    prefill_block: int

    @property
    def latent(self) -> int:
        """Values a position leaves in the cache."""
        return self.kv_lora_rank + self.rope


def init(spec: Spec, hidden: int, rng: jax.Array, *, std: float, out_std: float):
    """A sub-layer's leaves: kernels normal ``std`` (``o``, which writes the
    residual stream, ``out_std``), norms 1; all in ``spec.dtype``."""
    dt, H = spec.dtype, spec.heads
    k = jax.random.split(rng, 5)
    normal = lambda key, shape, s=std: (s * jax.random.normal(key, shape)).astype(dt)
    return {
        "q_a": {"kernel": normal(k[0], (hidden, spec.q_lora_rank))},
        "q_norm": layers.rmsnorm_init(spec.q_lora_rank, dt),
        "q_b": {"kernel": normal(k[1], (spec.q_lora_rank, H * (spec.nope + spec.rope)))},
        "kv_a": {"kernel": normal(k[2], (hidden, spec.latent))},
        "kv_norm": layers.rmsnorm_init(spec.kv_lora_rank, dt),
        "kv_b": {"kernel": normal(k[3], (spec.kv_lora_rank, H * (spec.nope + spec.v_dim)))},
        "o": {"kernel": normal(k[4], (H * spec.v_dim, hidden), out_std)},
    }


def _mm(spec: Spec, p, x):
    """``x @ kernel``: operands in ``dtype``, float32 out."""
    return layers.dense(p, x.astype(spec.dtype))


def out_proj(spec: Spec, p, o):
    """The heads' results ``[.., H x v_dim]`` back to the residual stream."""
    return _mm(spec, p["o"], o)


def query_and_latent(spec: Spec, p, h, pos):
    """From the normed ``h [.., D]`` at positions ``pos [..]``: the query
    ``q_nope [.., H, nope]``, ``q_rope [.., H, rope]`` (rotated) and the
    position's cache row ``[.., latent]`` = ``c | rotated kr``, all in
    ``dtype``."""
    H, nope, rope, R = spec.heads, spec.nope, spec.rope, spec.kv_lora_rank
    cq = layers.rmsnorm(p["q_norm"], _mm(spec, p["q_a"], h), spec.eps) * spec.q_scale
    q = _mm(spec, p["q_b"], cq).reshape(h.shape[:-1] + (H, nope + rope))
    ckv = _mm(spec, p["kv_a"], h)
    c = layers.rmsnorm(p["kv_norm"], ckv[..., :R], spec.eps) * spec.kv_scale
    cos, sin = layers.rope_angles_at(pos, spec.inv_freq)
    q_rope = layers.rope_interleaved(q[..., nope:], cos[..., None, :], sin[..., None, :])
    kr = layers.rope_interleaved(ckv[..., R:], cos, sin)
    row = jnp.concatenate([c, kr], axis=-1).astype(spec.dtype)
    return q[..., :nope].astype(spec.dtype), q_rope.astype(spec.dtype), row


def _kv_b(spec: Spec, p):
    """``kv_b`` by head: ``(W_uk [R, H, nope], W_uv [R, H, v_dim])``."""
    w = p["kv_b"]["kernel"].reshape(spec.kv_lora_rank, spec.heads, spec.nope + spec.v_dim)
    return w[..., :spec.nope], w[..., spec.nope:]


def attend_expanded(spec: Spec, p, q_nope, q_rope, rows, q_pos, n_blocks, block):
    """Queries ``[C, H, .]`` at positions ``q_pos [C]`` of ONE sequence
    against its cached rows ``rows [T, latent]``: the first ``n_blocks``
    blocks of ``block`` positions (``n_blocks`` may be traced), each expanded
    into keys and values and folded into a running softmax; a query sees the
    positions ``<=`` its own.  Returns ``[C, H x v_dim]`` float32.  Block
    ``i`` holds positions ``[i block, (i + 1) block)``; where ``T`` is no
    multiple of the block the last one is read shifted back inside the cache
    and what it shares with the block before is masked."""
    T, R = rows.shape[0], spec.kv_lora_rank
    C, H = q_nope.shape[:2]
    w_uk, w_uv = _kv_b(spec, p)
    f32 = jnp.float32

    def body(i, carry):
        m, l, acc = carry
        start = jnp.minimum(i * block, T - block)
        blk = jax.lax.dynamic_slice_in_dim(rows, start, block, axis=0)
        c, kr = blk[:, :R], blk[:, R:]
        k = jnp.einsum("tr,rhd->thd", c, w_uk,
                       preferred_element_type=f32).astype(spec.dtype)
        v = jnp.einsum("tr,rhd->thd", c, w_uv,
                       preferred_element_type=f32).astype(spec.dtype)
        s = jnp.einsum("qhd,thd->hqt", q_nope, k, preferred_element_type=f32)
        s = s + jnp.einsum("qhd,td->hqt", q_rope, kr, preferred_element_type=f32)
        s = s * spec.softmax_scale
        t = start + jnp.arange(block)
        own = (t >= i * block)[None, :] & (t[None, :] <= q_pos[:, None])
        s = jnp.where(own[None], s, -jnp.inf)
        # Block 0 holds position 0, which every query sees: the maximum is
        # finite from the first trip on.
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        w = jnp.exp(s - m_new)
        r = jnp.exp(m - m_new)
        l = l * r + w.sum(axis=-1, keepdims=True)
        acc = acc * r + jnp.einsum(
            "hqt,thd->hqd", w.astype(spec.dtype), v, preferred_element_type=f32)
        return m_new, l, acc

    stat = jnp.zeros((H, C, 1), f32)
    _, l, acc = jax.lax.fori_loop(
        0, n_blocks, body,
        (stat - jnp.inf, stat, jnp.zeros((H, C, spec.v_dim), f32)),
    )
    return jnp.moveaxis(acc / l, 0, 1).reshape(C, -1)


def decode_rows_read(block: int, pos, live, max_len: int) -> float:
    """Cache positions one decode step reads A SLOT IN THE MEAN, from the
    host's ``pos [S]`` and ``live [S]``: whole blocks of ``block`` positions
    up to each live slot's own row, none of a slot that is not live, summed
    and divided by the slots.  It counts what the KERNEL brings in
    (ops/latent_decode.py: what the chip does); the CPU's loop reads every
    slot to the deepest live row's block."""
    blk = min(block, max_len)
    n = np.where(live, pos + 1, 0)
    read = np.minimum(latent_decode.blocks_read(n, blk) * blk, max_len)
    return float(read.sum()) / len(n)


def write_rows(cache, new, pos):
    """cache ``[S, T, latent]`` with ``new[b]`` written at position
    ``pos[b]`` of slot ``b`` and nothing else changed: one
    ``dynamic_update_slice`` a slot, each in place in a donated cache
    (models/transformer.py ``_write_rows`` has the chip reading that chose
    this over a scatter)."""
    for b in range(cache.shape[0]):
        cache = jax.lax.dynamic_update_slice(cache, new[b:b + 1, None], (b, pos[b], 0))
    return cache


def _absorbed_loop(q, cache, n, *, values: int, scale: float, block: int):
    """ops/latent_decode.py ``latent_decode_attention`` in plain
    ``jax.numpy``, the CPU's form and the kernel's reference: a block of
    positions of EVERY slot a trip, up to the block that holds the deepest
    row read (a block wholly past a slot's ``n`` is an exact no-op for that
    slot), the slots that read nothing set to the kernel's zeros.  Block
    ``i`` holds positions ``[i block, (i + 1) block)``; where ``T`` is no
    multiple of the block the last one is read shifted back inside the
    cache and what it shares with the block before is masked."""
    S, T, _ = cache.shape
    H = q.shape[1]
    blk = min(block, T)
    f32 = jnp.float32

    def body(i, carry):
        m, l, acc = carry
        start = jnp.minimum(i * blk, T - blk)
        rows = jax.lax.dynamic_slice_in_dim(cache, start, blk, axis=1)
        s = jnp.einsum("shc,stc->sht", q, rows, preferred_element_type=f32)
        s = s * scale
        t = start + jnp.arange(blk)
        own = (t >= i * blk)[None, :] & (t[None, :] < n[:, None])
        s = jnp.where(own[:, None, :], s, -jnp.inf)
        # Block 0 holds position 0, which every slot that reads anything
        # sees: its maximum is finite from the first trip on.  A slot that
        # reads nothing carries NaN to the end, where it is set to zeros.
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        w = jnp.exp(s - m_new)
        r = jnp.exp(m - m_new)
        l = l * r + w.sum(axis=-1, keepdims=True)
        acc = acc * r + jnp.einsum(
            "sht,str->shr", w.astype(cache.dtype), rows[..., :values],
            preferred_element_type=f32)
        return m_new, l, acc

    stat = jnp.zeros((S, H, 1), f32)
    _, l, acc = jax.lax.fori_loop(
        0, latent_decode.blocks_read(jnp.max(n), blk), body,
        (stat - jnp.inf, stat, jnp.zeros((S, H, values), f32)),
    )
    return jnp.where((n > 0)[:, None, None], acc / l, 0.0)


def attend_absorbed(spec: Spec, p, q_nope, q_rope, cache, pos, live):
    """One query a slot against that slot's latent rows: ``q_nope [S, H,
    nope]``, ``q_rope [S, H, rope]``, ``cache [S, T, latent]``, ``pos [S]``,
    ``live [S]`` bool -> ``[S, H x v_dim]`` float32; a live slot ``b``
    attends over its positions ``<= pos[b]``, a slot that is not live reads
    nothing and its result is what ``W_uv`` makes of zeros (finite; it means
    nothing).  ``kv_b`` never touches the cache: its key half goes into the
    query (``q_nope . W_uk``, beside ``q_rope`` ONE latent-wide query), its
    value half comes after the weighted sum of the rows' first
    ``kv_lora_rank`` columns.  Between the two, one form a platform, chosen
    by the package's one platform test: the kernel on a TPU, the loop where
    the kernel would be interpreted (the CPU, where a grid step of the
    interpreter costs what a whole trip of the loop does)."""
    S = cache.shape[0]
    f32 = jnp.float32
    w_uk, w_uv = _kv_b(spec, p)
    q_lat = jnp.einsum("shd,rhd->shr", q_nope, w_uk, preferred_element_type=f32)
    q = jnp.concatenate([q_lat.astype(spec.dtype), q_rope], axis=-1)  # [S, H, latent]
    n = jnp.where(live, pos + 1, 0)
    attend = _absorbed_loop if interpret_mode() else latent_decode.latent_decode_attention
    c = attend(q, cache, n, values=spec.kv_lora_rank, scale=spec.softmax_scale,
               block=spec.decode_block)
    o = jnp.einsum("shr,rhd->shd", c.astype(spec.dtype), w_uv,
                   preferred_element_type=f32)
    return o.reshape(S, -1)


def chunk_write(cache, new, slot, offset, n_valid):
    """``cache [S, T, latent]`` with ``new [C, latent]`` rows ``[0,
    n_valid)`` written at ``[slot, offset:offset + n_valid]`` and nothing
    else touched.  The window starts at ``min(offset, T - C)``
    (``dynamic_update_slice`` clamps a start that overruns and would
    overwrite earlier rows), the chunk rolled inside it; as
    models/transformer.py ``_block_prefill``."""
    C, W = new.shape
    T = cache.shape[1]
    start = jnp.clip(offset, 0, T - C)
    shift = offset - start
    i = jnp.arange(C) - shift
    own = ((i >= 0) & (i < n_valid))[None, :, None]
    at = (slot, start, 0)
    old = jax.lax.dynamic_slice(cache, at, (1, C, W))
    win = jnp.where(own, jnp.roll(new[None], shift, axis=1), old)
    return jax.lax.dynamic_update_slice(cache, win, at)


def prefill_rows_read(block: int, offset: int, chunk: int, max_len: int) -> int:
    """Cache positions the attention of one chunk of ``chunk`` queries at
    ``offset`` reads: whole blocks of ``block`` positions up to the chunk's
    last query, at most the cache - the grid of ops/latent_prefill.py and
    the trips of :func:`attend_expanded` alike."""
    blk = min(block, max_len)
    return min(latent_prefill.blocks_read(offset, chunk, blk, max_len) * blk, max_len)


# ----------------------------------------------------------------------------
# The sub-layer on each of the three paths
# ----------------------------------------------------------------------------


def forward(spec: Spec, p, h):
    """The full forward's sub-layer: the normed ``h [B, L, D]`` -> ``[B, L,
    D]`` float32, causal; the expanded form, a sequence at a time."""
    L = h.shape[1]
    pos = jnp.arange(L)
    block = min(spec.prefill_block, L)
    n_blocks = -(-L // block)
    q_nope, q_rope, rows = query_and_latent(spec, p, h, pos[None])
    with jax.named_scope("mla/prefill"):
        o = jax.vmap(lambda qn, qr, r: attend_expanded(
            spec, p, qn, qr, r, pos, n_blocks, block))(q_nope, q_rope, rows)
    return out_proj(spec, p, o)


def decode(spec: Spec, p, h, cache, pos, live):
    """The one-token step's sub-layer: the normed ``h [S, D]`` at per-row
    positions ``pos [S]``, ``live [S]`` bool -> (``[S, D]`` float32, the
    cache with each row's latent written at its position).  A row that is
    not live writes its latent like the others and attends over nothing."""
    q_nope, q_rope, row = query_and_latent(spec, p, h, pos)
    with jax.named_scope("mla/decode"):
        cache = write_rows(cache, row, pos)
        o = attend_absorbed(spec, p, q_nope, q_rope, cache, pos, live)
    return out_proj(spec, p, o), cache


def prefill(spec: Spec, p, h, cache, slot, offset, n_valid):
    """The prefill chunk's sub-layer: the normed ``h [C, D]`` of ONE slot's
    positions ``offset .. offset + C - 1``, the first ``n_valid`` real ->
    (``[C, D]`` float32, the cache with the valid rows written); reads no
    further than ``offset + C``.  The attention is one form a platform, as
    :func:`attend_absorbed`'s: the kernel on a TPU (it reads the slot's rows
    where they lie), the loop over a copy of them where the kernel would be
    interpreted."""
    C, T = h.shape[0], cache.shape[1]
    q_pos = offset + jnp.arange(C)
    q_nope, q_rope, new = query_and_latent(spec, p, h, q_pos)
    with jax.named_scope("mla/prefill"):
        cache = chunk_write(cache, new, slot, offset, n_valid)
        if interpret_mode():
            block = min(spec.prefill_block, T)
            rows = jax.lax.dynamic_index_in_dim(cache, slot, keepdims=False)
            o = attend_expanded(
                spec, p, q_nope, q_rope, rows, q_pos,
                latent_prefill.blocks_read(offset, C, block, T), block)
        else:
            o = latent_prefill.latent_prefill_attention(
                q_nope, q_rope, p["kv_b"]["kernel"], cache, slot, offset,
                nope=spec.nope, scale=spec.softmax_scale, block=spec.prefill_block)
    return out_proj(spec, p, o), cache
