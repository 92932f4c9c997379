"""LongCat-Flash: latent attention (MLA) and a shortcut-connected expert
layer with zero-compute experts (Meituan's LongCat-Flash / -Omni language
model; the published ``config.json`` keys are this module's ``Config``).

Every one of the ``num_layers`` layers is a DOUBLE layer - two latent-
attention sub-layers, two dense gated-SiLU feed-forwards and ONE expert
layer whose result joins the residual a sub-layer late.  With ``N`` an
RMSNorm (four a layer):

    x1 = x  + MLA_0(N(x))        u = N(x1)        m = MoE(u)
    x2 = x1 + FFN_0(u)
    x3 = x2 + MLA_1(N(x2))
    y  = x3 + FFN_1(N(x3)) + m

MLA, ``H`` heads, for a normed ``h``:

    cq        = N(q_a . h) * sqrt(D / q_lora_rank)         [q_lora_rank]
    q         = q_b . cq                 -> H x (nope + rope)
    ckv | kr  = kv_a . h                 [kv_lora_rank + rope]
    c         = N(ckv) * sqrt(D / kv_lora_rank)
    k_nope|v  = kv_b . c                 -> H x (nope + v_head_dim)
    k         = [k_nope | rope(kr)]      (ONE kr for all heads)
    o         = o_proj . softmax(rope-d q . k / sqrt(nope + rope)) v

(the two ``sqrt`` factors are ``mla_scale_q_lora`` / ``mla_scale_kv_lora``;
scaling ``cq`` scales both parts of ``q``).  Rotary pairs are interleaved
(``layers.rope_interleaved``), no long-context scaling.  What a position
leaves behind is ``c`` (normed and scaled) and the rotated ``kr``:
``kv_lora_rank + rope`` values, 576 where expanded keys and values are
20480.  The cache is ``[slots, max_len, 576]`` a sub-layer in
``param_dtype`` and NEVER holds an expanded key or value.

Two forms of the same attention:

- the one-token step ABSORBS ``kv_b`` into the query and the output:
  ``q_nope . W_uk`` against ``c``, the weighted sum of ``c`` then through
  ``W_uv`` - ``H`` query heads over ONE latent row a position, whose first
  ``kv_lora_rank`` columns are the values too.  The cache is read a block
  of positions at a time as a running softmax, no further than the deepest
  row (:func:`decode_rows_read`); the new row is written in place.
- the prefill chunk and the full forward EXPAND a block of cached latents
  at a time into keys and values (``kv_b . c``) and attend as published.
  By the counts of a 512-token chunk that is the cheaper form at every
  depth - absorbing trades the expansion (``T x 16.8`` MFLOP) for products
  three and four times as wide (``T x 67`` MFLOP more) - and so the chip
  reads it: at offsets 0 / 3584 / 7168 a chunk takes 30.4 / 45.8 / 60.1 ms
  expanded and 33.0 / 49.9 / 66.7 absorbed (my chip run, PR 31).

MoE (ops/moe.py ``apply_share``): softmax router in float32 over
``n_routed_experts + zero_expert_num``; ``moe_topk`` of ``s + bias``;
weights ``routed_scaling_factor x s``, not renormalised; the zero-compute
experts are the identity.  THE SHARE: ``experts_held`` experts from
``expert_first`` on live here (0 = all), and a choice on any other routed
expert adds nothing - the chip's part of the layer in a deployment that
spreads the experts over chips while attention and the dense feed-forwards
are whole on each.  ``vocab_rows`` (0 = all) is the slice of the vocabulary
whose table rows and head columns live here; tokens and logits are over
the slice.

Precision: parameters in ``param_dtype`` (bfloat16); products in it with
float32 accumulation; residual stream, norms, rotary, router and softmax in
float32.

What a session owns in the cache: its rows of every sub-layer's latents.
A row is written once and may be written again (a row that is not live
writes at its position what the session's first real step writes anew), so
the engine's key/value contract holds; the step still asks for ``live``
(serve/model_server.py ``_DecodeEngine``) because it COUNTS: the cache
tree's ``counters`` entry holds int32 sums over layers and launches of what
the expert layer did (``moe_*``: ops/moe.py ``SHARE_COUNTS``; the chunk's
part of three of them once more as ``moe_chunk_*``), added to on the device
by the step and the chunk, and only live rows are counted or get expert
rows.

Serving only: no loss, no mesh.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from ..ops import moe as moe_ops
from . import layers

#: Cache positions one trip of the step's attention loop reads (or the whole
#: cache, where that is shorter).  A trip reads ``slots x block`` latent
#: rows of 1.1 KB and is a dozen operations, so a larger block trades
#: positions read past the deepest row (half a block of every slot) against
#: trips.  Chosen on one v5e chip at the served widths, 32 slots x 8192, 20
#: rows live (my chip run, PR 31; PERF.md section 6): with the deepest row
#: at 1500 / 4000 / 7900 the step takes 14.3 / 17.8 / 22.8 ms at 256 (what
#: models/transformer.py chose at 8 x 2048), 14.0 / 16.9 / 21.2 at 512 and
#: 14.7 / 15.8 / 20.3 at 1024: with prompts of thousands of tokens the
#: deepest row stands past 7000 in nine steps of ten, where 1024 is best.
DECODE_BLOCK = 1024
#: Cached positions a prefill chunk expands and attends over at a time
#: (1024 reads 35.6 / 57.0 / 84.7 ms at those offsets: same run).
PREFILL_BLOCK = 512


@dataclasses.dataclass(frozen=True)
class Config:
    """The published keys (LongCat-Flash-Omni's values as defaults) and the
    share.  Fixed by the family and not keys here: no bias anywhere, no
    shared expert, identity zero-compute experts, untied head."""

    vocab_size: int = 131072
    hidden_size: int = 6144
    ffn_hidden_size: int = 12288
    expert_ffn_hidden_size: int = 2048
    num_layers: int = 28
    num_attention_heads: int = 64
    kv_lora_rank: int = 512
    q_lora_rank: int = 1536
    qk_rope_head_dim: int = 64
    qk_nope_head_dim: int = 128
    v_head_dim: int = 128
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    routed_scaling_factor: float = 6.0
    n_routed_experts: int = 512
    zero_expert_num: int = 256
    moe_topk: int = 12
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e7
    #: The share (module docstring); 0 = everything.
    experts_held: int = 0
    expert_first: int = 0
    vocab_rows: int = 0
    param_dtype: str = "bfloat16"

    def __post_init__(self):
        if self.expert_first + self.held > self.n_routed_experts:
            raise ValueError("the held experts run past n_routed_experts")

    @property
    def dtype(self):
        return jnp.dtype(self.param_dtype)

    @property
    def held(self) -> int:
        return self.experts_held or self.n_routed_experts

    @property
    def vocab(self) -> int:
        return self.vocab_rows or self.vocab_size

    @property
    def latent(self) -> int:
        """Values a position leaves in a sub-layer's cache."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def share(self) -> moe_ops.ShareConfig:
        return moe_ops.ShareConfig(
            n_experts=self.n_routed_experts, n_zero=self.zero_expert_num,
            top_k=self.moe_topk, scale=float(self.routed_scaling_factor),
            first=self.expert_first, held=self.held,
        )


# ----------------------------------------------------------------------------
# Parameters and cache
# ----------------------------------------------------------------------------


def init(cfg: Config, rng: jax.Array):
    """Kernels and table normal 0.02 (projections back into the residual
    stream scaled by ``1 / sqrt(4 L)``: four a double layer), norms 1, the
    router's bias normal 1e-4 (small beside the scores' spread: it moves
    near-ties, not every token onto the same experts); all in
    ``param_dtype``."""
    dt = cfg.dtype
    D, H = cfg.hidden_size, cfg.num_attention_heads
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    F = cfg.expert_ffn_hidden_size
    n_all = cfg.n_routed_experts + cfg.zero_expert_num
    res = 0.02 / math.sqrt(4 * cfg.num_layers)

    def normal(k, shape, std=0.02):
        return (std * jax.random.normal(k, shape)).astype(dt)

    def attention(k):
        k = jax.random.split(k, 5)
        return {
            "q_a": {"kernel": normal(k[0], (D, cfg.q_lora_rank))},
            "q_norm": layers.rmsnorm_init(cfg.q_lora_rank, dt),
            "q_b": {"kernel": normal(k[1], (cfg.q_lora_rank, H * (nope + rope)))},
            "kv_a": {"kernel": normal(k[2], (D, cfg.latent))},
            "kv_norm": layers.rmsnorm_init(cfg.kv_lora_rank, dt),
            "kv_b": {"kernel": normal(k[3], (cfg.kv_lora_rank, H * (nope + vd)))},
            "o": {"kernel": normal(k[4], (H * vd, D), res)},
        }

    def moe(k):
        k = jax.random.split(k, 5)
        return {
            "router": {"kernel": normal(k[0], (D, n_all)),
                       "bias": normal(k[1], (n_all,), 1e-4)},
            "gate": normal(k[2], (cfg.held, D, F)),
            "up": normal(k[3], (cfg.held, D, F)),
            "down": normal(k[4], (cfg.held, F, D), res),
        }

    keys = jax.random.split(rng, cfg.num_layers + 2)
    params = {
        "emb": {"table": normal(keys[-1], (cfg.vocab, D))},
        "norm_f": layers.rmsnorm_init(D, dt),
        "head": {"kernel": normal(keys[-2], (D, cfg.vocab))},
    }
    for i in range(cfg.num_layers):
        k = jax.random.split(keys[i], 5)
        layer = {"moe": moe(k[4])}
        for j in (0, 1):
            layer[f"attn_norm_{j}"] = layers.rmsnorm_init(D, dt)
            layer[f"attn_{j}"] = attention(k[j])
            layer[f"ffn_norm_{j}"] = layers.rmsnorm_init(D, dt)
            layer[f"ffn_{j}"] = layers.gated_mlp_init(
                k[2 + j], D, cfg.ffn_hidden_size, out_std=res, dtype=dt)
        params[f"layer_{i}"] = layer
    return params


#: Of the counts, those the chunk keeps a second time as ``moe_chunk_<name>``:
#: a reader that sets the kernel's time in a trace against its least has to
#: know what the chunk's calls did apart from the step's.
CHUNK_COUNTS = ("choices_held", "experts_touched", "calls")


def counters_init() -> dict:
    names = [f"moe_{n}" for n in moe_ops.SHARE_COUNTS]
    names += [f"moe_chunk_{n}" for n in CHUNK_COUNTS]
    return {name: jnp.zeros((), jnp.int32) for name in names}


def init_cache(cfg: Config, slots: int, max_len: int):
    """What ``slots`` sessions own - ``[slots, max_len, 576]`` a sub-layer -
    and the counters (module docstring)."""
    one = lambda: jnp.zeros((slots, max_len, cfg.latent), cfg.dtype)
    cache = {
        f"layer_{i}": {"attn_0": one(), "attn_1": one()}
        for i in range(cfg.num_layers)
    }
    cache["counters"] = counters_init()
    return cache


# ----------------------------------------------------------------------------
# The pieces the three paths share
# ----------------------------------------------------------------------------


def _mm(cfg: Config, p, x):
    """``x @ kernel``: operands in ``param_dtype``, float32 out."""
    return layers.dense(p, x.astype(cfg.dtype))


def _ffn(cfg: Config, p, u):
    return layers.gated_mlp(p, u, dtype=cfg.dtype)


def _norm(cfg: Config, p, x):
    return layers.rmsnorm(p, x, cfg.rms_norm_eps)


def _query_and_latent(cfg: Config, p, h, pos):
    """From the normed ``h [.., D]`` at positions ``pos [..]``: the query
    ``q_nope [.., H, nope]``, ``q_rope [.., H, rope]`` (rotated) and the
    position's cache row ``[.., 576]`` = ``c | rotated kr``, all in
    ``param_dtype``."""
    H, nope, rope = cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    D, R = cfg.hidden_size, cfg.kv_lora_rank
    cq = _norm(cfg, p["q_norm"], _mm(cfg, p["q_a"], h))
    if cfg.mla_scale_q_lora:
        cq = cq * math.sqrt(D / cfg.q_lora_rank)
    q = _mm(cfg, p["q_b"], cq).reshape(h.shape[:-1] + (H, nope + rope))
    ckv = _mm(cfg, p["kv_a"], h)
    c = _norm(cfg, p["kv_norm"], ckv[..., :R])
    if cfg.mla_scale_kv_lora:
        c = c * math.sqrt(D / R)
    cos, sin = layers.rope_angles(pos, rope, cfg.rope_theta)
    q_rope = layers.rope_interleaved(q[..., nope:], cos[..., None, :], sin[..., None, :])
    kr = layers.rope_interleaved(ckv[..., R:], cos, sin)
    row = jnp.concatenate([c, kr], axis=-1).astype(cfg.dtype)
    return q[..., :nope].astype(cfg.dtype), q_rope.astype(cfg.dtype), row


def _kv_b(cfg: Config, p):
    """``kv_b`` by head: ``(W_uk [R, H, nope], W_uv [R, H, vd])``."""
    w = p["kv_b"]["kernel"].reshape(
        cfg.kv_lora_rank, cfg.num_attention_heads, cfg.qk_nope_head_dim + cfg.v_head_dim)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


def _scale(cfg: Config) -> float:
    return 1.0 / math.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)


def _attend_expanded(cfg: Config, p, q_nope, q_rope, rows, q_pos, n_blocks, block):
    """Queries ``[C, H, .]`` at positions ``q_pos [C]`` of ONE sequence
    against its cached rows ``rows [T, 576]``: the first ``n_blocks`` blocks
    of ``block`` positions (``n_blocks`` may be traced), each expanded into
    keys and values and folded into a running softmax; a query sees the
    positions ``<=`` its own.  Returns ``[C, H x vd]`` float32.  Block ``i``
    holds positions ``[i block, (i + 1) block)``; where ``T`` is no multiple
    of the block the last one is read shifted back inside the cache and what
    it shares with the block before is masked."""
    T, R = rows.shape[0], cfg.kv_lora_rank
    C, H = q_nope.shape[:2]
    w_uk, w_uv = _kv_b(cfg, p)
    f32 = jnp.float32

    def body(i, carry):
        m, l, acc = carry
        start = jnp.minimum(i * block, T - block)
        blk = jax.lax.dynamic_slice_in_dim(rows, start, block, axis=0)
        c, kr = blk[:, :R], blk[:, R:]
        k = jnp.einsum("tr,rhd->thd", c, w_uk,
                       preferred_element_type=f32).astype(cfg.dtype)
        v = jnp.einsum("tr,rhd->thd", c, w_uv,
                       preferred_element_type=f32).astype(cfg.dtype)
        s = jnp.einsum("qhd,thd->hqt", q_nope, k, preferred_element_type=f32)
        s = s + jnp.einsum("qhd,td->hqt", q_rope, kr, preferred_element_type=f32)
        s = s * _scale(cfg)
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
            "hqt,thd->hqd", w.astype(cfg.dtype), v, preferred_element_type=f32)
        return m_new, l, acc

    stat = jnp.zeros((H, C, 1), f32)
    _, l, acc = jax.lax.fori_loop(
        0, n_blocks, body,
        (stat - jnp.inf, stat, jnp.zeros((H, C, cfg.v_head_dim), f32)),
    )
    return jnp.moveaxis(acc / l, 0, 1).reshape(C, -1)


def _expert_layer(cfg: Config, p, u, live, counters, chunk: bool = False):
    """``MoE(u)`` for ``u [T, D]`` and the counters with this call's counts
    added (``chunk``: to the chunk's own entries too)."""
    m, counts = moe_ops.apply_share(p, u, cfg.share, live, dtype=cfg.dtype)
    added = {f"moe_{k}": v for k, v in counts.items()}
    if chunk:
        added.update({f"moe_chunk_{k}": counts[k] for k in CHUNK_COUNTS})
    return m, {k: v + added.get(k, 0) for k, v in counters.items()}


def _double_layer(cfg: Config, p, x, attn, moe):
    """One double layer on ``x [.., D]`` float32.  ``attn(j, p_attn, h)`` is
    sub-layer ``j``'s attention of the normed ``h``; ``moe(u)`` the expert
    layer, or None where its result feeds nothing that is kept."""
    x = x + attn(0, p["attn_0"], _norm(cfg, p["attn_norm_0"], x))
    u = _norm(cfg, p["ffn_norm_0"], x)
    m = moe(u) if moe is not None else 0.0
    x = x + _ffn(cfg, p["ffn_0"], u)
    x = x + attn(1, p["attn_1"], _norm(cfg, p["attn_norm_1"], x))
    return x + _ffn(cfg, p["ffn_1"], _norm(cfg, p["ffn_norm_1"], x)) + m


def _embed(cfg: Config, params, tokens):
    return layers.embedding_lookup(params["emb"], tokens).astype(jnp.float32)


def _logits(cfg: Config, params, h):
    return _mm(cfg, params["head"], _norm(cfg, params["norm_f"], h))


# ----------------------------------------------------------------------------
# Full forward
# ----------------------------------------------------------------------------


def apply(cfg: Config, params, tokens):
    """tokens ``[B, L]`` int32 -> logits ``[B, L, vocab]`` float32, causal;
    attention in the expanded form, a sequence at a time."""
    B, L = tokens.shape
    pos = jnp.arange(L)
    block = min(PREFILL_BLOCK, L)
    n_blocks = -(-L // block)
    h = _embed(cfg, params, tokens)
    for i in range(cfg.num_layers):
        p = params[f"layer_{i}"]

        def attn(j, pa, y):
            q_nope, q_rope, rows = _query_and_latent(cfg, pa, y, pos[None])
            with jax.named_scope("mla/prefill"):
                o = jax.vmap(lambda qn, qr, r: _attend_expanded(
                    cfg, pa, qn, qr, r, pos, n_blocks, block))(q_nope, q_rope, rows)
            return _mm(cfg, pa["o"], o)

        def moe(u):
            m, _ = moe_ops.apply_share(
                p["moe"], u.reshape(B * L, -1), cfg.share, dtype=cfg.dtype)
            return m.reshape(u.shape)

        h = _double_layer(cfg, p, h, attn, moe)
    return _logits(cfg, params, h)


# ----------------------------------------------------------------------------
# Serving: the one-token step and the prefill chunk
# ----------------------------------------------------------------------------


def decode_rows_read(max_pos, max_len: int):
    """Cache positions of EVERY slot that one decode step reads when its
    deepest row stands at ``max_pos``: whole blocks of :data:`DECODE_BLOCK`
    up to the one that holds that position, at most the cache.  The host's
    count of what :func:`_attend_absorbed`'s loop does on the device."""
    blk = min(DECODE_BLOCK, max_len)
    return min(max_len, (max_pos // blk + 1) * blk)


def _write_rows(cache, new, pos):
    """cache ``[S, T, 576]`` with ``new[b]`` written at position ``pos[b]``
    of slot ``b`` and nothing else changed: one ``dynamic_update_slice`` a
    slot, each in place in a donated cache (models/transformer.py
    ``_write_rows`` has the chip reading that chose this over a scatter)."""
    for b in range(cache.shape[0]):
        cache = jax.lax.dynamic_update_slice(cache, new[b:b + 1, None], (b, pos[b], 0))
    return cache


def _attend_absorbed(cfg: Config, p, q_nope, q_rope, cache, pos):
    """One query a slot against that slot's latent rows: ``q_nope [S, H,
    nope]``, ``q_rope [S, H, rope]``, ``cache [S, T, 576]``, ``pos [S]`` ->
    ``[S, H x vd]`` float32; slot ``b`` attends over its positions ``<=
    pos[b]``.  ``kv_b`` never touches the cache: its key half goes into the
    query (``q_nope . W_uk``, then ONE product of ``[q_lat | q_rope]``
    against the 576-wide row), its value half comes after the weighted sum
    of the rows' first ``kv_lora_rank`` columns.  The loop is
    models/transformer.py ``_decode_attention``'s: a block of positions a
    trip, ``max(pos) // block + 1`` trips, a block wholly past a row's
    position an exact no-op for that row."""
    S, T, _ = cache.shape
    R, H = cfg.kv_lora_rank, cfg.num_attention_heads
    blk = min(DECODE_BLOCK, T)
    f32 = jnp.float32
    w_uk, w_uv = _kv_b(cfg, p)
    q_lat = jnp.einsum("shd,rhd->shr", q_nope, w_uk, preferred_element_type=f32)
    q = jnp.concatenate([q_lat.astype(cfg.dtype), q_rope], axis=-1)  # [S, H, 576]

    def body(i, carry):
        m, l, acc = carry
        start = jnp.minimum(i * blk, T - blk)
        rows = jax.lax.dynamic_slice_in_dim(cache, start, blk, axis=1)
        s = jnp.einsum("shc,stc->sht", q, rows, preferred_element_type=f32) * _scale(cfg)
        t = start + jnp.arange(blk)
        own = (t >= i * blk)[None, :] & (t[None, :] <= pos[:, None])
        s = jnp.where(own[:, None, :], s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        w = jnp.exp(s - m_new)
        r = jnp.exp(m - m_new)
        l = l * r + w.sum(axis=-1, keepdims=True)
        acc = acc * r + jnp.einsum(
            "sht,str->shr", w.astype(cfg.dtype), rows[..., :R],
            preferred_element_type=f32)
        return m_new, l, acc

    stat = jnp.zeros((S, H, 1), f32)
    _, l, acc = jax.lax.fori_loop(
        0, jnp.max(pos) // blk + 1, body,
        (stat - jnp.inf, stat, jnp.zeros((S, H, R), f32)),
    )
    o = jnp.einsum("shr,rhd->shd", (acc / l).astype(cfg.dtype), w_uv,
                   preferred_element_type=f32)
    return o.reshape(S, -1)


def decode_step_batch(cfg: Config, params, cache, token, pos, live):
    """token ``[S]`` int32, pos ``[S]`` int32 (per-row positions), live
    ``[S]`` bool -> (logits ``[S, vocab]``, new cache): every row advances
    its own session one position - writes its latent row at ``pos`` in place
    and attends over its slot's rows ``<= pos``.  A row that is not live is
    inert the key/value way (what it writes is written again by the
    session's first real step, its logits mean nothing); ``live`` keeps it
    out of the expert layer and out of the counters."""
    counters = cache["counters"]
    new_cache = {}
    h = _embed(cfg, params, token)
    for i in range(cfg.num_layers):
        p, c = params[f"layer_{i}"], cache[f"layer_{i}"]
        written = {}

        def attn(j, pa, y):
            q_nope, q_rope, row = _query_and_latent(cfg, pa, y, pos)
            with jax.named_scope("mla/decode"):
                rows = written[f"attn_{j}"] = _write_rows(c[f"attn_{j}"], row, pos)
                o = _attend_absorbed(cfg, pa, q_nope, q_rope, rows, pos)
            return _mm(cfg, pa["o"], o)

        def moe(u):
            nonlocal counters
            m, counters = _expert_layer(cfg, p["moe"], u, live, counters)
            return m

        h = _double_layer(cfg, p, h, attn, moe)
        new_cache[f"layer_{i}"] = written
    new_cache["counters"] = counters
    return _logits(cfg, params, h), new_cache


def _chunk_write(cache, new, slot, offset, n_valid):
    """Write ``new [C, 576]`` rows ``[0, n_valid)`` into ``cache [S, T,
    576]`` at ``[slot, offset:offset + n_valid]`` and touch nothing else;
    returns the cache and the slot's rows ``[T, 576]``.  The window starts
    at ``min(offset, T - C)`` (``dynamic_update_slice`` clamps a start that
    overruns and would overwrite earlier rows), the chunk rolled inside it;
    as models/transformer.py ``_block_prefill``."""
    C, W = new.shape
    T = cache.shape[1]
    start = jnp.clip(offset, 0, T - C)
    shift = offset - start
    i = jnp.arange(C) - shift
    own = ((i >= 0) & (i < n_valid))[None, :, None]
    at = (slot, start, 0)
    old = jax.lax.dynamic_slice(cache, at, (1, C, W))
    win = jnp.where(own, jnp.roll(new[None], shift, axis=1), old)
    cache = jax.lax.dynamic_update_slice(cache, win, at)
    return cache, jax.lax.dynamic_slice_in_dim(cache, slot, 1, axis=0)[0]


def prefill_chunk(cfg: Config, params, cache, tokens, slot, offset, n_valid):
    """tokens ``[C]`` int32 - ONE slot's prompt tokens at positions ``offset
    .. offset + C - 1``, the first ``n_valid`` real, the rest padding -> new
    cache: one forward pass writes the valid tokens' latent rows into the
    slot's rows and touches no other slot; its attention expands the slot's
    rows a block at a time and reads no further than ``offset + C``.  No
    final norm, head or logits: the caller decodes the prompt's LAST token
    the ordinary way.  The last layer's second feed-forward and its expert
    layer feed nothing that is returned: the first the compiler drops, the
    second is not called, so what the counters say ran did run.  ``C`` is
    static; ``slot``, ``offset`` and ``n_valid`` are traced scalars, so one
    program serves every chunk."""
    C = tokens.shape[0]
    T = cache["layer_0"]["attn_0"].shape[1]
    block = min(PREFILL_BLOCK, T)
    q_pos = offset + jnp.arange(C)
    n_blocks = jnp.minimum(offset + C - 1, T - 1) // block + 1
    valid = jnp.arange(C) < n_valid
    counters = cache["counters"]
    new_cache = {}
    h = _embed(cfg, params, tokens)
    for i in range(cfg.num_layers):
        p, c = params[f"layer_{i}"], cache[f"layer_{i}"]
        written = {}

        def attn(j, pa, y):
            q_nope, q_rope, new = _query_and_latent(cfg, pa, y, q_pos)
            with jax.named_scope("mla/prefill"):
                written[f"attn_{j}"], rows = _chunk_write(
                    c[f"attn_{j}"], new, slot, offset, n_valid)
                o = _attend_expanded(
                    cfg, pa, q_nope, q_rope, rows, q_pos, n_blocks, block)
            return _mm(cfg, pa["o"], o)

        def moe(u):
            nonlocal counters
            m, counters = _expert_layer(cfg, p["moe"], u, valid, counters, chunk=True)
            return m

        h = _double_layer(cfg, p, h, attn, moe if i + 1 < cfg.num_layers else None)
        new_cache[f"layer_{i}"] = written
    new_cache["counters"] = counters
    return new_cache


def serve_decode_fns(cfg: Config):
    """``(init_cache_fn, step_fn, prefill_fn)`` for ``serve.
    ModelReplicaServer(decode_fns=...)``.  ``step_fn`` takes ``live`` (it
    counts live rows) and says how far a step reads (``cache_rows_read``)."""

    def init_cache_fn(slots: int, max_len: int):
        return init_cache(cfg, slots, max_len)

    def step_fn(params, cache, tokens, pos, live):
        return decode_step_batch(cfg, params, cache, tokens, pos, live)

    step_fn.cache_rows_read = decode_rows_read

    def prefill_fn(params, cache, tokens, slot, offset, n_valid):
        return prefill_chunk(cfg, params, cache, tokens, slot, offset, n_valid)

    return init_cache_fn, step_fn, prefill_fn


# ----------------------------------------------------------------------------
# Generation
# ----------------------------------------------------------------------------


def generate(cfg: Config, params, prompt, *, max_new_tokens: int,
             temperature: float = 0.0, rng: jax.Array | None = None):
    """prompt ``[B, Tp]`` -> ``[B, Tp + max_new_tokens]``: each row's prompt
    but its last token goes through :func:`prefill_chunk` (one chunk a row),
    then a ``lax.scan`` of :func:`decode_step_batch` decodes greedily
    (temperature 0) or by temperature sampling - the path a replica takes."""
    prompt = jnp.asarray(prompt, jnp.int32)
    B, Tp = prompt.shape
    rng = jax.random.key(0) if rng is None else rng
    run = _generate_loop(cfg, Tp, Tp + max_new_tokens, float(temperature))
    cache = init_cache(cfg, B, Tp + max_new_tokens)
    return jnp.concatenate([prompt, run(params, cache, prompt, rng).T], axis=1)


@functools.lru_cache(maxsize=32)
def _generate_loop(cfg: Config, Tp: int, total: int, temperature: float):
    def step(params, carry, pos):
        cache, tok, rng = carry
        B = tok.shape[0]
        logits, cache = decode_step_batch(
            cfg, params, cache, tok, jnp.full((B,), pos), jnp.ones((B,), bool))
        rng, sub = jax.random.split(rng)
        if temperature > 0:
            nxt = jax.random.categorical(sub, logits / temperature)
        else:
            nxt = jnp.argmax(logits, axis=-1)
        nxt = nxt.astype(jnp.int32)
        return (cache, nxt, rng), nxt

    def run(params, cache, prompt, rng):
        if Tp > 1:
            cache = jax.lax.fori_loop(
                0, prompt.shape[0],
                lambda b, c: prefill_chunk(
                    cfg, params, c, prompt[b, :Tp - 1], b, 0, Tp - 1),
                cache,
            )
        _, toks = jax.lax.scan(
            lambda c, p: step(params, c, p),
            (cache, prompt[:, Tp - 1], rng), jnp.arange(Tp - 1, total - 1),
        )
        return toks

    return jax.jit(run)
