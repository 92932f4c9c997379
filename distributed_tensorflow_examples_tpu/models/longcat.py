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

MLA is models/mla.py's sub-layer (its equations and its two forms - the
one-token step ABSORBS ``kv_b`` and reads the latent cache, the chunk and the
full forward EXPAND a block of latents at a time - are there), here with 64
heads, ``cq`` scaled by ``sqrt(D / q_lora_rank)`` and the latent by ``sqrt(D
/ kv_lora_rank)`` (``mla_scale_q_lora`` / ``mla_scale_kv_lora``), unscaled
rotary frequencies and the softmax's scale ``1 / sqrt(nope + rope)``.  What
a position leaves behind is 576 values where expanded keys and values are
20480; the cache is ``[slots, max_len, 576]`` a sub-layer in
``param_dtype``.  The chunk EXPANDS because by the counts of a 512-token
chunk that is the cheaper form at every depth - absorbing trades the
expansion (``T x 16.8`` MFLOP) for products three and four times as wide
(``T x 67`` MFLOP more) - and so the chip read it while both were loops:
at offsets 0 / 3584 / 7168 a chunk took 30.4 / 45.8 / 60.1 ms expanded and
33.0 / 49.9 / 66.7 absorbed (my chip run, PR 31).  On a TPU the expanded
form is one kernel a sub-layer since PR 38 (:data:`PREFILL_BLOCK`).

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
(serve/model_server.py ``_DecodeEngine``): its attention reads the cache
of the live rows only, each to its own position (models/mla.py), and it
COUNTS: the cache
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
from . import decoding, layers, mla

#: Cache positions the step's attention reads at a time
#: (``mla.Spec.decode_block``): an item of the kernel's grid
#: (ops/latent_decode.py) brings in one block of ONE live slot, 1.1 KB a
#: position, so a larger block trades positions read past a slot's own row
#: (half a block of every live slot) against grid steps.  Chosen on one v5e
#: chip at the served widths, 32 slots x 8192 (my chip run, PR 35; PERF.md
#: section 6): with 20 rows live at 1500 / 4000 / 7900 the step takes 11.0 /
#: 11.5 / 12.6 ms at 1024 and 10.5 / 11.3 / 12.8 at 512 (the loop this
#: kernel replaced: 12.7 / 14.3 / 17.7 at 1024, same run), with 10 rows
#: live at the cell's depths (mean 4,800) 10.35 at 1024 and 10.51 at 512
#: (the loop: 15.98): level, and 1024 is kept.
DECODE_BLOCK = 1024
#: Cached positions a prefill chunk expands and attends over at a time
#: (``mla.Spec.prefill_block``): an item of the kernel's grid
#: (ops/latent_prefill.py) takes one block for a group of heads, the CPU's
#: loop one a trip.  Chosen on one v5e chip at the served widths, 64 heads,
#: 32 slots x 8192 (my chip runs, PR 38; PERF.md section 6): one sub-layer's
#: attention of a 512-token chunk at offsets 1536 / 3584 / 7168 takes 0.67 /
#: 1.18 / 2.21 ms at 1024, 0.84 / 1.52 / 2.71 at 512 and 1.25 / 2.30 / 4.13
#: at 256 (the loop this kernel replaced, at its best block of 512: 1.57 /
#: 2.91 / 5.24, same runs) - what a head pays a block whatever its width
#: (the reductions of the running maximum and sum, the accumulator's
#: rescale: about 1.1 us) is paid half as often - and a whole chunk at 0 /
#: 1536 / 3584 / 7168 takes 29.7 / 31.4 / 35.5 / 42.6 ms (the loop: 30.8 /
#: 36.9 / 45.5 / 59.6).  Heads a group: 4, 8 and 16 read level (2.21 / 2.21 /
#: 2.19 at 7168); ops/latent_prefill.py takes the most its VMEM plan holds.
PREFILL_BLOCK = 1024


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

    def mla(self) -> mla.Spec:
        """The latent attention's spec (it holds an array: call it inside
        the traced program)."""
        D = self.hidden_size
        return mla.Spec(
            heads=self.num_attention_heads, q_lora_rank=self.q_lora_rank,
            kv_lora_rank=self.kv_lora_rank, nope=self.qk_nope_head_dim,
            rope=self.qk_rope_head_dim, v_dim=self.v_head_dim,
            q_scale=math.sqrt(D / self.q_lora_rank) if self.mla_scale_q_lora else 1.0,
            kv_scale=math.sqrt(D / self.kv_lora_rank) if self.mla_scale_kv_lora else 1.0,
            softmax_scale=1.0 / math.sqrt(self.qk_nope_head_dim + self.qk_rope_head_dim),
            inv_freq=layers.rope_frequencies(self.qk_rope_head_dim, self.rope_theta),
            eps=self.rms_norm_eps, dtype=self.dtype,
            decode_block=DECODE_BLOCK, prefill_block=PREFILL_BLOCK,
        )

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
    D, F = cfg.hidden_size, cfg.expert_ffn_hidden_size
    spec = cfg.mla()
    n_all = cfg.n_routed_experts + cfg.zero_expert_num
    res = 0.02 / math.sqrt(4 * cfg.num_layers)

    def normal(k, shape, std=0.02):
        return (std * jax.random.normal(k, shape)).astype(dt)

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
            layer[f"attn_{j}"] = mla.init(spec, D, k[j], std=0.02, out_std=res)
            layer[f"ffn_norm_{j}"] = layers.rmsnorm_init(D, dt)
            layer[f"ffn_{j}"] = layers.gated_mlp_init(
                k[2 + j], D, cfg.ffn_hidden_size, out_std=res, dtype=dt)
        params[f"layer_{i}"] = layer
    return params


#: What this model keeps of ops/moe.py ``SHARE_COUNTS``, as ``moe_<name>``.
COUNTS = ("choices", "choices_held", "choices_zero", "experts_touched", "calls")
#: Of the counts, those the chunk keeps a second time as ``moe_chunk_<name>``:
#: a reader that sets the kernel's time in a trace against its least has to
#: know what the chunk's calls did apart from the step's.
CHUNK_COUNTS = ("choices_held", "experts_touched", "calls")


def init_cache(cfg: Config, slots: int, max_len: int):
    """What ``slots`` sessions own - ``[slots, max_len, 576]`` a sub-layer -
    and the counters (module docstring)."""
    one = lambda: jnp.zeros((slots, max_len, cfg.latent), cfg.dtype)
    cache = {
        f"layer_{i}": {"attn_0": one(), "attn_1": one()}
        for i in range(cfg.num_layers)
    }
    cache["counters"] = moe_ops.share_counters(COUNTS, CHUNK_COUNTS)
    return cache


# ----------------------------------------------------------------------------
# The pieces the three paths share (the latent attention's: models/mla.py)
# ----------------------------------------------------------------------------


def _mm(cfg: Config, p, x):
    """``x @ kernel``: operands in ``param_dtype``, float32 out."""
    return layers.dense(p, x.astype(cfg.dtype))


def _ffn(cfg: Config, p, u):
    return layers.gated_mlp(p, u, dtype=cfg.dtype)


def _norm(cfg: Config, p, x):
    return layers.rmsnorm(p, x, cfg.rms_norm_eps)


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
    spec = cfg.mla()
    h = _embed(cfg, params, tokens)
    for i in range(cfg.num_layers):
        p = params[f"layer_{i}"]

        def attn(j, pa, y):
            return mla.forward(spec, pa, y)

        def moe(u):
            m, _ = moe_ops.apply_share(
                p["moe"], u.reshape(B * L, -1), cfg.share, dtype=cfg.dtype)
            return m.reshape(u.shape)

        h = _double_layer(cfg, p, h, attn, moe)
    return _logits(cfg, params, h)


# ----------------------------------------------------------------------------
# Serving: the one-token step and the prefill chunk
# ----------------------------------------------------------------------------


def decode_step_batch(cfg: Config, params, cache, token, pos, live):
    """token ``[S]`` int32, pos ``[S]`` int32 (per-row positions), live
    ``[S]`` bool -> (logits ``[S, vocab]``, new cache): every row advances
    its own session one position - writes its latent row at ``pos`` in place
    and attends over its slot's rows ``<= pos``.  A row that is not live is
    inert the key/value way (what it writes is written again by the
    session's first real step, its logits mean nothing); ``live`` keeps it
    out of the attention's read of the cache (it attends over nothing), out
    of the expert layer and out of the counters."""
    spec = cfg.mla()
    counters = cache["counters"]
    new_cache = {}
    h = _embed(cfg, params, token)
    for i in range(cfg.num_layers):
        p, c = params[f"layer_{i}"], cache[f"layer_{i}"]
        written = {}

        def attn(j, pa, y):
            o, written[f"attn_{j}"] = mla.decode(spec, pa, y, c[f"attn_{j}"], pos, live)
            return o

        def moe(u):
            nonlocal counters
            m, counters = moe_ops.apply_share_counted(
                p["moe"], u, cfg.share, live, counters, dtype=cfg.dtype)
            return m

        h = _double_layer(cfg, p, h, attn, moe)
        new_cache[f"layer_{i}"] = written
    new_cache["counters"] = counters
    return _logits(cfg, params, h), new_cache


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
    spec = cfg.mla()
    valid = jnp.arange(tokens.shape[0]) < n_valid
    counters = cache["counters"]
    new_cache = {}
    h = _embed(cfg, params, tokens)
    for i in range(cfg.num_layers):
        p, c = params[f"layer_{i}"], cache[f"layer_{i}"]
        written = {}

        def attn(j, pa, y):
            o, written[f"attn_{j}"] = mla.prefill(
                spec, pa, y, c[f"attn_{j}"], slot, offset, n_valid)
            return o

        def moe(u):
            nonlocal counters
            m, counters = moe_ops.apply_share_counted(
                p["moe"], u, cfg.share, valid, counters,
                chunk_counts=CHUNK_COUNTS, dtype=cfg.dtype)
            return m

        h = _double_layer(cfg, p, h, attn, moe if i + 1 < cfg.num_layers else None)
        new_cache[f"layer_{i}"] = written
    new_cache["counters"] = counters
    return new_cache


def serve_decode_fns(cfg: Config):
    """What ``serve.ModelReplicaServer(decode_fns=...)`` is told of this
    model (``decoding.DecodeFns``): its step takes ``live`` (it reads and
    counts live rows only), and a step and a chunk read the cache as far as
    ``mla.decode_rows_read`` / ``mla.prefill_rows_read`` say at this model's
    blocks."""
    return decoding.serve_fns(
        cfg, init_cache, decode_step_batch, prefill_chunk, wants_live=True,
        step_rows_read=functools.partial(mla.decode_rows_read, DECODE_BLOCK),
        chunk_rows_read=functools.partial(mla.prefill_rows_read, PREFILL_BLOCK))


# ----------------------------------------------------------------------------
# Generation
# ----------------------------------------------------------------------------


def generate(cfg: Config, params, prompt, *, max_new_tokens: int,
             temperature: float = 0.0, rng: jax.Array | None = None):
    """prompt ``[B, Tp]`` -> ``[B, Tp + max_new_tokens]`` by
    :func:`prefill_chunk` and :func:`decode_step_batch`, the path a replica
    takes (models/decoding.py)."""
    return decoding.generate(
        cfg, params, prompt, init_cache=init_cache, prefill_chunk=prefill_chunk,
        decode_step_batch=decode_step_batch, max_new_tokens=max_new_tokens,
        temperature=temperature, rng=rng)
