"""SmallThinker: a router that reads its layer's INPUT, before the first
norm and before attention; whole expert layers of ReLU-gated experts with
nothing beside them; one global layer WITHOUT positional encoding to three
rotary sliding-window layers; grouped-query attention with neither QK-norm
nor gate (PowerInfer's SmallThinker family, ``model_name``
``smallthinker_21b_instruct``; the published ``config.json`` keys are this
module's ``Config``).

With ``N`` an RMSNorm (two a layer, before each sub-layer and none after),
``h0 = table[token]`` (not scaled) and layer ``i`` global where
``sliding_window_layout[i]`` is 0, else a window layer:

    r   = x Wr                              router logits, float32, from the layer's INPUT
    x1  = x  + Attn_i(N_in(x))
    y   = x1 + sum_{e in choice} w_e E_e(N_post(x1))
    logits = head(N_final(y_last))          untied head

    choice = the moe_num_active_primary_experts largest of r
    w      = softmax of r over the chosen  (= softmax over all, the chosen kept
             and divided by their sum: moe_primary_router_apply_softmax,
             norm_topk_prob)
    E_e(u) = down_e(relu(gate_e u) * (up_e u))

    Attn(u):  q = u Wq as [heads, head_dim];  k, v = u Wk, u Wv as [kv, head_dim]
              where rope_layout[i] is 1: q, k rotated by rotary positions
              (theta, the whole head, no scaling); where 0: NO positional encoding
              o_t = softmax_j(q_t . k_j / sqrt(head_dim)) v_j   over j <= t, in a
                    window layer also j > t - sliding_window_size; query head g
                    reads K/V head g // (heads / kv)
              out = o Wo

The routed part is ops/moe.py's two halves with every expert of the model
held (``first`` 0, ``held`` ``moe_num_primary_experts``: the share is the
whole layer): ``share_plan`` on ``x`` FIRST - scores, choice, weights and the
rows' places in the grouped product, under ``smallthinker/route_ahead`` -
then attention, then ``apply_share_plan`` on ``N_post(x1)``.  Nothing
between attention and the experts' product waits for the router; a
deployment would send its exchange's addresses while attention runs.  The
rotary pairs are the INTERLEAVED ones (``layers.rope_interleaved``), where
the source rotates halves: a checkpoint's ``Wq``, ``Wk`` columns would be
re-ordered within each head on loading.

THE SHARE is of depth only: ``held_layers`` names the PUBLISHED layers that
live here (empty: all), in order - a stage of a pipeline whose every layer is
whole on its chip.  Parameters and cache entries are keyed by the published
index (``layer_4``), and a layer's kind is that of its published index.

THE CACHE is models/ring_cache.py's: a global layer ``max_len`` rows a slot,
a window layer a RING of ``sliding_window_size + ring_slack`` rows (its keys
kept rotated), a SPARE slot for the step's rows that are not live; the
step's and the chunk's blocked attention are that module's.

What the model counts on the device (the cache tree's ``counters``): the
``moe_*`` and ``moe_chunk_*`` sums and the five ``attn_*`` rows of
models/afmoe.py under the same names, and two more ``[slots]`` int32 arrays,
one increment a live row a STEP (not a layer): ``attn_live_steps``, and
``attn_past_window_steps`` where the row's position + 1 exceeds
``sliding_window_size`` - the steps in which the window binds.

Precision: parameters in ``param_dtype`` (bfloat16); products in it with
float32 accumulation; residual stream, norms, rotary, router and softmax in
float32.

Serving only: no loss, no mesh.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from ..ops import moe as moe_ops
from . import decoding, layers, ring_cache

#: The source's pattern: a global layer, then three window layers.
_PERIOD = (0, 1, 1, 1)


@dataclasses.dataclass(frozen=True)
class Config:
    """The published keys (SmallThinker-21BA3B-Instruct's values as
    defaults) and the share.  Fixed by the family and not keys here: no bias
    in any projection, ReLU on the gate's half, the softmax over the chosen
    (``moe_primary_router_apply_softmax``, ``norm_topk_prob``), no rotary
    scaling, untied head."""

    vocab_size: int = 151936
    hidden_size: int = 2560
    moe_ffn_hidden_size: int = 768
    num_hidden_layers: int = 52
    num_attention_heads: int = 28
    num_key_value_heads: int = 4
    head_dim: int = 128
    #: 1: the layer attends through ``sliding_window_size``; 0: over everything.
    sliding_window_layout: tuple[int, ...] = _PERIOD * 13
    #: 1: the layer's queries and keys are rotated; 0: no positional encoding.
    rope_layout: tuple[int, ...] = _PERIOD * 13
    sliding_window_size: int = 4096
    moe_num_primary_experts: int = 64
    moe_num_active_primary_experts: int = 6
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1.5e6
    #: The share (module docstring): published layer indices; () = all.
    held_layers: tuple[int, ...] = ()
    #: Rows a window layer's ring has beyond the window: the widest chunk
    #: that may be written into it (the serve engine's ``PREFILL_CHUNK``).
    ring_slack: int = 512
    #: Cache rows the step's and the chunk's attention read at a time.
    attn_block: int = 512
    param_dtype: str = "bfloat16"

    def __post_init__(self):
        for name in ("sliding_window_layout", "rope_layout"):
            layout = getattr(self, name)
            if len(layout) != self.num_hidden_layers or set(layout) - {0, 1}:
                raise ValueError(
                    f"{name} is not {self.num_hidden_layers} entries of 0 or 1")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must be a multiple of K/V heads")
        held = self.held_layers
        if any(not 0 <= i < self.num_hidden_layers for i in held) or \
                list(held) != sorted(set(held)):
            raise ValueError(f"held_layers {held} are not published layers in order")

    @property
    def dtype(self):
        return jnp.dtype(self.param_dtype)

    @property
    def layers(self) -> tuple[int, ...]:
        """The published indices of the layers that are here, in order."""
        return self.held_layers or tuple(range(self.num_hidden_layers))

    def window(self, i: int) -> int | None:
        """How far back layer ``i`` sees; None: everything."""
        return self.sliding_window_size if self.sliding_window_layout[i] else None

    def cache_rows(self, i: int, max_len: int) -> int:
        """Rows a slot has in layer ``i``'s cache (models/ring_cache.py)."""
        if not self.sliding_window_layout[i]:
            return max_len
        return min(max_len, self.sliding_window_size + self.ring_slack)

    @property
    def share(self) -> moe_ops.ShareConfig:
        return moe_ops.ShareConfig(
            n_experts=self.moe_num_primary_experts, n_zero=0,
            top_k=self.moe_num_active_primary_experts, scale=1.0, first=0,
            held=self.moe_num_primary_experts, scoring="softmax", normalise=True,
            activation="relu",
        )


# ----------------------------------------------------------------------------
# Parameters and cache
# ----------------------------------------------------------------------------


#: What this model keeps of ops/moe.py ``SHARE_COUNTS``, as ``moe_<name>``,
#: and of them what the chunk keeps a second time as ``moe_chunk_<name>``
#: (models/deepseek.py has the reasons).
COUNTS = ("choices", "choices_held", "experts_touched", "calls", "tokens_reaching")
CHUNK_COUNTS = ("choices_held", "experts_touched", "calls")
#: The step's attention by kind of layer and the two counts a step (module
#: docstring), ``[slots]`` each.
ATTN_COUNTS = ring_cache.ATTN_COUNTS + ("attn_live_steps", "attn_past_window_steps")


def init_cache(cfg: Config, slots: int, max_len: int):
    """What ``slots`` sessions own, per layer by kind, a SPARE slot beside
    them, and the counters (module docstring)."""
    KV, hd = cfg.num_key_value_heads, cfg.head_dim
    rows = lambda i: jnp.zeros(
        (slots + 1, KV, cfg.cache_rows(i, max_len), hd), cfg.dtype)
    cache = {f"layer_{i}": {"k": rows(i), "v": rows(i)} for i in cfg.layers}
    cache["counters"] = {
        **moe_ops.share_counters(COUNTS, CHUNK_COUNTS),
        **{name: jnp.zeros((slots,), jnp.int32) for name in ATTN_COUNTS},
    }
    return cache


# ----------------------------------------------------------------------------
# The pieces the three paths share
# ----------------------------------------------------------------------------


def _norm(cfg: Config, p, x):
    return layers.rmsnorm(p, x, cfg.rms_norm_eps)


def _mm(cfg: Config, p, x):
    """``x @ kernel``: operands in ``param_dtype``, float32 out."""
    return layers.dense(p, x.astype(cfg.dtype))


def _qkv(cfg: Config, p, u, pos, rotary: bool):
    """``q [.., KV, G, hd]`` and ``kv [.., 2, KV, hd]`` (keys, values) in
    ``param_dtype`` - what the cache keeps - from the normed ``u [.., D]``
    at positions ``pos [..]``, rotated where the layer rotates."""
    KV, hd = cfg.num_key_value_heads, cfg.head_dim
    G = cfg.num_attention_heads // KV
    lead = u.shape[:-1]
    q = _mm(cfg, p["q"], u).reshape(lead + (KV, G, hd))
    k = _mm(cfg, p["k"], u).reshape(lead + (KV, hd))
    v = _mm(cfg, p["v"], u).reshape(lead + (KV, hd))
    if rotary:
        cos, sin = layers.rope_angles(pos, hd, cfg.rope_theta)  # [.., hd / 2]
        q = layers.rope_interleaved(q, cos[..., None, None, :], sin[..., None, None, :])
        k = layers.rope_interleaved(k, cos[..., None, :], sin[..., None, :])
    return q.astype(cfg.dtype), jnp.stack([k, v], axis=-3).astype(cfg.dtype)


def _out(cfg: Config, p, o):
    """``o Wo`` for the heads' results ``o [.., KV, G, hd]`` float32."""
    return _mm(cfg, p["o"], o.reshape(o.shape[:-3] + (-1,)))


def _scope(window) -> str:
    return "smallthinker/attn_global" if window is None else "smallthinker/attn_window"


def _layer(cfg: Config, p, x, live, attn, counters, *, experts=True,
           chunk_counts=()):
    """One layer on ``x [T, D]`` float32 -> ``(y, counters)``: the plan from
    ``x`` itself, ``attn(p_attn, u)`` on the normed ``u``, the experts under
    the plan on the normed ``x1``, their counts added to ``counters`` (which
    may be empty).  ``experts`` False: the layer's expert part feeds nothing
    that is kept, and neither it nor its plan is made."""
    if experts:
        with jax.named_scope("smallthinker/route_ahead"):
            plan = moe_ops.share_plan(p["moe"]["router"], x, cfg.share, live)
    x = x + attn(p["attn"], _norm(cfg, p["norm_in"], x))
    if not experts:
        return x, counters
    m, counters = moe_ops.apply_share_counted(
        p["moe"], _norm(cfg, p["norm_post"], x), cfg.share, live, counters,
        chunk_counts=chunk_counts, dtype=cfg.dtype, plan=plan)
    return x + m, counters


def _embed(cfg: Config, params, tokens):
    return layers.embedding_lookup(params["emb"], tokens).astype(jnp.float32)


def _logits(cfg: Config, params, h):
    return layers.dense(params["head"], _norm(cfg, params["norm_f"], h).astype(cfg.dtype))


# ----------------------------------------------------------------------------
# Full forward
# ----------------------------------------------------------------------------


def apply(cfg: Config, params, tokens):
    """tokens ``[B, L]`` int32 -> logits ``[B, L, vocab]`` float32; every
    layer's attention under its own mask (causal, and in a window layer the
    window), whole."""
    B, L = tokens.shape
    t = jnp.arange(L)
    behind = t[:, None] - t[None, :]  # [query, key]
    h = _embed(cfg, params, tokens).reshape(B * L, -1)
    for i in cfg.layers:
        window = cfg.window(i)
        seen = behind >= 0 if window is None else (behind >= 0) & (behind < window)

        def attn(pa, u):
            q, kv = _qkv(cfg, pa, u.reshape(B, L, -1), jnp.broadcast_to(t, (B, L)),
                         bool(cfg.rope_layout[i]))
            with jax.named_scope(_scope(window)):
                s = jnp.einsum("bqkgd,btkd->bkgqt", q, kv[:, :, 0],
                               preferred_element_type=jnp.float32)
                s = jnp.where(seen, s / math.sqrt(cfg.head_dim), -jnp.inf)
                w = jax.nn.softmax(s, axis=-1).astype(cfg.dtype)
                o = jnp.einsum("bkgqt,btkd->bqkgd", w, kv[:, :, 1],
                               preferred_element_type=jnp.float32)
            return _out(cfg, pa, o).reshape(B * L, -1)

        h, _ = _layer(cfg, params[f"layer_{i}"], h, None, attn, {})
    return _logits(cfg, params, h.reshape(B, L, -1))


# ----------------------------------------------------------------------------
# Serving: the one-token step and the prefill chunk
# ----------------------------------------------------------------------------


def decode_step_batch(cfg: Config, params, cache, token, pos, live):
    """token ``[S]`` int32, pos ``[S]`` int32 (per-row positions), live
    ``[S]`` bool -> (logits ``[S, vocab]``, new cache): every LIVE row
    advances its own session one position - writes its key and value at its
    row of every layer (a ring's: ``pos % R``) and attends over what its
    session has written.  A row that is not live leaves everything its slot
    owns as it was, reads nothing, gets no expert row and no count; its
    logits mean nothing."""
    counters = dict(cache["counters"])
    counters["attn_live_steps"] += live.astype(jnp.int32)
    counters["attn_past_window_steps"] += (
        live & (pos + 1 > cfg.sliding_window_size)).astype(jnp.int32)
    new_cache = {}
    h = _embed(cfg, params, token)
    for i in cfg.layers:
        window = cfg.window(i)

        def attn(pa, u):
            q, new = _qkv(cfg, pa, u, pos, bool(cfg.rope_layout[i]))
            o, new_cache[f"layer_{i}"] = ring_cache.step_attention(
                q, new, cache[f"layer_{i}"], pos, live, window, counters,
                attn_block=cfg.attn_block, scope=_scope(window))
            return _out(cfg, pa, o)

        h, counters = _layer(cfg, params[f"layer_{i}"], h, live, attn, counters)
    new_cache["counters"] = counters
    return _logits(cfg, params, h), new_cache


def prefill_chunk(cfg: Config, params, cache, tokens, slot, offset, n_valid):
    """tokens ``[C]`` int32 - ONE slot's prompt tokens at positions ``offset
    .. offset + C - 1``, the first ``n_valid`` real, the rest padding -> new
    cache: one forward pass writes the valid tokens' keys and values into
    the slot's rows of every layer (a ring's: ``(offset + i) % R``) and
    touches no other slot; its attention reads the slot's rows a block at a
    time and no further than ``offset + C``.  No final norm, head or logits:
    the caller decodes the prompt's LAST token the ordinary way.  The LAST
    layer's experts feed no cache row: neither they nor their plan are made,
    so what the counters say ran did run.  ``C`` is static and at most
    ``ring_slack`` where a ring wraps; ``slot``, ``offset`` and ``n_valid``
    are traced scalars, so one program serves every chunk."""
    C = tokens.shape[0]
    valid = jnp.arange(C) < n_valid
    pos = offset + jnp.arange(C)
    counters = cache["counters"]
    new_cache = {}
    h = _embed(cfg, params, tokens)
    for i in cfg.layers:
        window = cfg.window(i)

        def attn(pa, u):
            q, new = _qkv(cfg, pa, u, pos, bool(cfg.rope_layout[i]))
            o, new_cache[f"layer_{i}"] = ring_cache.chunk_attention(
                q, new, cache[f"layer_{i}"], slot, offset, n_valid, window,
                slack=cfg.ring_slack, attn_block=cfg.attn_block, dtype=cfg.dtype,
                scope=_scope(window))
            return _out(cfg, pa, o)

        h, counters = _layer(
            cfg, params[f"layer_{i}"], h, valid, attn, counters,
            experts=i != cfg.layers[-1], chunk_counts=CHUNK_COUNTS)
    new_cache["counters"] = counters
    return new_cache


decode_rows_read = ring_cache.decode_rows_read
prefill_rows_read = ring_cache.prefill_rows_read


def serve_decode_fns(cfg: Config):
    """What ``serve.ModelReplicaServer(decode_fns=...)`` is told of this
    model (``decoding.DecodeFns``): its step takes ``live`` (a row that is
    not live must leave its ring alone), and a step and a chunk read the
    cache as far as :func:`decode_rows_read` / :func:`prefill_rows_read` say."""
    return decoding.serve_fns(
        cfg, init_cache, decode_step_batch, prefill_chunk, wants_live=True,
        step_rows_read=functools.partial(decode_rows_read, cfg),
        chunk_rows_read=functools.partial(prefill_rows_read, cfg))


# ----------------------------------------------------------------------------
# Generation
# ----------------------------------------------------------------------------


def generate(cfg: Config, params, prompt, *, max_new_tokens: int,
             temperature: float = 0.0, rng: jax.Array | None = None):
    """prompt ``[B, Tp]`` -> ``[B, Tp + max_new_tokens]`` by
    :func:`prefill_chunk` and :func:`decode_step_batch`, the path a replica
    takes (models/decoding.py).  Its one chunk a row is the whole prompt, so
    the rings get the slack that chunk needs."""
    slack = max(cfg.ring_slack, jnp.shape(prompt)[1] - 1)
    return decoding.generate(
        dataclasses.replace(cfg, ring_slack=slack), params, prompt,
        init_cache=init_cache, prefill_chunk=prefill_chunk,
        decode_step_batch=decode_step_batch, max_new_tokens=max_new_tokens,
        temperature=temperature, rng=rng)
