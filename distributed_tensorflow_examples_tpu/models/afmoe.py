"""AFMoE: gated grouped-query attention behind QK-norm, three layers of four
over a sliding window and the fourth over everything, leading dense layers,
then expert layers of many small experts chosen by a sigmoid router with
normalised weights and a bias in the choice (arcee-ai's Trinity family,
``model_type`` ``afmoe``; the published ``config.json`` keys are this
module's ``Config``).

With ``N`` an RMSNorm (four a layer, one after each sub-layer too, before
the residual add), ``h0 = sqrt(hidden_size) x table[token]`` (``mup_enabled``)
and layer ``i`` of kind ``layer_types[i]``:

    x1 = x  + N_post_attn(Attn_i(N_in(x)))
    y  = x1 + N_post_mlp(F_i(N_pre_mlp(x1)))
    logits = head(N_final(y_last))                     untied head

    Attn(u):  q = N_q(u Wq as [heads, head_dim]);  k = N_k(u Wk as [kv, head_dim])
              v = u Wv as [kv, head_dim]           (N_q, N_k over a head's own width)
              sliding layer: q, k rotated by rotary positions (theta, the whole
                             head, no scaling); full layer: NO positional encoding
              o_t = softmax_j(q_t . k_j / sqrt(head_dim)) v_j   over j <= t, and in
                    a sliding layer also j > t - sliding_window; query head g reads
                    K/V head g // (heads / kv)
              out = (o * sigmoid(u Wg)) Wo          the gate per head and channel

``F`` is a dense gated-SiLU feed-forward ``hidden -> intermediate_size ->
hidden`` in the first ``num_dense_layers`` layers and the expert layer after
them, for ``u = N_pre_mlp(x1)``:

    s = sigmoid(u Wr) in float32;  choice = the num_experts_per_tok largest of s + b
    w = route_scale x s[choice] / (sum of s[choice] + 1e-20)
    f = Shared(u) + sum_i w_i E_choice_i(u)

``b`` (``expert_bias``) enters the CHOICE only.  The routed part is
ops/moe.py ``apply_share``'s with every expert of the model held (``first``
0, ``held`` ``num_experts``: the share is the whole layer); ``Shared`` is one
gated-SiLU feed-forward of width ``num_shared_experts x
moe_intermediate_size`` on every token, issued after the routed part as a
product of its own (``moe/shared``).  The rotary pairs are the INTERLEAVED
ones (``layers.rope_interleaved``: elements ``(2 i, 2 i + 1)``), where the
source rotates halves ``(i, i + head_dim / 2)``: a checkpoint's ``Wq``,
``Wk`` columns and the two norms' scales would be re-ordered within each
head on loading.

THE SHARE is of depth only: ``held_layers`` names the PUBLISHED layers that
live here (empty: all), in order - a stage of a pipeline whose every layer is
whole on its chip.  Parameters and cache entries are keyed by the published
index (``layer_4``), and a layer's kind and feed-forward are those of its
published index.

THE CACHE is models/ring_cache.py's, per layer BY KIND, arrays ``k`` and
``v`` a layer in ``param_dtype`` (a sliding layer's keys are kept rotated): a
full layer ``max_len`` rows a slot, a sliding layer a RING of
``sliding_window + ring_slack`` rows written before it is attended, a SPARE
slot beside the seated ones for the step's rows that are not live; what a
row holds is position arithmetic.  The step's and the chunk's blocked
attention are that module's too (``attend_step``, ``attend_chunk``).  The
step is told which rows are LIVE: one that is not leaves everything its slot
owns unchanged, reads nothing and is counted nowhere.

What the model counts on the device (the cache tree's ``counters``): the
``moe_*`` sums of models/deepseek.py (:data:`COUNTS`, the chunk's part of
three once more as ``moe_chunk_*``), and for the STEP's attention, per kind
of layer and summed over that kind's layers, the cache rows it read and the
rows its live sessions needed (``attn_window_rows_read`` / ``_needed``,
``attn_global_rows_read`` / ``_needed``, ``attn_rows_read`` for both kinds),
each a ``[slots]`` int32 array, a slot's own rows in its element: the engine
sums the elements' differences, each modulo 2**32, which a slot's 16,384
rows a step at a hundred steps a second reach in seven minutes and the sum
over 32 slots would in thirteen seconds.

Precision: parameters in ``param_dtype`` (bfloat16); products in it with
float32 accumulation; residual stream, norms, rotary, router, gate and
softmax in float32.

Serving only: no loss (``load_balance_coeff`` is training's), no mesh.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from ..ops import moe as moe_ops
from . import decoding, layers, ring_cache

SLIDING, FULL = "sliding_attention", "full_attention"
#: The source's pattern: ``global_attn_every_n_layers`` 4.
_PERIOD = (SLIDING, SLIDING, SLIDING, FULL)


@dataclasses.dataclass(frozen=True)
class Config:
    """The published keys (Trinity-Mini's values as defaults) and the share.
    Fixed by the family and not keys here: no bias in any projection, gated
    SiLU, sigmoid scoring with normalised weights (``score_func``,
    ``route_norm``), no group limit (``n_group`` = ``topk_group`` = 1),
    ``mup_enabled``, no rotary scaling, untied head."""

    vocab_size: int = 200192
    hidden_size: int = 2048
    intermediate_size: int = 6144
    moe_intermediate_size: int = 1024
    num_hidden_layers: int = 32
    num_dense_layers: int = 2
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    layer_types: tuple[str, ...] = _PERIOD * 8
    sliding_window: int = 2048
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    route_scale: float = 2.826
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e4
    #: The share (module docstring): published layer indices; () = all.
    held_layers: tuple[int, ...] = ()
    #: Rows a sliding layer's ring has beyond the window: the widest chunk
    #: that may be written into it (the serve engine's ``PREFILL_CHUNK``).
    ring_slack: int = 512
    #: Cache rows the step's and the chunk's attention read at a time.
    attn_block: int = 512
    param_dtype: str = "bfloat16"

    def __post_init__(self):
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers, "
                f"num_hidden_layers {self.num_hidden_layers}")
        if set(self.layer_types) - {SLIDING, FULL}:
            raise ValueError(f"layer_types other than {SLIDING!r} and {FULL!r}")
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

    def is_dense(self, i: int) -> bool:
        return i < self.num_dense_layers

    def window(self, i: int) -> int | None:
        """How far back layer ``i`` sees; None: everything."""
        return self.sliding_window if self.layer_types[i] == SLIDING else None

    def cache_rows(self, i: int, max_len: int) -> int:
        """Rows a slot has in layer ``i``'s cache (module docstring)."""
        if self.layer_types[i] == FULL:
            return max_len
        return min(max_len, self.sliding_window + self.ring_slack)

    @property
    def share(self) -> moe_ops.ShareConfig:
        return moe_ops.ShareConfig(
            n_experts=self.num_experts, n_zero=0, top_k=self.num_experts_per_tok,
            scale=float(self.route_scale), first=0, held=self.num_experts,
            scoring="sigmoid", normalise=True,
        )


# ----------------------------------------------------------------------------
# Parameters and cache
# ----------------------------------------------------------------------------


def init(cfg: Config, rng: jax.Array):
    """Kernels and table normal ``1 / sqrt(hidden)``, the router's bias
    normal 0.05, norms 1; all in ``param_dtype``; the experts stacked.  No
    projection is scaled for depth: every sub-layer's write passes a norm."""
    dt, D, F = cfg.dtype, cfg.hidden_size, cfg.moe_intermediate_size
    H, KV, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    std = 1.0 / math.sqrt(D)

    def normal(k, shape, s=std):
        return (s * jax.random.normal(k, shape)).astype(dt)

    keys = jax.random.split(rng, len(cfg.layers) + 2)
    params = {
        "emb": {"table": normal(keys[-1], (cfg.vocab_size, D))},
        "norm_f": layers.rmsnorm_init(D, dt),
        "head": {"kernel": normal(keys[-2], (D, cfg.vocab_size))},
    }
    for key, i in zip(keys, cfg.layers):
        k = jax.random.split(key, 12)
        layer = {
            "norm_in": layers.rmsnorm_init(D, dt),
            "norm_post_attn": layers.rmsnorm_init(D, dt),
            "norm_pre_mlp": layers.rmsnorm_init(D, dt),
            "norm_post_mlp": layers.rmsnorm_init(D, dt),
            "attn": {
                "q": {"kernel": normal(k[0], (D, H * hd))},
                "k": {"kernel": normal(k[1], (D, KV * hd))},
                "v": {"kernel": normal(k[2], (D, KV * hd))},
                "gate": {"kernel": normal(k[3], (D, H * hd))},
                "o": {"kernel": normal(k[4], (H * hd, D))},
                "q_norm": layers.rmsnorm_init(hd, dt),
                "k_norm": layers.rmsnorm_init(hd, dt),
            },
        }
        if cfg.is_dense(i):
            layer["ffn"] = layers.gated_mlp_init(
                k[5], D, cfg.intermediate_size, std=std, dtype=dt)
        else:
            E = cfg.num_experts
            layer["moe"] = {
                "router": {"kernel": normal(k[5], (D, E)),
                           "bias": normal(k[6], (E,), 0.05)},
                "gate": normal(k[7], (E, D, F)),
                "up": normal(k[8], (E, D, F)),
                "down": normal(k[9], (E, F, D)),
            }
            layer["shared"] = layers.gated_mlp_init(
                k[10], D, cfg.num_shared_experts * F, std=std, dtype=dt)
        params[f"layer_{i}"] = layer
    return params


#: What this model keeps of ops/moe.py ``SHARE_COUNTS``, as ``moe_<name>``,
#: and of them what the chunk keeps a second time as ``moe_chunk_<name>``
#: (models/deepseek.py has the reasons).
COUNTS = ("choices", "choices_held", "experts_touched", "calls", "tokens_reaching")
CHUNK_COUNTS = ("choices_held", "experts_touched", "calls")
#: The step's attention by kind of layer (module docstring), ``[slots]`` each.
ATTN_COUNTS = ring_cache.ATTN_COUNTS


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
    at positions ``pos [..]``: QK-norm over each head's own width, then in
    a sliding layer (``rotary``) the rotation."""
    KV, hd = cfg.num_key_value_heads, cfg.head_dim
    G = cfg.num_attention_heads // KV
    lead = u.shape[:-1]
    q = _norm(cfg, p["q_norm"], _mm(cfg, p["q"], u).reshape(lead + (KV, G, hd)))
    k = _norm(cfg, p["k_norm"], _mm(cfg, p["k"], u).reshape(lead + (KV, hd)))
    v = _mm(cfg, p["v"], u).reshape(lead + (KV, hd))
    if rotary:
        cos, sin = layers.rope_angles(pos, hd, cfg.rope_theta)  # [.., hd / 2]
        q = layers.rope_interleaved(q, cos[..., None, None, :], sin[..., None, None, :])
        k = layers.rope_interleaved(k, cos[..., None, :], sin[..., None, :])
    return q.astype(cfg.dtype), jnp.stack([k, v], axis=-3).astype(cfg.dtype)


def _gated_out(cfg: Config, p, u, o):
    """``(o * sigmoid(u Wg)) Wo`` for the heads' results ``o [.., KV, G,
    hd]`` float32."""
    with jax.named_scope("afmoe/gate"):
        g = jax.nn.sigmoid(_mm(cfg, p["gate"], u))
        return _mm(cfg, p["o"], o.reshape(g.shape) * g)


def _scope(window) -> str:
    return "afmoe/attn_global" if window is None else "afmoe/attn_window"


def _layer(cfg: Config, p, i: int, x, attn, routed):
    """Layer ``i`` on ``x [.., D]`` float32.  ``attn(p_attn, u)`` is the
    attention of the normed ``u``; ``routed(u)`` the routed experts' part of
    an expert layer; ``routed`` None: the layer's feed-forward feeds nothing
    that is kept and is skipped whole."""
    x = x + _norm(cfg, p["norm_post_attn"], attn(p["attn"], _norm(cfg, p["norm_in"], x)))
    if routed is None:
        return x
    u = _norm(cfg, p["norm_pre_mlp"], x)
    if cfg.is_dense(i):
        with jax.named_scope("ffn/dense"):
            f = layers.gated_mlp(p["ffn"], u, dtype=cfg.dtype)
    else:
        m = routed(u)
        with jax.named_scope("moe/shared"):
            f = m + layers.gated_mlp(p["shared"], u, dtype=cfg.dtype)
    return x + _norm(cfg, p["norm_post_mlp"], f)


def _embed(cfg: Config, params, tokens):
    h = layers.embedding_lookup(params["emb"], tokens).astype(jnp.float32)
    return h * math.sqrt(cfg.hidden_size)


def _logits(cfg: Config, params, h):
    return layers.dense(params["head"], _norm(cfg, params["norm_f"], h).astype(cfg.dtype))


# ----------------------------------------------------------------------------
# Full forward
# ----------------------------------------------------------------------------


def apply(cfg: Config, params, tokens):
    """tokens ``[B, L]`` int32 -> logits ``[B, L, vocab]`` float32; every
    layer's attention under its own mask (causal, and in a sliding layer the
    window), whole."""
    B, L = tokens.shape
    t = jnp.arange(L)
    behind = t[:, None] - t[None, :]  # [query, key]
    h = _embed(cfg, params, tokens)
    for i in cfg.layers:
        p = params[f"layer_{i}"]
        window = cfg.window(i)
        seen = behind >= 0 if window is None else (behind >= 0) & (behind < window)

        def attn(pa, u):
            q, kv = _qkv(cfg, pa, u, jnp.broadcast_to(t, (B, L)), window is not None)
            with jax.named_scope(_scope(window)):
                s = jnp.einsum("bqkgd,btkd->bkgqt", q, kv[:, :, 0],
                               preferred_element_type=jnp.float32)
                s = jnp.where(seen, s / math.sqrt(cfg.head_dim), -jnp.inf)
                w = jax.nn.softmax(s, axis=-1).astype(cfg.dtype)
                o = jnp.einsum("bkgqt,btkd->bqkgd", w, kv[:, :, 1],
                               preferred_element_type=jnp.float32)
            return _gated_out(cfg, pa, u, o)

        def routed(u):
            m, _ = moe_ops.apply_share(
                p["moe"], u.reshape(B * L, -1), cfg.share, dtype=cfg.dtype)
            return m.reshape(u.shape)

        h = _layer(cfg, p, i, h, attn, routed)
    return _logits(cfg, params, h)


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
    new_cache = {}
    h = _embed(cfg, params, token)
    for i in cfg.layers:
        p = params[f"layer_{i}"]
        window = cfg.window(i)

        def attn(pa, u):
            q, new = _qkv(cfg, pa, u, pos, window is not None)
            o, new_cache[f"layer_{i}"] = ring_cache.step_attention(
                q, new, cache[f"layer_{i}"], pos, live, window, counters,
                attn_block=cfg.attn_block, scope=_scope(window))
            return _gated_out(cfg, pa, u, o)

        def routed(u):
            nonlocal counters
            m, counters = moe_ops.apply_share_counted(
                p["moe"], u, cfg.share, live, counters, dtype=cfg.dtype)
            return m

        h = _layer(cfg, p, i, h, attn, routed)
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
    layer's feed-forward feeds no cache row and is not called, so what the
    counters say ran did run.  ``C`` is static and at most ``ring_slack``
    where a ring wraps; ``slot``, ``offset`` and ``n_valid`` are traced
    scalars, so one program serves every chunk."""
    C = tokens.shape[0]
    valid = jnp.arange(C) < n_valid
    pos = offset + jnp.arange(C)
    counters = cache["counters"]
    new_cache = {}
    h = _embed(cfg, params, tokens)
    for i in cfg.layers:
        p = params[f"layer_{i}"]
        window = cfg.window(i)

        def attn(pa, u):
            q, new = _qkv(cfg, pa, u, pos, window is not None)
            o, new_cache[f"layer_{i}"] = ring_cache.chunk_attention(
                q, new, cache[f"layer_{i}"], slot, offset, n_valid, window,
                slack=cfg.ring_slack, attn_block=cfg.attn_block, dtype=cfg.dtype,
                scope=_scope(window))
            return _gated_out(cfg, pa, u, o)

        def routed(u):
            nonlocal counters
            m, counters = moe_ops.apply_share_counted(
                p["moe"], u, cfg.share, valid, counters,
                chunk_counts=CHUNK_COUNTS, dtype=cfg.dtype)
            return m

        h = _layer(cfg, p, i, h, attn, routed if i != cfg.layers[-1] else None)
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
