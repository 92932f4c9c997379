"""Jamba: Mamba-1 layers that carry a state beside attention layers that
carry keys and values (AI21's hybrid; the published ``config.json`` keys
are this module's ``Config``).

Every layer is ``x + mixer(norm(x))`` then ``x + ffn(norm(x))`` with RMSNorm
and a gated SiLU feed-forward; a final RMSNorm; logits through the embedding
table (tied).  The mixer of layer ``i`` is attention where ``i %
attn_layer_period == attn_layer_offset`` (grouped K/V heads, causal, no
positional encoding, no bias), else Mamba-1:

    x, z   = in_proj(u)                                  [.., 2 x d_inner]
    x      = silu(causal depthwise conv_{d_conv}(x) + bias)
    dt,B,C = rmsnorm each of x_proj(x)                   [.., dt_rank + 2 N]
    dt     = softplus(dt_proj(dt) + bias)                [.., d_inner]
    h_t    = exp(dt_t * A) * h_{t-1} + (dt_t x_t) B_t    A = -exp(A_log)
    y_t    = h_t . C_t + D x_t
    out    = out_proj(y * silu(z))

Precision.  Parameters are held in ``param_dtype`` (bfloat16, the type the
source publishes) and every product runs in it with float32 accumulation;
the residual stream, the norms, ``dt``, ``exp(dt A)``, the recurrence, the
state, the conv tail and the softmax are float32.  No path leaves a piece of
the mathematics out: the full forward (:func:`apply`), the prefill chunk
(:func:`prefill_chunk`) and the one-token step (:func:`decode_step_batch`)
compute the same layers, the first two through the selective-scan kernel
(ops/selective_scan.py), the step in plain ``jax.numpy``.

Layout.  ``A_log`` is ``[N, d_inner]`` and the conv kernel ``[d_conv,
d_inner]`` - the transposes of the source's - so that channels lie on the
TPU's lanes and 16 state dimensions are not padded to 128; the cached state
is ``[slots, N, d_inner]`` for the same reason.

What a session owns in the cache, per layer BY KIND: an attention layer
``k, v [slots, kv_heads, max_len, head_dim]`` (``param_dtype``); a Mamba
layer the conv tail ``[slots, d_conv - 1, d_inner]`` and the state
``[slots, N, d_inner]`` (float32).  A state is overwritten by every step,
so the serving contract differs from a key/value cache's (serve/
model_server.py ``_DecodeEngine``): the step is told which rows are LIVE
and leaves every other row's state as it was; a session starts from the
zero state - the chunk at ``offset == 0`` and the step at ``pos == 0``
start there, whatever the slot held; a chunk carries tail and state on
from the chunk before it.

Serving only: no loss, no mesh (one chip holds it whole).
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from ..ops.selective_scan import selective_scan
from . import decoding, layers

ATTENTION, MAMBA = "attention", "mamba"


@dataclasses.dataclass(frozen=True)
class Config:
    """The published keys (AI21-Jamba2-3B's values as defaults).  Fixed by
    the family and not keys here: the conv has a bias, the Mamba projections
    have none, every feed-forward is the dense one (``num_experts`` 1)."""

    vocab_size: int = 65536
    hidden_size: int = 2560
    num_hidden_layers: int = 28
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    num_attention_heads: int = 20
    num_key_value_heads: int = 1
    #: 0 = ``hidden_size // num_attention_heads`` (the source gives none).
    head_dim: int = 0
    intermediate_size: int = 8192
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_dt_rank: int = 160
    mamba_expand: int = 2
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = True
    param_dtype: str = "bfloat16"

    def __post_init__(self):
        if not self.tie_word_embeddings:
            raise ValueError("jamba: only the tied output head is built")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must be a multiple of K/V heads")

    @property
    def dtype(self):
        return jnp.dtype(self.param_dtype)

    @property
    def attn_head_dim(self) -> int:
        return self.head_dim or self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def layer_kinds(self) -> tuple[str, ...]:
        return tuple(
            ATTENTION if i % self.attn_layer_period == self.attn_layer_offset
            else MAMBA
            for i in range(self.num_hidden_layers)
        )


# ----------------------------------------------------------------------------
# Parameters
# ----------------------------------------------------------------------------


def init(cfg: Config, rng: jax.Array):
    """Kernels and table normal 0.02 (projections back into the residual
    stream scaled by ``1 / sqrt(2 L)``), norms 1, ``A = -(1..N)`` on every
    channel, ``D = 1``, ``dt_proj``'s bias the inverse softplus of steps
    drawn log-uniformly from 0.001-0.1 (Mamba's published initialisation),
    conv bias 0; all in ``param_dtype``."""
    dt = cfg.dtype
    D, Di, N, R = cfg.hidden_size, cfg.d_inner, cfg.mamba_d_state, cfg.mamba_dt_rank
    H, KV, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.attn_head_dim
    res = 0.02 / math.sqrt(2 * cfg.num_hidden_layers)

    def kernel(k, shape, std=0.02):
        return {"kernel": (std * jax.random.normal(k, shape)).astype(dt)}

    def mamba(k):
        k = jax.random.split(k, 6)
        step = jnp.exp(
            jax.random.uniform(k[4], (Di,)) * math.log(0.1 / 0.001) + math.log(0.001)
        )
        return {
            "in_proj": kernel(k[0], (D, 2 * Di)),
            "conv": {
                "kernel": (0.02 * jax.random.normal(k[1], (cfg.mamba_d_conv, Di))).astype(dt),
                "bias": jnp.zeros((Di,), dt),
            },
            "x_proj": kernel(k[2], (Di, R + 2 * N)),
            "dt_norm": layers.rmsnorm_init(R, dt),
            "b_norm": layers.rmsnorm_init(N, dt),
            "c_norm": layers.rmsnorm_init(N, dt),
            "dt_proj": {
                **kernel(k[3], (R, Di)),
                "bias": (step + jnp.log(-jnp.expm1(-step))).astype(dt),
            },
            "A_log": jnp.broadcast_to(
                jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32))[:, None], (N, Di)
            ).astype(dt),
            "D": jnp.ones((Di,), dt),
            "out_proj": kernel(k[5], (Di, D), res),
        }

    def attention(k):
        k = jax.random.split(k, 4)
        return {
            "q": kernel(k[0], (D, H * hd)), "k": kernel(k[1], (D, KV * hd)),
            "v": kernel(k[2], (D, KV * hd)), "o": kernel(k[3], (H * hd, D), res),
        }

    keys = jax.random.split(rng, cfg.num_hidden_layers + 1)
    params = {
        "emb": {"table": (0.02 * jax.random.normal(keys[-1], (cfg.vocab_size, D))).astype(dt)},
        "norm_f": layers.rmsnorm_init(D, dt),
    }
    for i, kind in enumerate(cfg.layer_kinds):
        km, kf = jax.random.split(keys[i])
        params[f"layer_{i}"] = {
            "norm1": layers.rmsnorm_init(D, dt),
            kind: mamba(km) if kind == MAMBA else attention(km),
            "norm2": layers.rmsnorm_init(D, dt),
            "ffn": layers.gated_mlp_init(
                kf, D, cfg.intermediate_size, out_std=res, dtype=dt),
        }
    return params


def init_cache(cfg: Config, slots: int, max_len: int):
    """What ``slots`` sessions own, per layer by kind (module docstring)."""
    Di, N, K = cfg.d_inner, cfg.mamba_d_state, cfg.mamba_d_conv
    kv = (slots, cfg.num_key_value_heads, max_len, cfg.attn_head_dim)
    cache = {}
    for i, kind in enumerate(cfg.layer_kinds):
        if kind == MAMBA:
            cache[f"layer_{i}"] = {
                "conv": jnp.zeros((slots, K - 1, Di), jnp.float32),
                "ssm": jnp.zeros((slots, N, Di), jnp.float32),
            }
        else:
            cache[f"layer_{i}"] = {
                "k": jnp.zeros(kv, cfg.dtype), "v": jnp.zeros(kv, cfg.dtype),
            }
    return cache


# ----------------------------------------------------------------------------
# The pieces the three paths share
# ----------------------------------------------------------------------------


def _mm(cfg: Config, p, x):
    """``x @ kernel (+ bias)``: operands in ``param_dtype``, float32 out."""
    return layers.dense(p, x.astype(cfg.dtype))


def _ffn(cfg: Config, p, h):
    y = layers.rmsnorm(p["norm2"], h, cfg.rms_norm_eps)
    return h + layers.gated_mlp(p["ffn"], y, dtype=cfg.dtype)


def _mamba_inputs(cfg: Config, p, u, tail):
    """From the normed input ``u [B, C, D]`` and the conv's carried tail
    ``[B, d_conv - 1, d_inner]`` to what the recurrence takes: ``x`` (after
    conv and SiLU), the gate ``z``, ``dt [B, C, d_inner]``, ``B, C [B, C,
    N]``; and the conv's input window ``[B, C + d_conv - 1, d_inner]``, from
    which the caller cuts the next tail."""
    C = u.shape[1]
    N, R = cfg.mamba_d_state, cfg.mamba_dt_rank
    x, z = jnp.split(_mm(cfg, p["in_proj"], u), 2, axis=-1)
    window = jnp.concatenate([tail, x], axis=1)
    w = p["conv"]["kernel"].astype(jnp.float32)
    x = p["conv"]["bias"].astype(jnp.float32) + sum(
        w[k] * window[:, k:k + C] for k in range(cfg.mamba_d_conv)
    )
    x = jax.nn.silu(x)
    dt, b, c = jnp.split(_mm(cfg, p["x_proj"], x), [R, R + N], axis=-1)
    eps = cfg.rms_norm_eps
    dt = layers.rmsnorm(p["dt_norm"], dt, eps)
    b = layers.rmsnorm(p["b_norm"], b, eps)
    c = layers.rmsnorm(p["c_norm"], c, eps)
    dt = jax.nn.softplus(_mm(cfg, p["dt_proj"], dt))
    return x, z, dt, b, c, window


def _mamba_chunk(cfg: Config, p, u, tail, h0, n_valid):
    """The Mamba mixer over ``C`` consecutive tokens of each of ``B``
    sequences: ``u [B, C, D]`` normed, of which the first ``n_valid``
    positions are real; ``tail``, ``h0`` carried in.  Returns the mixer's
    output and the tail and state after the valid tokens."""
    x, z, dt, b, c, window = _mamba_inputs(cfg, p, u, tail)
    a = -jnp.exp(p["A_log"].astype(jnp.float32))
    d = p["D"].astype(jnp.float32)
    with jax.named_scope("mamba/scan"):
        y, h = jax.lax.map(
            lambda row: selective_scan(
                row[0], row[1], a, row[2], row[3], d, row[4], n_valid),
            (x, dt, b, c, h0),
        )
    out = _mm(cfg, p["out_proj"], y * jax.nn.silu(z))
    tail = jax.lax.dynamic_slice_in_dim(window, n_valid, tail.shape[1], axis=1)
    return out, tail, h


def _mamba_step(cfg: Config, p, u, tail, h0):
    """The same mixer for ONE token of each row: ``u [S, D]``."""
    x, z, dt, b, c, window = _mamba_inputs(cfg, p, u[:, None], tail)
    x, z, dt, b, c = x[:, 0], z[:, 0], dt[:, 0], b[:, 0], c[:, 0]
    with jax.named_scope("mamba/state_update"):
        a = -jnp.exp(p["A_log"].astype(jnp.float32))
        h = jnp.exp(dt[:, None] * a) * h0 + (dt * x)[:, None] * b[:, :, None]
        y = jnp.sum(h * c[:, :, None], axis=1) + p["D"].astype(jnp.float32) * x
    return _mm(cfg, p["out_proj"], y * jax.nn.silu(z)), window[:, 1:], h


def _qkv(cfg: Config, p, u):
    """``q [.., KV, G, hd]``, ``k, v [.., KV, hd]`` in ``param_dtype`` (what
    the cache keeps) from the normed ``u [.., D]``."""
    KV, hd = cfg.num_key_value_heads, cfg.attn_head_dim
    G = cfg.num_attention_heads // KV
    lead = u.shape[:-1]
    q = _mm(cfg, p["q"], u).astype(cfg.dtype).reshape(lead + (KV, G, hd))
    k = _mm(cfg, p["k"], u).astype(cfg.dtype).reshape(lead + (KV, hd))
    v = _mm(cfg, p["v"], u).astype(cfg.dtype).reshape(lead + (KV, hd))
    return q, k, v


def _attend(cfg: Config, p, q, k, v, seen):
    """Grouped-query attention of ``q [B, Q, KV, G, hd]`` over ``k, v [B,
    KV, T, hd]`` where ``seen [B, Q, T]`` says which positions a query may
    read; softmax in float32; then the output projection."""
    with jax.named_scope("attn/mqa"):
        s = jnp.einsum(
            "bqkgd,bktd->bkgqt", q, k, preferred_element_type=jnp.float32
        ) / math.sqrt(cfg.attn_head_dim)
        s = jnp.where(seen[:, None, None], s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1).astype(cfg.dtype)
        o = jnp.einsum(
            "bkgqt,bktd->bqkgd", w, v, preferred_element_type=jnp.float32
        )
    return _mm(cfg, p["o"], o.reshape(o.shape[:2] + (-1,)))


# ----------------------------------------------------------------------------
# Full forward
# ----------------------------------------------------------------------------


def apply(cfg: Config, params, tokens):
    """tokens ``[B, L]`` int32 -> logits ``[B, L, V]`` float32, causal."""
    B, L = tokens.shape
    h = layers.embedding_lookup(params["emb"], tokens).astype(jnp.float32)
    causal = jnp.broadcast_to(jnp.tril(jnp.ones((L, L), bool)), (B, L, L))
    for i, kind in enumerate(cfg.layer_kinds):
        p = params[f"layer_{i}"]
        u = layers.rmsnorm(p["norm1"], h, cfg.rms_norm_eps)
        if kind == MAMBA:
            tail = jnp.zeros((B, cfg.mamba_d_conv - 1, cfg.d_inner), jnp.float32)
            h0 = jnp.zeros((B, cfg.mamba_d_state, cfg.d_inner), jnp.float32)
            out, _, _ = _mamba_chunk(cfg, p[MAMBA], u, tail, h0, L)
        else:
            q, k, v = _qkv(cfg, p[ATTENTION], u)
            out = _attend(
                cfg, p[ATTENTION], q, jnp.moveaxis(k, 1, 2), jnp.moveaxis(v, 1, 2),
                causal,
            )
        h = _ffn(cfg, p, h + out)
    return _logits(cfg, params, h)


def _logits(cfg: Config, params, h):
    h = layers.rmsnorm(params["norm_f"], h, cfg.rms_norm_eps).astype(cfg.dtype)
    return jnp.einsum(
        "...d,vd->...v", h, params["emb"]["table"],
        preferred_element_type=jnp.float32,
    )


# ----------------------------------------------------------------------------
# Serving: the one-token step and the prefill chunk
# ----------------------------------------------------------------------------


def _row_mask(mask, like):
    return mask.reshape(mask.shape + (1,) * (like.ndim - 1))


def decode_step_batch(cfg: Config, params, cache, token, pos, live):
    """token ``[S]`` int32, pos ``[S]`` int32 (per-row positions), live
    ``[S]`` bool -> (logits ``[S, V]``, new cache): every LIVE row advances
    its own session one position; a row that is not live (an empty slot, a
    session whose prompt is still being prefilled) leaves everything its
    slot owns as it was, and its logits mean nothing.  A row at ``pos == 0``
    starts from the zero state whatever its slot held."""
    T = next(c["k"].shape[2] for c in cache.values() if "k" in c)
    fresh = pos == 0
    here = (jnp.arange(T)[None] == pos[:, None]) & live[:, None]  # [S, T]
    seen = (jnp.arange(T)[None] <= pos[:, None])[:, None]  # [S, 1, T]
    h = layers.embedding_lookup(params["emb"], token).astype(jnp.float32)
    new_cache = {}
    for i, kind in enumerate(cfg.layer_kinds):
        p, c = params[f"layer_{i}"], cache[f"layer_{i}"]
        u = layers.rmsnorm(p["norm1"], h, cfg.rms_norm_eps)
        if kind == MAMBA:
            tail0 = jnp.where(_row_mask(fresh, c["conv"]), 0.0, c["conv"])
            h0 = jnp.where(_row_mask(fresh, c["ssm"]), 0.0, c["ssm"])
            out, tail, state = _mamba_step(cfg, p[MAMBA], u, tail0, h0)
            new_cache[f"layer_{i}"] = {
                "conv": jnp.where(_row_mask(live, tail), tail, c["conv"]),
                "ssm": jnp.where(_row_mask(live, state), state, c["ssm"]),
            }
        else:
            q, k, v = _qkv(cfg, p[ATTENTION], u)
            at = here[:, None, :, None]  # [S, 1, T, 1]
            ck = jnp.where(at, k[:, :, None], c["k"])
            cv = jnp.where(at, v[:, :, None], c["v"])
            # A live row reads its own new key; one that is not reads
            # whatever is there, and nothing keeps the result.
            out = _attend(cfg, p[ATTENTION], q[:, None], ck, cv, seen)[:, 0]
            new_cache[f"layer_{i}"] = {"k": ck, "v": cv}
        h = _ffn(cfg, p, h + out)
    return _logits(cfg, params, h), new_cache


def _kv_chunk_write(cache, new, slot, offset, n_valid):
    """Write ``new [KV, C, hd]`` rows ``[0, n_valid)`` into ``cache [S, KV,
    T, hd]`` at ``[slot, :, offset:offset + n_valid]`` and touch nothing
    else; returns the cache and the slot's rows ``[1, KV, T, hd]``.  The
    window starts at ``min(offset, T - C)`` (``dynamic_update_slice`` clamps
    a start that overruns and would overwrite earlier rows), the chunk
    rolled inside it; as models/transformer.py ``_block_prefill``."""
    KV, C, hd = new.shape
    T = cache.shape[2]
    start = jnp.clip(offset, 0, T - C)
    shift = offset - start
    i = jnp.arange(C) - shift
    own = ((i >= 0) & (i < n_valid))[None, None, :, None]
    at = (slot, 0, start, 0)
    old = jax.lax.dynamic_slice(cache, at, (1, KV, C, hd))
    win = jnp.where(own, jnp.roll(new[None], shift, axis=2), old)
    cache = jax.lax.dynamic_update_slice(cache, win, at)
    return cache, jax.lax.dynamic_slice_in_dim(cache, slot, 1, axis=0)


def prefill_chunk(cfg: Config, params, cache, tokens, slot, offset, n_valid):
    """tokens ``[C]`` int32 - ONE slot's prompt tokens at positions ``offset
    .. offset + C - 1``, the first ``n_valid`` real, the rest padding -> new
    cache: one forward pass writes the valid tokens' keys and values into
    the slot's rows and advances the slot's conv tails and states by exactly
    the valid tokens, from what the chunk before left there - or from zero
    where ``offset == 0`` - and touches no other slot.  No final norm, head
    or logits: the caller decodes the prompt's LAST token the ordinary way.
    ``C`` is static (at most the cache's ``max_len``); ``slot``, ``offset``
    and ``n_valid`` are traced scalars, so one program serves every chunk."""
    C = tokens.shape[0]
    T = next(c["k"].shape[2] for c in cache.values() if "k" in c)
    fresh = offset == 0
    q_pos = offset + jnp.arange(C)
    seen = (jnp.arange(T)[None] <= q_pos[:, None])[None]  # [1, C, T]
    h = layers.embedding_lookup(params["emb"], tokens[None]).astype(jnp.float32)
    new_cache = {}
    for i, kind in enumerate(cfg.layer_kinds):
        p, c = params[f"layer_{i}"], cache[f"layer_{i}"]
        u = layers.rmsnorm(p["norm1"], h, cfg.rms_norm_eps)
        if kind == MAMBA:
            row = lambda a: jax.lax.dynamic_slice_in_dim(a, slot, 1, axis=0)
            tail0 = jnp.where(fresh, 0.0, row(c["conv"]))
            h0 = jnp.where(fresh, 0.0, row(c["ssm"]))
            out, tail, state = _mamba_chunk(cfg, p[MAMBA], u, tail0, h0, n_valid)
            put = lambda a, r: jax.lax.dynamic_update_slice_in_dim(a, r, slot, axis=0)
            new_cache[f"layer_{i}"] = {
                "conv": put(c["conv"], tail), "ssm": put(c["ssm"], state),
            }
        else:
            q, k, v = _qkv(cfg, p[ATTENTION], u)
            ck, sk = _kv_chunk_write(c["k"], jnp.moveaxis(k[0], 0, 1), slot, offset, n_valid)
            cv, sv = _kv_chunk_write(c["v"], jnp.moveaxis(v[0], 0, 1), slot, offset, n_valid)
            out = _attend(cfg, p[ATTENTION], q, sk, sv, seen)
            new_cache[f"layer_{i}"] = {"k": ck, "v": cv}
        # The last layer's feed-forward feeds nothing that is returned; the
        # compiler drops it.
        h = _ffn(cfg, p, h + out)
    return new_cache


def serve_decode_fns(cfg: Config):
    """What ``serve.ModelReplicaServer(decode_fns=...)`` is told of this
    model (``decoding.DecodeFns``): its step takes ``live``, the rows that
    may change what their slots own (``_DecodeEngine``); a step and a chunk
    are taken to read all of the cache's rows."""
    return decoding.serve_fns(
        cfg, init_cache, decode_step_batch, prefill_chunk, wants_live=True)


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
