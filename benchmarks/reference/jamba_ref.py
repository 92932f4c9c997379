"""Plain float32 reference of Jamba (Mamba-1 layers beside attention layers).

Follows the published description (AI21's ``config.json`` keys, read from
the dict ``c``): every layer ``x + mixer(rmsnorm(x))`` then ``x +
ffn(rmsnorm(x))`` with a gated SiLU feed-forward, a final RMSNorm, logits
through the embedding table (tied).  Layer ``i`` mixes by attention where
``i % attn_layer_period == attn_layer_offset`` (grouped K/V heads, causal,
no positions, no bias), else by Mamba-1 with the recurrence as a
``lax.scan`` over time.  No kernel, no cache, no batching tricks; it imports
nothing of the program and makes its own weights from the seed, ONE LAYER AT
A TIME (whole in float32 the tree is 12 GB: it never is).

Seeded leaves (the source publishes no initialisation; ``assumed`` in the
configuration's file): kernels and table normal 0.02 (``init_std``), the
projections back into the residual stream scaled by ``1 / sqrt(2 L)``; norms 1; ``A_log =
log(1..N)`` on every channel; ``D`` 1; ``dt_proj``'s bias the inverse
softplus of ``d_inner`` steps spaced geometrically over 0.001-0.1; conv bias
0.  Every leaf depends on ``(seed, leaf id)`` alone and is rounded ONCE to
bfloat16, the type the source holds parameters in: the program serves those
values, the reference computes with them in float32.  ``A_log`` is ``[N,
d_inner]`` and the conv kernel ``[d_conv, d_inner]``, the transposes of the
source's (the program keeps channels on the lanes); seeded values have no
orientation to lose.

A layer's leaf ids are ``1000 + 32 * layer + j``: a Mamba layer has 17
leaves, more than the stride of 16 that ``weights.build`` uses, so the
family brings its own.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import precision, weights

HIGHEST = precision.HIGHEST
_LAYER_BASE, _LAYER_STRIDE = 1000, 32


def head_dim(c: dict) -> int:
    return c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]


def layer_kinds(c: dict) -> list[str]:
    return [
        "attention" if i % c["attn_layer_period"] == c["attn_layer_offset"] else "mamba"
        for i in range(c["num_hidden_layers"])
    ]


# -- seeded leaves ------------------------------------------------------------


def init_std(c: dict) -> float:
    """The seeded kernels' standard deviation: 0.02, about ``1 /
    sqrt(hidden_size)`` at the published width, so that a product of unit
    inputs has unit size; a tiny width states its own (``init_std``) for
    the same reason - at 0.02 and width 64 the layers would add next to
    nothing to the embedding and no precision could be told from another."""
    return c.get("init_std", 0.02)


def top_spec(c: dict) -> list:
    return [
        (("emb", "table"), 0, (c["vocab_size"], c["hidden_size"]), "normal", init_std(c)),
        (("norm_f", "scale"), 1, (c["hidden_size"],), "ones", 0.0),
    ]


def layer_spec(c: dict, kind: str) -> list:
    """Rows ``(path, leaf id of layer 0, shape, kind, std)``."""
    D, F = c["hidden_size"], c["intermediate_size"]
    Di, N = c["mamba_expand"] * D, c["mamba_d_state"]
    R, K = c["mamba_dt_rank"], c["mamba_d_conv"]
    H, KV, hd = c["num_attention_heads"], c["num_key_value_heads"], head_dim(c)
    std = init_std(c)
    res = std / math.sqrt(2 * c["num_hidden_layers"])
    b = _LAYER_BASE
    if kind == "mamba":
        mixer = [
            (("mamba", "in_proj", "kernel"), b + 2, (D, 2 * Di), "normal", std),
            (("mamba", "conv", "kernel"), b + 3, (K, Di), "normal", std),
            (("mamba", "conv", "bias"), b + 4, (Di,), "zeros", 0.0),
            (("mamba", "x_proj", "kernel"), b + 5, (Di, R + 2 * N), "normal", std),
            (("mamba", "dt_norm", "scale"), b + 6, (R,), "ones", 0.0),
            (("mamba", "b_norm", "scale"), b + 7, (N,), "ones", 0.0),
            (("mamba", "c_norm", "scale"), b + 8, (N,), "ones", 0.0),
            (("mamba", "dt_proj", "kernel"), b + 9, (R, Di), "normal", std),
            (("mamba", "dt_proj", "bias"), b + 10, (Di,), "dt_bias", 0.0),
            (("mamba", "A_log", ), b + 11, (N, Di), "a_log", 0.0),
            (("mamba", "D"), b + 12, (Di,), "ones", 0.0),
            (("mamba", "out_proj", "kernel"), b + 13, (Di, D), "normal", res),
        ]
    else:
        mixer = [
            (("attention", "q", "kernel"), b + 2, (D, H * hd), "normal", std),
            (("attention", "k", "kernel"), b + 3, (D, KV * hd), "normal", std),
            (("attention", "v", "kernel"), b + 4, (D, KV * hd), "normal", std),
            (("attention", "o", "kernel"), b + 5, (H * hd, D), "normal", res),
        ]
    return [
        (("norm1", "scale"), b + 0, (D,), "ones", 0.0),
        (("norm2", "scale"), b + 1, (D,), "ones", 0.0),
        *mixer,
        (("ffn", "gate", "kernel"), b + 16, (D, F), "normal", std),
        (("ffn", "up", "kernel"), b + 17, (D, F), "normal", std),
        (("ffn", "down", "kernel"), b + 18, (F, D), "normal", res),
    ]


def _leaf(key, leaf_id, shape, kind: str, std: float):
    if kind == "a_log":
        steps = jnp.log(jnp.arange(1, shape[0] + 1, dtype=jnp.float32))
        return jnp.broadcast_to(steps[:, None], shape)
    if kind == "dt_bias":
        step = jnp.exp(jnp.linspace(math.log(0.001), math.log(0.1), shape[0]))
        return step + jnp.log(-jnp.expm1(-step))
    return weights.make_leaf(key, leaf_id, shape, kind, std)


def build(spec: list, key, layer=None, dtype=jnp.bfloat16) -> dict:
    """The nested dict of ``spec``'s leaves, each rounded to ``dtype`` (the
    served type); ``layer`` (it may be traced) offsets the ids."""
    tree: dict = {}
    for path, leaf_id, shape, kind, std in spec:
        if layer is not None:
            leaf_id = leaf_id + _LAYER_STRIDE * layer
        node = tree
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = _leaf(key, leaf_id, shape, kind, std).astype(dtype)
    return tree


def tree(c: dict, key, dtype=jnp.bfloat16) -> dict:
    """The whole parameter tree in the served type, named as the program
    names it (trace it under one jit)."""
    out = build(top_spec(c), key, dtype=dtype)
    for i, kind in enumerate(layer_kinds(c)):
        out[f"layer_{i}"] = build(layer_spec(c, kind), key, layer=i, dtype=dtype)
    return out


def _f32(tree_):
    return jax.tree.map(lambda x: x.astype(jnp.float32), tree_)


# -- the layers ----------------------------------------------------------------


def _rmsnorm(p, x, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * p["scale"]


def mamba_mixer(c: dict, p, u, mode: str):
    """u ``[B, L, D]`` float32, from the zero state."""
    B, L, _ = u.shape
    N, R, K = c["mamba_d_state"], c["mamba_dt_rank"], c["mamba_d_conv"]
    eps = c["rms_norm_eps"]
    x, z = jnp.split(precision.matmul(u, p["in_proj"]["kernel"], mode), 2, axis=-1)
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    x = p["conv"]["bias"] + sum(
        p["conv"]["kernel"][k] * padded[:, k:k + L] for k in range(K))
    x = jax.nn.silu(x)
    dt, b, cc = jnp.split(
        precision.matmul(x, p["x_proj"]["kernel"], mode), [R, R + N], axis=-1)
    dt = _rmsnorm(p["dt_norm"], dt, eps)
    b = _rmsnorm(p["b_norm"], b, eps)
    cc = _rmsnorm(p["c_norm"], cc, eps)
    dt = jax.nn.softplus(
        precision.matmul(dt, p["dt_proj"]["kernel"], mode) + p["dt_proj"]["bias"])
    a = -jnp.exp(p["A_log"])  # [N, Di]

    def step(h, inp):
        xt, dtt, bt, ct = inp  # [B, Di], [B, Di], [B, N], [B, N]
        h = jnp.exp(dtt[:, None] * a) * h + (dtt * xt)[:, None] * bt[:, :, None]
        return h, jnp.sum(h * ct[:, :, None], axis=1) + p["D"] * xt

    time_major = lambda v: jnp.moveaxis(v, 1, 0)
    h0 = jnp.zeros((B, N, x.shape[-1]), jnp.float32)
    _, y = jax.lax.scan(step, h0, tuple(time_major(v) for v in (x, dt, b, cc)))
    y = jnp.moveaxis(y, 0, 1)
    return precision.matmul(y * jax.nn.silu(z), p["out_proj"]["kernel"], mode)


def attention_mixer(c: dict, p, u, mode: str):
    B, L, _ = u.shape
    H, KV, hd = c["num_attention_heads"], c["num_key_value_heads"], head_dim(c)
    q = precision.matmul(u, p["q"]["kernel"], mode).reshape(B, L, KV, H // KV, hd)
    k = precision.matmul(u, p["k"]["kernel"], mode).reshape(B, L, KV, hd)
    v = precision.matmul(u, p["v"]["kernel"], mode).reshape(B, L, KV, hd)
    s = jnp.einsum("bqkgd,btkd->bkgqt", q, k, precision=HIGHEST) / np.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((L, L), bool)), s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqt,btkd->bqkgd", w, v, precision=HIGHEST).reshape(B, L, H * hd)
    return precision.matmul(o, p["o"]["kernel"], mode)


def layer(c: dict, kind: str, p, h, mode: str):
    eps = c["rms_norm_eps"]
    u = _rmsnorm(p["norm1"], h, eps)
    mixer = mamba_mixer if kind == "mamba" else attention_mixer
    h = h + mixer(c, p[kind], u, mode)
    u = _rmsnorm(p["norm2"], h, eps)
    f = p["ffn"]
    g = jax.nn.silu(precision.matmul(u, f["gate"]["kernel"], mode))
    g = g * precision.matmul(u, f["up"]["kernel"], mode)
    return h + precision.matmul(g, f["down"]["kernel"], mode)


@functools.lru_cache(maxsize=None)
def _programs(c_items: tuple, mode: str):
    c = dict(c_items)

    @jax.jit
    def embed(key, tokens):
        top = _f32(build(top_spec(c)[:1], key))
        return jnp.take(top["emb"]["table"], tokens, axis=0)

    def one(kind):
        spec = layer_spec(c, kind)
        return jax.jit(lambda key, i, h: layer(
            c, kind, _f32(build(spec, key, layer=i)), h, mode))

    @jax.jit
    def head(key, h_rows):
        top = _f32(build(top_spec(c), key))
        y = _rmsnorm(top["norm_f"], h_rows, c["rms_norm_eps"])
        return precision.matmul(y, top["emb"]["table"].T, mode)

    return embed, {"mamba": one("mamba"), "attention": one("attention")}, head


def _hidden(c: dict, seed: int, tokens, mode: str):
    embed, layers, head = _programs(tuple(sorted(c.items())), mode)
    key = weights.base_key(seed)
    h = embed(key, jnp.asarray(tokens, jnp.int32))
    for i, kind in enumerate(layer_kinds(c)):
        h = layers[kind](key, jnp.int32(i), h)
    return h, functools.partial(head, key)


def logits(c: dict, seed: int, tokens, mode: str = "float32") -> np.ndarray:
    """The full forward: logits ``[B, L, vocab]`` of ``tokens [B, L]``."""
    h, head = _hidden(c, seed, tokens, mode)
    return np.asarray(head(h))


def logits_at(c: dict, seed: int, tokens: np.ndarray, rows: np.ndarray,
              cols: np.ndarray, mode: str = "float32") -> np.ndarray:
    """Logits ``[len(rows), vocab]`` at positions ``(rows[i], cols[i])`` of
    the padded ``tokens [B, L]`` (causal, so right padding is inert)."""
    h, head = _hidden(c, seed, tokens, mode)
    return np.asarray(head(h[jnp.asarray(rows), jnp.asarray(cols)]))
