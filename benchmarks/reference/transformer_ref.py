"""Plain float32 reference of the GPT-2-style block the cell runs.

Pre-norm blocks, learned positions, tanh-GELU MLP with biases, qkv and
proj without bias, an untied output head; qkv columns are head-major
``(H, 3, head_dim)``.  No cache, no batching tricks, no kernels; it imports
nothing of the program and makes its own weights from the seed, one layer
at a time, so it fits beside whatever else the device holds.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import precision, weights

HIGHEST = precision.HIGHEST


def _layernorm(p, x, eps=1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def block(p, h, n_heads: int, mode: str):
    """h: [B, L, D] float32, causal."""
    B, L, D = h.shape
    hd = D // n_heads
    y = _layernorm(p["ln1"], h)
    qkv = precision.matmul(y, p["qkv"]["kernel"], mode).reshape(B, L, n_heads, 3, hd)
    q, k, v = (jnp.moveaxis(qkv[:, :, :, j], 2, 1) for j in range(3))  # [B,H,L,hd]
    s = jnp.einsum("bhqd,bhtd->bhqt", q, k, precision=HIGHEST) / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((L, L), bool))
    s = jnp.where(causal, s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqt,bhtd->bhqd", w, v, precision=HIGHEST)
    o = jnp.moveaxis(o, 1, 2).reshape(B, L, D)
    h = h + precision.matmul(o, p["proj"]["kernel"], mode)
    y = _layernorm(p["ln2"], h)
    y = precision.matmul(y, p["mlp_in"]["kernel"], mode) + p["mlp_in"]["bias"]
    y = jax.nn.gelu(y, approximate=True)
    return h + precision.matmul(y, p["mlp_out"]["kernel"], mode) + p["mlp_out"]["bias"]


@functools.lru_cache(maxsize=None)
def _programs(c_items: tuple, mode: str):
    c = dict(c_items)
    top_spec = weights.transformer_top_spec(c)
    block_spec = weights.transformer_block_spec(c)

    @jax.jit
    def embed(key, tokens):
        top = weights.build(top_spec[:2], key)
        L = tokens.shape[1]
        return jnp.take(top["emb"]["table"], tokens, axis=0) + top["pos"]["table"][:L][None]

    @jax.jit
    def layer(key, i, h):
        return block(weights.build(block_spec, key, layer=i), h, c["n_heads"], mode)

    @jax.jit
    def head(key, h_rows):
        top = weights.build(top_spec[2:], key)
        return precision.matmul(_layernorm(top["ln_f"], h_rows), top["head"]["kernel"], mode)

    return embed, layer, head


def logits_at(c: dict, seed: int, tokens: np.ndarray, rows: np.ndarray,
              cols: np.ndarray, mode: str = "float32") -> np.ndarray:
    """Logits ``[len(rows), vocab]`` at positions ``(rows[i], cols[i])`` of
    the padded ``tokens [B, L]`` (causal, so right padding is inert)."""
    embed, layer, head = _programs(tuple(sorted(c.items())), mode)
    key = weights.base_key(seed)
    h = embed(key, jnp.asarray(tokens, jnp.int32))
    for i in range(c["n_layers"]):
        h = layer(key, jnp.int32(i), h)
    out = head(key, h[jnp.asarray(rows), jnp.asarray(cols)])
    return np.asarray(out)
