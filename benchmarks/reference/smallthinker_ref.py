"""Plain float32 reference of SmallThinker (PowerInfer's SmallThinker family:
a router that reads its layer's input before attention, whole layers of
ReLU-gated experts, a global layer with no positional encoding to three
rotary sliding-window layers, grouped-query attention), for one pipeline
stage of it.

Follows the published description: ``config.json``'s keys, read from the
dict ``c``, for every size, ``sliding_window_layout``, ``rope_layout``,
``sliding_window_size``, ``rope_theta``, ``rms_norm_eps`` and the counts of
experts (``moe_primary_router_apply_softmax`` true, ``norm_topk_prob`` true,
``rope_scaling`` null and an untied head are what this file writes); and the
catalog's description with the source's published modelling code AS RECALLED
for where the router reads (the UN-NORMED stream entering the layer), the
two pre-norms a layer and none after a sub-layer, no bias anywhere, ``relu``
on the gate's half, and rotary by halves.  With ``N`` an RMSNorm and ``h0 =
table[token]`` (not scaled), layer ``i`` is

    r = x Wr;   x1 = x + Attn(N_in(x));   y = x1 + sum_e w_e E_e(N_post(x1))

    choice = the moe_num_active_primary_experts largest of r;  w = softmax of r
             over the chosen;  E_e(u) = down_e(relu(gate_e u) * (up_e u))

    Attn(u): q = u Wq -> [heads, head_dim], k = u Wk, v = u Wv -> [kv, head_dim];
             where rope_layout[i] is 1, q and k rotated by rotary positions AS THE
             SOURCE WRITES IT - ``x cos + rotate_half(x) sin`` over halves ``(i, i
             + head_dim / 2)``, ``inv_freq_i = theta^(-2 i / head_dim)`` - where
             0 NOT AT ALL; keys and values repeated ``heads / kv`` times; softmax
             of ``q . k / sqrt(head_dim)`` over ``j <= t``, where
             sliding_window_layout[i] is 1 also ``j > t - sliding_window_size``;
             ``out = o Wo``

Final RMSNorm, untied head.  The one re-ordering: each head's vector is taken
from the program's interleaved pairs to the source's two halves before it is
rotated (the seeded ``Wq``, ``Wk`` are drawn in the program's order; a
checkpoint's would be in the source's).

THE SHARE is of depth: ``held_layers`` lists the PUBLISHED layers that are
here (none: all).  Every one is whole - all its experts, the whole
vocabulary.  The experts are a plain loop over their ids with a mask;
positions go through attention a block of queries at a time so that ``[3,
16384]`` fits; there is no cache, no ring (a mask), no kernel, no plan (the
router is one product where the equations put it).  It imports nothing of
the program and makes its own weights from the seed, ONE LAYER AT A TIME and
one expert at a time.

Seeded leaves (the source publishes no initialisation; ``assumed`` in the
configuration's file): kernels and table normal ``init_std``; the projections
that write the stream, ``o`` and every expert's ``down``, ``out_std_factor x
init_std`` - NO norm follows a sub-layer, so what is written stays as large
as it is written, and the factor sets how large the layers' writes are
BESIDE THE TABLE ROW the stream starts from (the stream's own size means
nothing: every reader but the router norms it); the router's kernel
``router_spread / (init_std x sqrt(hidden_size))`` in every layer - the
router reads the UN-NORMED stream, which starts as a table row of norm
``init_std x sqrt(hidden_size)`` and stays near it, so a token's logits
spread ``router_spread`` over the experts; norms 1.
Every leaf depends on ``(seed, leaf id)`` alone and is rounded ONCE to
bfloat16.  A layer's leaf ids are ``1000 + 64 layer + j`` with ``layer`` the
PUBLISHED index; an expert's ``1000000 + 3 (experts x layer + expert) + j``:
any stage, and the whole model, come from one seed.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import precision, weights

HIGHEST = precision.HIGHEST
_LAYER_BASE, _LAYER_STRIDE, _EXPERT_BASE = 1000, 64, 1_000_000
#: Query positions that go through attention at a time.
QUERY_BLOCK = 128


def init_std(c: dict) -> float:
    return c.get("init_std", 1.0 / math.sqrt(c["hidden_size"]))


def out_std(c: dict) -> float:
    return init_std(c) * c.get("out_std_factor", 1.0)


def held_layers(c: dict) -> tuple:
    return tuple(c.get("held_layers") or range(c["num_hidden_layers"]))


def router_std(c: dict) -> float:
    """The router's kernel, in every layer (module docstring)."""
    return c["router_spread"] / (init_std(c) * math.sqrt(c["hidden_size"]))


# -- seeded leaves ------------------------------------------------------------


def top_spec(c: dict) -> list:
    D, V = c["hidden_size"], c["vocab_size"]
    return [
        (("emb", "table"), 0, (V, D), "normal", init_std(c)),
        (("norm_f", "scale"), 1, (D,), "ones", 0.0),
        (("head", "kernel"), 2, (D, V), "normal", init_std(c)),
    ]


def layer_spec(c: dict, i: int) -> list:
    """Rows ``(path, leaf id of layer 0, shape, kind, std)`` of published
    layer ``i`` but for its experts."""
    D, H, KV, hd = (c["hidden_size"], c["num_attention_heads"],
                    c["num_key_value_heads"], c["head_dim"])
    std, b = init_std(c), _LAYER_BASE
    return [
        (("norm_in", "scale"), b + 20, (D,), "ones", 0.0),
        (("norm_post", "scale"), b + 20, (D,), "ones", 0.0),
        (("attn", "q", "kernel"), b + 0, (D, H * hd), "normal", std),
        (("attn", "k", "kernel"), b + 1, (D, KV * hd), "normal", std),
        (("attn", "v", "kernel"), b + 2, (D, KV * hd), "normal", std),
        (("attn", "o", "kernel"), b + 3, (H * hd, D), "normal", out_std(c)),
        (("moe", "router", "kernel"), b + 4, (D, c["moe_num_primary_experts"]),
         "normal", router_std(c)),
    ]


def build(spec: list, key, layer=None, dtype=jnp.bfloat16) -> dict:
    """The nested dict of ``spec``'s leaves, each rounded to ``dtype`` (the
    served type); ``layer`` (it may be traced) offsets the ids."""
    tree_: dict = {}
    for path, leaf_id, shape, kind, std in spec:
        if layer is not None:
            leaf_id = leaf_id + _LAYER_STRIDE * layer
        node = tree_
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = weights.make_leaf(key, leaf_id, shape, kind, std).astype(dtype)
    return tree_


def expert(c: dict, key, layer, e, dtype=jnp.bfloat16) -> dict:
    """Expert ``e`` of PUBLISHED layer ``layer`` (either may be traced):
    ``gate, up [D, F]``, ``down [F, D]``."""
    D, F = c["hidden_size"], c["moe_ffn_hidden_size"]
    base = _EXPERT_BASE + 3 * (c["moe_num_primary_experts"] * layer + e)
    leaf = lambda j, shape, std: weights.make_leaf(
        key, base + j, shape, "normal", std).astype(dtype)
    return {"gate": leaf(0, (D, F), init_std(c)), "up": leaf(1, (D, F), init_std(c)),
            "down": leaf(2, (F, D), out_std(c))}


def tree(c: dict, key, dtype=jnp.bfloat16) -> dict:
    """The whole parameter tree of the stage in the served type, named as
    the program names it (a layer by its published index), the experts
    stacked (trace it under one jit)."""
    out = build(top_spec(c), key, dtype=dtype)
    for i in held_layers(c):
        layer_ = build(layer_spec(c, i), key, layer=i, dtype=dtype)
        layer_["moe"].update(jax.vmap(lambda e: expert(c, key, i, e, dtype))(
            jnp.arange(c["moe_num_primary_experts"])))
        out[f"layer_{i}"] = layer_
    return out


def _f32(tree_):
    return jax.tree.map(lambda x: x.astype(jnp.float32), tree_)


# -- the layers ----------------------------------------------------------------


def _rmsnorm(p, x, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * p["scale"]


def _rope(c: dict, x, pos):
    """``x [.., head_dim]`` at positions ``pos`` (broadcasting against
    ``x[..., 0]``): the program's pairs ``(2 i, 2 i + 1)`` re-ordered to the
    source's ``(i, i + head_dim / 2)``, then halves rotated the source's
    way.  The result is in the source's layout, on queries and keys alike."""
    dim = x.shape[-1]
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    inv_freq = 1.0 / float(c["rope_theta"]) ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    a = pos[..., None] * jnp.asarray(inv_freq, jnp.float32)
    cos = jnp.concatenate([jnp.cos(a), jnp.cos(a)], axis=-1)
    sin = jnp.concatenate([jnp.sin(a), jnp.sin(a)], axis=-1)
    rotated = jnp.concatenate([-x[..., dim // 2:], x[..., :dim // 2]], axis=-1)
    return x * cos + rotated * sin


def attention(c: dict, i: int, p, h, mode: str):
    """h ``[B, L, D]`` float32, normed -> ``[B, L, D]``: layer ``i``'s."""
    B, L, _ = h.shape
    H, KV, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    mm = lambda x, name: precision.matmul(x, p[name]["kernel"], mode)
    q = mm(h, "q").reshape(B, L, H, hd)
    k = mm(h, "k").reshape(B, L, KV, hd)
    v = mm(h, "v").reshape(B, L, KV, hd)
    if c["rope_layout"][i]:
        pos = jnp.arange(L, dtype=jnp.float32)[None, :, None]
        q, k = _rope(c, q, pos), _rope(c, k, pos)
    # repeat_kv: query head g reads K/V head g // (H / KV).
    k, v = jnp.repeat(k, H // KV, axis=2), jnp.repeat(v, H // KV, axis=2)
    qb = min(QUERY_BLOCK, L)
    starts = jnp.arange(0, L, qb)

    def block(start):
        # The last block is read shifted back inside the sequence; its rows
        # are put where they belong below.
        start = jnp.minimum(start, L - qb)
        qs = jax.lax.dynamic_slice_in_dim(q, start, qb, axis=1)
        s = jnp.einsum("bqhd,bthd->bhqt", qs, k, precision=HIGHEST) / math.sqrt(hd)
        behind = (start + jnp.arange(qb))[:, None] - jnp.arange(L)[None, :]
        seen = behind >= 0
        if c["sliding_window_layout"][i]:
            seen &= behind < c["sliding_window_size"]
        w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqt,bthd->bqhd", w, v, precision=HIGHEST)

    o = jax.lax.map(block, starts)  # [n, B, qb, H, hd]
    rows = jnp.minimum(starts, L - qb)[:, None] + jnp.arange(qb)[None, :]
    out = jnp.zeros((B, L, H, hd), jnp.float32).at[:, rows.reshape(-1)].set(
        jnp.moveaxis(o, 0, 1).reshape(B, -1, H, hd))
    return mm(out.reshape(B, L, H * hd), "o")


def _reglu(x, p, mode: str):
    g = jax.nn.relu(precision.matmul(x, p["gate"], mode))
    return precision.matmul(g * precision.matmul(x, p["up"], mode), p["down"], mode)


def route(c: dict, p, x):
    """``(choice [.., k] expert ids, weights [.., k])`` from the layer's
    INPUT ``x``, in float32 whatever the mode: the largest logits, and the
    softmax over them alone."""
    r = jnp.matmul(x, p["router"]["kernel"], precision=HIGHEST)
    top, choice = jax.lax.top_k(r, c["moe_num_active_primary_experts"])
    return choice, jax.nn.softmax(top, axis=-1)


def routed(c: dict, choice, w, expert_fn, u, mode: str):
    """``sum_i w_i E_i(u)``: a loop over the experts' ids, each applied to
    every token under a mask.  ``expert_fn(e)`` gives expert ``e``'s float32
    matrices."""

    def one(e, m):
        w_e = jnp.sum(jnp.where(choice == e, w, 0.0), axis=-1, keepdims=True)
        return m + w_e * _reglu(u, expert_fn(e), mode)

    return jax.lax.fori_loop(0, c["moe_num_primary_experts"], one, jnp.zeros_like(u))


def layer(c: dict, i: int, p, expert_fn, x, mode: str):
    eps = c["rms_norm_eps"]
    choice, w = route(c, p["moe"], x)
    x = x + attention(c, i, p["attn"], _rmsnorm(p["norm_in"], x, eps), mode)
    return x + routed(c, choice, w, expert_fn, _rmsnorm(p["norm_post"], x, eps), mode)


def _hashable(c: dict) -> tuple:
    return tuple(sorted((k, tuple(v) if isinstance(v, (list, tuple)) else v)
                        for k, v in c.items()))


@functools.lru_cache(maxsize=None)
def _programs(c_items: tuple, mode: str):
    c = dict(c_items)

    @jax.jit
    def embed(key, tokens):
        top = _f32(build(top_spec(c)[:1], key))
        return jnp.take(top["emb"]["table"], tokens, axis=0)

    def one(i):
        # A layer's kind is static: one program a published index.
        return jax.jit(lambda key, h: layer(
            c, i, _f32(build(layer_spec(c, i), key, layer=i)),
            lambda e: _f32(expert(c, key, i, e)), h, mode))

    @jax.jit
    def head(key, h_rows):
        top = _f32(build(top_spec(c)[1:], key))
        y = _rmsnorm(top["norm_f"], h_rows, c["rms_norm_eps"])
        return precision.matmul(y, top["head"]["kernel"], mode)

    return embed, {i: one(i) for i in held_layers(c)}, head


def _hidden(c: dict, seed: int, tokens, mode: str):
    embed, one, head = _programs(_hashable(c), mode)
    key = weights.base_key(seed)
    h = embed(key, jnp.asarray(tokens, jnp.int32))
    for i in held_layers(c):
        h = one[i](key, h)
    return h, functools.partial(head, key)


def logits(c: dict, seed: int, tokens, mode: str = "float32") -> np.ndarray:
    """The full forward: logits ``[B, L, vocab]`` of ``tokens [B, L]``."""
    h, head = _hidden(c, seed, tokens, mode)
    return np.asarray(head(h))


def logits_at(c: dict, seed: int, tokens: np.ndarray, rows: np.ndarray,
              cols: np.ndarray, mode: str = "float32") -> np.ndarray:
    """Logits ``[len(rows), vocab]`` at positions ``(rows[i], cols[i])`` of
    the padded ``tokens [B, L]`` (causal, so right padding is inert): the
    head is applied to those rows alone."""
    h, head = _hidden(c, seed, tokens, mode)
    return np.asarray(head(h[jnp.asarray(rows), jnp.asarray(cols)]))
