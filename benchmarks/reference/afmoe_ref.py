"""Plain float32 reference of AFMoE (arcee-ai's Trinity family: gated
grouped-query attention behind QK-norm, sliding-window and full layers
mixed, leading dense layers, expert layers under a sigmoid router with
normalised weights and a bias in the choice), for one pipeline stage of it.

Follows the published description: ``config.json``'s keys, read from the
dict ``c``, for every size, ``layer_types``, ``sliding_window``,
``num_dense_layers``, ``num_experts_per_tok``, ``num_shared_experts``,
``route_scale``, ``rope_theta`` and ``rms_norm_eps`` (``score_func`` sigmoid,
``route_norm`` true, ``mup_enabled`` true, ``n_group`` = ``topk_group`` = 1
and an untied head are what this file writes); and the source's published
modelling code AS RECALLED for the four norms a layer and where they sit,
QK-norm, the output gate and its width, rotary in the sliding layers only,
``expert_bias`` in the choice only, the shared expert's width and the
``1e-20``.  With ``N`` an RMSNorm and ``h0 = sqrt(hidden_size) x
table[token]``, layer ``i`` of kind ``layer_types[i]`` is

    x1 = x  + N_post_attn(Attn(N_in(x)));   y = x1 + N_post_mlp(F(N_pre_mlp(x1)))

    Attn(u): q = N_q(u Wq -> [heads, head_dim]), k = N_k(u Wk -> [kv, head_dim]),
             v = u Wv -> [kv, head_dim]; in a sliding layer q and k rotated by
             rotary positions AS THE SOURCE WRITES IT - ``x cos + rotate_half(x)
             sin`` over halves ``(i, i + head_dim / 2)``, ``inv_freq_i =
             theta^(-2 i / head_dim)`` - in a full layer NOT AT ALL; keys and
             values repeated ``heads / kv`` times; softmax of ``q . k /
             sqrt(head_dim)`` over ``j <= t``, in a sliding layer also ``j > t -
             sliding_window``; ``out = (o * sigmoid(u Wg)) Wo``

``F`` a dense gated-SiLU feed-forward (``intermediate_size``) in the layers
below ``num_dense_layers``; after them ``s = sigmoid(u Wr)`` in float32, the
``num_experts_per_tok`` largest of ``s + expert_bias`` chosen, weights
``route_scale x s_i / (sum of the chosen s + 1e-20)``, ``E_i`` a gated-SiLU
feed-forward of width ``moe_intermediate_size``, and beside them ONE of
width ``num_shared_experts x moe_intermediate_size`` on every token with
weight 1.  Final RMSNorm, untied head.  The one re-ordering: each head's
vector is taken from the program's interleaved pairs to the source's two
halves before it is rotated (the seeded ``Wq``, ``Wk`` are drawn in the
program's order; a checkpoint's would be in the source's).

THE SHARE is of depth: ``held_layers`` lists the PUBLISHED layers that are
here (none: all).  Every one is whole - all ``num_experts`` experts, the
whole vocabulary.  The experts are a plain loop over their ids with a mask;
positions go through attention a block of queries at a time so that ``[3,
16384]`` fits; there is no cache, no ring (a mask), no kernel.  It imports
nothing of the program and makes its own weights from the seed, ONE LAYER
AT A TIME and one expert at a time.

Seeded leaves (the source publishes no initialisation; ``assumed`` in the
configuration's file): kernels and table normal ``init_std``; the router's
kernel ``router_std_factor x init_std``; a routed expert's ``down``
``expert_down_factor x init_std`` (what the routed part weighs beside the
shared expert in a sub-layer's write, which a norm then sets to unit size);
``expert_bias`` normal ``expert_bias_std``; norms 1.  Every leaf depends on
``(seed, leaf id)`` alone and is rounded ONCE to bfloat16.  A layer's leaf
ids are ``1000 + 64 layer + j`` with ``layer`` the PUBLISHED index; an
expert's ``1000000 + 3 (num_experts layer + expert) + j``: any stage, and
the whole model, come from one seed.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import precision, weights

HIGHEST = precision.HIGHEST
SLIDING, FULL = "sliding_attention", "full_attention"
_LAYER_BASE, _LAYER_STRIDE, _EXPERT_BASE = 1000, 64, 1_000_000
#: Query positions that go through attention at a time.
QUERY_BLOCK = 128
NORMALISE_EPS = 1e-20


def init_std(c: dict) -> float:
    return c.get("init_std", 1.0 / math.sqrt(c["hidden_size"]))


def held_layers(c: dict) -> tuple:
    return tuple(c.get("held_layers") or range(c["num_hidden_layers"]))


def is_dense(c: dict, i: int) -> bool:
    return i < c["num_dense_layers"]


# -- seeded leaves ------------------------------------------------------------


def top_spec(c: dict) -> list:
    D, V = c["hidden_size"], c["vocab_size"]
    return [
        (("emb", "table"), 0, (V, D), "normal", init_std(c)),
        (("norm_f", "scale"), 1, (D,), "ones", 0.0),
        (("head", "kernel"), 2, (D, V), "normal", init_std(c)),
    ]


def layer_spec(c: dict, dense: bool) -> list:
    """Rows ``(path, leaf id of layer 0, shape, kind, std)`` of a layer but
    for its routed experts."""
    D, H, KV, hd = (c["hidden_size"], c["num_attention_heads"],
                    c["num_key_value_heads"], c["head_dim"])
    std, b = init_std(c), _LAYER_BASE
    spec = [
        ((name, "scale"), b + 20, (D,), "ones", 0.0)
        for name in ("norm_in", "norm_post_attn", "norm_pre_mlp", "norm_post_mlp")
    ] + [
        (("attn", "q", "kernel"), b + 0, (D, H * hd), "normal", std),
        (("attn", "k", "kernel"), b + 1, (D, KV * hd), "normal", std),
        (("attn", "v", "kernel"), b + 2, (D, KV * hd), "normal", std),
        (("attn", "gate", "kernel"), b + 3, (D, H * hd), "normal", std),
        (("attn", "o", "kernel"), b + 4, (H * hd, D), "normal", std),
        (("attn", "q_norm", "scale"), b + 20, (hd,), "ones", 0.0),
        (("attn", "k_norm", "scale"), b + 20, (hd,), "ones", 0.0),
    ]
    if dense:
        name, first, F = "ffn", b + 10, c["intermediate_size"]
    else:
        name, first = "shared", b + 13
        F = c["num_shared_experts"] * c["moe_intermediate_size"]
        spec += [
            (("moe", "router", "kernel"), b + 16, (D, c["num_experts"]), "normal",
             std * c.get("router_std_factor", 1.0)),
            (("moe", "router", "bias"), b + 17, (c["num_experts"],), "normal",
             c.get("expert_bias_std", 0.0)),
        ]
    return spec + [
        ((name, "gate", "kernel"), first + 0, (D, F), "normal", std),
        ((name, "up", "kernel"), first + 1, (D, F), "normal", std),
        ((name, "down", "kernel"), first + 2, (F, D), "normal", std),
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
    """Routed expert ``e`` of PUBLISHED layer ``layer`` (either may be
    traced): ``gate, up [D, F]``, ``down [F, D]``."""
    D, F = c["hidden_size"], c["moe_intermediate_size"]
    base = _EXPERT_BASE + 3 * (c["num_experts"] * layer + e)
    leaf = lambda j, shape, s=1.0: weights.make_leaf(
        key, base + j, shape, "normal", s * init_std(c)).astype(dtype)
    return {"gate": leaf(0, (D, F)), "up": leaf(1, (D, F)),
            "down": leaf(2, (F, D), c.get("expert_down_factor", 1.0))}


def tree(c: dict, key, dtype=jnp.bfloat16) -> dict:
    """The whole parameter tree of the stage in the served type, named as
    the program names it (a layer by its published index), the experts
    stacked (trace it under one jit)."""
    out = build(top_spec(c), key, dtype=dtype)
    for i in held_layers(c):
        layer_ = build(layer_spec(c, is_dense(c, i)), key, layer=i, dtype=dtype)
        if not is_dense(c, i):
            layer_["moe"].update(jax.vmap(
                lambda e: expert(c, key, i, e, dtype))(jnp.arange(c["num_experts"])))
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


def attention(c: dict, p, h, kind: str, mode: str):
    """h ``[B, L, D]`` float32, normed -> ``[B, L, D]``."""
    B, L, _ = h.shape
    H, KV, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    eps = c["rms_norm_eps"]
    mm = lambda x, name: precision.matmul(x, p[name]["kernel"], mode)
    q = _rmsnorm(p["q_norm"], mm(h, "q").reshape(B, L, H, hd), eps)
    k = _rmsnorm(p["k_norm"], mm(h, "k").reshape(B, L, KV, hd), eps)
    v = mm(h, "v").reshape(B, L, KV, hd)
    if kind == SLIDING:
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
        if kind == SLIDING:
            seen &= behind < c["sliding_window"]
        w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqt,bthd->bqhd", w, v, precision=HIGHEST)

    o = jax.lax.map(block, starts)  # [n, B, qb, H, hd]
    rows = jnp.minimum(starts, L - qb)[:, None] + jnp.arange(qb)[None, :]
    out = jnp.zeros((B, L, H, hd), jnp.float32).at[:, rows.reshape(-1)].set(
        jnp.moveaxis(o, 0, 1).reshape(B, -1, H, hd))
    gate = jax.nn.sigmoid(mm(h, "gate"))
    return mm(out.reshape(B, L, H * hd) * gate, "o")


def _gated(x, p, mode: str):
    g = jax.nn.silu(precision.matmul(x, p["gate"], mode))
    return precision.matmul(g * precision.matmul(x, p["up"], mode), p["down"], mode)


def _kernels(p):
    return {k: v["kernel"] for k, v in p.items()}


def route(c: dict, p, u):
    """``(choice [.., k] expert ids, weights [.., k])``, in float32 whatever
    the mode: the bias picks, the scores weigh."""
    s = jax.nn.sigmoid(jnp.matmul(u, p["router"]["kernel"], precision=HIGHEST))
    _, choice = jax.lax.top_k(s + p["router"]["bias"], c["num_experts_per_tok"])
    w = jnp.take_along_axis(s, choice, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + NORMALISE_EPS)
    return choice, c["route_scale"] * w


def routed(c: dict, p, expert_fn, u, mode: str):
    """``sum_i w_i E_i(u)``: a loop over the experts' ids, each applied to
    every token under a mask.  ``expert_fn(e)`` gives expert ``e``'s float32
    matrices."""
    choice, w = route(c, p, u)

    def one(e, m):
        w_e = jnp.sum(jnp.where(choice == e, w, 0.0), axis=-1, keepdims=True)
        return m + w_e * _gated(u, expert_fn(e), mode)

    return jax.lax.fori_loop(0, c["num_experts"], one, jnp.zeros_like(u))


def layer(c: dict, i: int, p, expert_fn, x, mode: str):
    eps = c["rms_norm_eps"]
    a = attention(c, p["attn"], _rmsnorm(p["norm_in"], x, eps), c["layer_types"][i], mode)
    x = x + _rmsnorm(p["norm_post_attn"], a, eps)
    u = _rmsnorm(p["norm_pre_mlp"], x, eps)
    if is_dense(c, i):
        f = _gated(u, _kernels(p["ffn"]), mode)
    else:
        f = routed(c, p["moe"], expert_fn, u, mode) + _gated(u, _kernels(p["shared"]), mode)
    return x + _rmsnorm(p["norm_post_mlp"], f, eps)


def _hashable(c: dict) -> tuple:
    return tuple(sorted((k, tuple(v) if isinstance(v, (list, tuple)) else v)
                        for k, v in c.items()))


@functools.lru_cache(maxsize=None)
def _programs(c_items: tuple, mode: str):
    c = dict(c_items)

    @jax.jit
    def embed(key, tokens):
        top = _f32(build(top_spec(c)[:1], key))
        return jnp.take(top["emb"]["table"], tokens, axis=0) * math.sqrt(c["hidden_size"])

    def one(i):
        # A layer's kind and feed-forward are static: one program a
        # published index (five of them in the benchmark's stage).
        spec = layer_spec(c, is_dense(c, i))
        return jax.jit(lambda key, h: layer(
            c, i, _f32(build(spec, key, layer=i)),
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
