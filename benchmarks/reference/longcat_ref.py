"""Plain float32 reference of LongCat-Flash's language model (latent
attention, a shortcut-connected expert layer with zero-compute experts), for
one chip's share of it.

Follows the published description (Meituan's ``config.json`` keys, read from
the dict ``c``).  Each of the ``num_layers`` layers is a double layer; with
``N`` an RMSNorm:

    x1 = x + MLA_0(N(x));  u = N(x1);  m = MoE(u);  x2 = x1 + FFN_0(u)
    x3 = x2 + MLA_1(N(x2));  y = x3 + FFN_1(N(x3)) + m

MLA: ``cq = N(q_a h) sqrt(D / q_lora_rank)``; ``q = q_b cq`` -> heads x (nope
+ rope); ``[ckv | kr] = kv_a h``; ``c = N(ckv) sqrt(D / kv_lora_rank)``;
``[k_nope | v] = kv_b c``; rotary (theta ``rope_theta``, pairs interleaved,
no scaling) on ``q_rope`` and on the one ``kr`` all heads share; causal
softmax of ``q [k_nope | kr] / sqrt(nope + rope)``; ``o``.  In the EXPANDED
form only: every position's keys and values are made; there is no latent
cache and nothing is absorbed.  MoE: ``s = softmax(router u)`` in float32
over ``n_routed_experts + zero_expert_num``; the ``moe_topk`` largest of ``s
+ bias``; weights ``routed_scaling_factor s_i``, not renormalised; ``E_i`` a
gated-SiLU feed-forward for a routed expert, the identity for a
zero-compute one.  Final RMSNorm, untied head.

THE SHARE.  ``experts_held`` experts from ``expert_first`` on are here; a
choice on another routed expert adds nothing (what the other chips of the
deployment would add is left out, as the program leaves it out); the
zero-compute experts are computed where the token is.  ``vocab_rows`` rows
of the table and columns of the head are here (slice 0 of the vocabulary).
The experts are a plain loop over the held ids with a mask; positions go
through attention a block of queries at a time so that ``[3, 8192]`` fits.
No kernel, no cache, no batching tricks; it imports nothing of the program
and makes its own weights from the seed, ONE LAYER AT A TIME and one expert
at a time.

Seeded leaves (the source publishes no initialisation; ``assumed`` in the
configuration's file): kernels and table normal ``init_std``; the
projections back into the residual stream (``o``, the dense ``down``)
scaled by ``1 / sqrt(4 L)``; an expert's ``down`` NOT scaled (its weight ``6
s_i`` is some 0.06 at random weights already); norms 1; the router's bias
normal 1e-4, small beside the scores' spread so that it moves near-ties
only.  Every leaf depends on ``(seed, leaf id)`` alone and is rounded ONCE
to bfloat16.  A layer's leaf ids are ``1000 + 64 layer + j``; AN EXPERT'S
LEAVES ARE KEYED BY ITS GLOBAL ID, ``1000000 + 3 (n_routed_experts layer +
expert) + j``: any rank's share, and the uncut layer, come from one seed.
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
    return c.get("init_std", 0.0128)


def held(c: dict) -> tuple[int, int]:
    """``(first, how many)`` of the routed experts that are here."""
    return c.get("expert_first", 0), c.get("experts_held") or c["n_routed_experts"]


def vocab(c: dict) -> int:
    return c.get("vocab_rows") or c["vocab_size"]


# -- seeded leaves ------------------------------------------------------------


def top_spec(c: dict) -> list:
    D, V = c["hidden_size"], vocab(c)
    return [
        (("emb", "table"), 0, (V, D), "normal", init_std(c)),
        (("norm_f", "scale"), 1, (D,), "ones", 0.0),
        (("head", "kernel"), 2, (D, V), "normal", init_std(c)),
    ]


def layer_spec(c: dict) -> list:
    """Rows ``(path, leaf id of layer 0, shape, kind, std)`` of a double
    layer but for its experts."""
    D, F, H = c["hidden_size"], c["ffn_hidden_size"], c["num_attention_heads"]
    Rq, Rkv = c["q_lora_rank"], c["kv_lora_rank"]
    nope, rope, vd = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    n_all = c["n_routed_experts"] + c["zero_expert_num"]
    std = init_std(c)
    res = std / math.sqrt(4 * c["num_layers"])
    b = _LAYER_BASE
    spec = [
        (("moe", "router", "kernel"), b + 16, (D, n_all), "normal", std),
        (("moe", "router", "bias"), b + 17, (n_all,), "normal", 1e-4),
    ]
    for j in (0, 1):
        a, f = b + 5 * j, b + 10 + 3 * j
        spec += [
            ((f"attn_norm_{j}", "scale"), b + 20, (D,), "ones", 0.0),
            ((f"attn_{j}", "q_a", "kernel"), a + 0, (D, Rq), "normal", std),
            ((f"attn_{j}", "q_norm", "scale"), b + 20, (Rq,), "ones", 0.0),
            ((f"attn_{j}", "q_b", "kernel"), a + 1, (Rq, H * (nope + rope)), "normal", std),
            ((f"attn_{j}", "kv_a", "kernel"), a + 2, (D, Rkv + rope), "normal", std),
            ((f"attn_{j}", "kv_norm", "scale"), b + 20, (Rkv,), "ones", 0.0),
            ((f"attn_{j}", "kv_b", "kernel"), a + 3, (Rkv, H * (nope + vd)), "normal", std),
            ((f"attn_{j}", "o", "kernel"), a + 4, (H * vd, D), "normal", res),
            ((f"ffn_norm_{j}", "scale"), b + 20, (D,), "ones", 0.0),
            ((f"ffn_{j}", "gate", "kernel"), f + 0, (D, F), "normal", std),
            ((f"ffn_{j}", "up", "kernel"), f + 1, (D, F), "normal", std),
            ((f"ffn_{j}", "down", "kernel"), f + 2, (F, D), "normal", res),
        ]
    return spec


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
        node[path[-1]] = weights.make_leaf(key, leaf_id, shape, kind, std).astype(dtype)
    return tree


def expert(c: dict, key, layer, e, dtype=jnp.bfloat16) -> dict:
    """Routed expert ``e`` (its GLOBAL id; it may be traced) of ``layer``:
    ``gate, up [D, F]``, ``down [F, D]``."""
    D, F = c["hidden_size"], c["expert_ffn_hidden_size"]
    base = _EXPERT_BASE + 3 * (c["n_routed_experts"] * layer + e)
    std = init_std(c)
    leaf = lambda j, shape: weights.make_leaf(key, base + j, shape, "normal", std).astype(dtype)
    return {"gate": leaf(0, (D, F)), "up": leaf(1, (D, F)), "down": leaf(2, (F, D))}


def tree(c: dict, key, dtype=jnp.bfloat16) -> dict:
    """The whole parameter tree of the share in the served type, named as
    the program names it, the held experts stacked (trace it under one
    jit)."""
    first, n = held(c)
    out = build(top_spec(c), key, dtype=dtype)
    spec = layer_spec(c)
    for i in range(c["num_layers"]):
        layer = build(spec, key, layer=i, dtype=dtype)
        layer["moe"].update(jax.vmap(
            lambda e: expert(c, key, i, e, dtype))(first + jnp.arange(n)))
        out[f"layer_{i}"] = layer
    return out


def _f32(tree_):
    return jax.tree.map(lambda x: x.astype(jnp.float32), tree_)


# -- the layers ----------------------------------------------------------------


def _rmsnorm(p, x, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * p["scale"]


def _rope(x, pos, theta):
    """Pairs ``(2 i, 2 i + 1)`` of ``x [.., L, .., dim]`` turned by ``pos
    theta ** (-2 i / dim)``; ``pos`` broadcasts against ``x[..., 0]``."""
    dim = x.shape[-1]
    inv = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    a = pos[..., None] * inv
    x0, x1 = x[..., 0::2], x[..., 1::2]
    return jnp.stack(
        [x0 * jnp.cos(a) - x1 * jnp.sin(a), x0 * jnp.sin(a) + x1 * jnp.cos(a)], axis=-1
    ).reshape(x.shape)


def mla(c: dict, p, h, mode: str):
    """h ``[B, L, D]`` float32, normed -> ``[B, L, D]``."""
    B, L, D = h.shape
    H, eps = c["num_attention_heads"], c["rms_norm_eps"]
    nope, rope, vd = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    Rq, Rkv = c["q_lora_rank"], c["kv_lora_rank"]
    mm = lambda x, name: precision.matmul(x, p[name]["kernel"], mode)
    cq = _rmsnorm(p["q_norm"], mm(h, "q_a"), eps)
    if c.get("mla_scale_q_lora", True):
        cq = cq * math.sqrt(D / Rq)
    q = mm(cq, "q_b").reshape(B, L, H, nope + rope)
    ckv = mm(h, "kv_a")
    lat = _rmsnorm(p["kv_norm"], ckv[..., :Rkv], eps)
    if c.get("mla_scale_kv_lora", True):
        lat = lat * math.sqrt(D / Rkv)
    kv = mm(lat, "kv_b").reshape(B, L, H, nope + vd)
    pos = jnp.arange(L, dtype=jnp.float32)
    theta = float(c["rope_theta"])
    q = jnp.concatenate(
        [q[..., :nope], _rope(q[..., nope:], pos[None, :, None], theta)], axis=-1)
    kr = _rope(ckv[..., Rkv:], pos[None, :], theta)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(kr[:, :, None], (B, L, H, rope))], axis=-1)
    v = kv[..., nope:]
    qb = min(QUERY_BLOCK, L)
    starts = jnp.arange(0, L, qb)

    def block(start):
        # The last block is read shifted back inside the sequence; its rows
        # are put where they belong below.
        start = jnp.minimum(start, L - qb)
        qs = jax.lax.dynamic_slice_in_dim(q, start, qb, axis=1)
        s = jnp.einsum("bqhd,bthd->bhqt", qs, k, precision=HIGHEST) / math.sqrt(nope + rope)
        seen = jnp.arange(L)[None, :] <= (start + jnp.arange(qb))[:, None]
        w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqt,bthd->bqhd", w, v, precision=HIGHEST)

    o = jax.lax.map(block, starts)  # [n, B, qb, H, vd]
    rows = jnp.minimum(starts, L - qb)[:, None] + jnp.arange(qb)[None, :]
    out = jnp.zeros((B, L, H, vd), jnp.float32).at[:, rows.reshape(-1)].set(
        jnp.moveaxis(o, 0, 1).reshape(B, -1, H, vd))
    return mm(out.reshape(B, L, H * vd), "o")


def _gated(x, p, mode: str):
    g = jax.nn.silu(precision.matmul(x, p["gate"], mode))
    return precision.matmul(g * precision.matmul(x, p["up"], mode), p["down"], mode)


def route(c: dict, p, u):
    """``(choice [.., k] ids over routed + zero-compute experts, weights
    [.., k])``, in float32 whatever the mode."""
    s = jax.nn.softmax(jnp.matmul(u, p["router"]["kernel"], precision=HIGHEST), axis=-1)
    _, choice = jax.lax.top_k(s + p["router"]["bias"], c["moe_topk"])
    return choice, c["routed_scaling_factor"] * jnp.take_along_axis(s, choice, axis=-1)


def moe(c: dict, p, expert_fn, u, mode: str):
    """The share's part of ``sum_i w_i E_i(u)``: a loop over the held ids,
    each expert applied to every token under a mask, then ``w u`` for the
    zero-compute choices.  ``expert_fn(e)`` gives expert ``e``'s float32
    matrices."""
    first, n = held(c)
    choice, w = route(c, p, u)

    def one(i, m):
        e = first + i
        w_e = jnp.sum(jnp.where(choice == e, w, 0.0), axis=-1, keepdims=True)
        return m + w_e * _gated(u, expert_fn(e), mode)

    m = jax.lax.fori_loop(0, n, one, jnp.zeros_like(u))
    w_zero = jnp.sum(jnp.where(choice >= c["n_routed_experts"], w, 0.0), axis=-1, keepdims=True)
    return m + w_zero * u


def layer(c: dict, p, expert_fn, x, mode: str):
    eps = c["rms_norm_eps"]
    ffn = lambda j, y: _gated(y, {k: v["kernel"] for k, v in p[f"ffn_{j}"].items()}, mode)
    x = x + mla(c, p["attn_0"], _rmsnorm(p["attn_norm_0"], x, eps), mode)
    u = _rmsnorm(p["ffn_norm_0"], x, eps)
    m = moe(c, p["moe"], expert_fn, u, mode)
    x = x + ffn(0, u)
    x = x + mla(c, p["attn_1"], _rmsnorm(p["attn_norm_1"], x, eps), mode)
    return x + ffn(1, _rmsnorm(p["ffn_norm_1"], x, eps)) + m


@functools.lru_cache(maxsize=None)
def _programs(c_items: tuple, mode: str):
    c = dict(c_items)

    @jax.jit
    def embed(key, tokens):
        top = _f32(build(top_spec(c)[:1], key))
        return jnp.take(top["emb"]["table"], tokens, axis=0)

    spec = layer_spec(c)

    @jax.jit
    def one(key, i, h):
        return layer(c, _f32(build(spec, key, layer=i)),
                     lambda e: _f32(expert(c, key, i, e)), h, mode)

    @jax.jit
    def head(key, h_rows):
        top = _f32(build(top_spec(c)[1:], key))
        y = _rmsnorm(top["norm_f"], h_rows, c["rms_norm_eps"])
        return precision.matmul(y, top["head"]["kernel"], mode)

    return embed, one, head


def _hidden(c: dict, seed: int, tokens, mode: str):
    embed, one, head = _programs(tuple(sorted(c.items())), mode)
    key = weights.base_key(seed)
    h = embed(key, jnp.asarray(tokens, jnp.int32))
    for i in range(c["num_layers"]):
        h = one(key, jnp.int32(i), h)
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
