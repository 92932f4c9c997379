"""Plain float32 reference of DeepSeek-V2 (latent attention with scaled
rotary positions, a leading dense layer, expert layers with a choice limited
to groups and shared experts), for one chip's share of it.

Follows the published description (deepseek-ai's ``config.json`` keys, read
from the dict ``c``; ``rope_scaling``'s keys flattened to ``rope_<key>``).
With ``N`` an RMSNorm, every layer is

    x1 = x + MLA(N(x));  y = x1 + F(N(x1))

``F`` a dense gated-SiLU feed-forward (width ``intermediate_size``) in the
first ``first_k_dense_replace`` layers, the expert layer in every
``moe_layer_freq``-th layer after them.

MLA: ``cq = N(q_a h)``; ``q = q_b cq`` -> heads x (nope + rope); ``[ckv | kr]
= kv_a h``; ``c = N(ckv)``; ``[k_nope | v] = kv_b c``; rotary on ``q_rope``
and on the one ``kr`` all heads share, AS THE SOURCE WRITES IT: each rope
vector re-ordered from interleaved pairs to two halves, then ``x cos +
rotate_half(x) sin`` with ``cos``, ``sin`` of ``pos x inv_freq`` times
``m(mscale) / m(mscale_all_dim)``; ``inv_freq`` the YaRN law (``f_i =
theta^(-2i/dim)``; ``corr(r) = dim ln(original_max / (2 pi r)) / (2 ln
theta)``; ``low = floor(corr(beta_fast))``, ``high = ceil(corr(beta_slow))``;
``ramp_i = clip((i - low) / (high - low), 0, 1)``; ``inv_freq_i = f_i (1 -
ramp_i) + (f_i / factor) ramp_i``; ``m(a) = 0.1 a ln(factor) + 1``); causal
softmax of ``(nope + rope)^-0.5 m(mscale_all_dim)^2 q [k_nope | kr]``; ``o``.
In the EXPANDED form only: every position's keys and values are made; there
is no latent cache and nothing is absorbed.

Expert layer: ``s = softmax(router u)`` in float32 over ``n_routed_experts``,
no bias; the choice as the source's ``group_limited_greedy`` writes it -
scores reshaped ``[T, n_group, n / n_group]``, each group's maximum, the
``topk_group`` best groups, scores outside them set to 0, the
``num_experts_per_tok`` largest of what is left; weights
``routed_scaling_factor s_i``, not renormalised; ``E_i`` a gated-SiLU
feed-forward of width ``moe_intermediate_size``; beside them ONE gated-SiLU
feed-forward of width ``n_shared_experts x moe_intermediate_size`` on every
token with weight 1.  Final RMSNorm, untied head.

THE SHARE.  ``experts_held`` experts from ``expert_first`` on are here; a
choice on another routed expert adds nothing (what the other chips of the
deployment would add is left out, as the program leaves it out); the shared
experts and the dense layer are whole.  ``vocab_rows`` rows of the table and
columns of the head are here (slice 0 of the vocabulary).  The experts are a
plain loop over the held ids with a mask; positions go through attention a
block of queries at a time so that ``[3, 4096]`` fits.  No kernel, no cache,
no batching tricks; it imports nothing of the program and makes its own
weights from the seed, ONE LAYER AT A TIME and one expert at a time.

Seeded leaves (the source publishes no initialisation; ``assumed`` in the
configuration's file): kernels and table normal ``init_std``; EVERY
projection that writes the residual stream - ``o``, the dense ``down``, the
shared experts' ``down`` and each routed expert's ``down`` - scaled by ``1 /
sqrt(2 L)``; the router's kernel ``router_std_factor x init_std``; norms 1.
Every leaf depends on ``(seed, leaf id)`` alone and is rounded ONCE to
bfloat16.  A layer's leaf ids are ``1000 + 64 layer + j``; AN EXPERT'S LEAVES ARE KEYED
BY ITS GLOBAL ID, ``1000000 + 3 (n_routed_experts layer + expert) + j``: any
rank's share, and the uncut layer, come from one seed.
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


def held(c: dict) -> tuple[int, int]:
    """``(first, how many)`` of the routed experts that are here."""
    return c.get("expert_first", 0), c.get("experts_held") or c["n_routed_experts"]


def vocab(c: dict) -> int:
    return c.get("vocab_rows") or c["vocab_size"]


def layer_kind(c: dict, i: int) -> str:
    moe = i >= c["first_k_dense_replace"] and i % c["moe_layer_freq"] == 0
    return "moe" if moe else "dense"


# -- seeded leaves ------------------------------------------------------------


def top_spec(c: dict) -> list:
    D, V = c["hidden_size"], vocab(c)
    return [
        (("emb", "table"), 0, (V, D), "normal", init_std(c)),
        (("norm_f", "scale"), 1, (D,), "ones", 0.0),
        (("head", "kernel"), 2, (D, V), "normal", init_std(c)),
    ]


def layer_spec(c: dict, kind: str) -> list:
    """Rows ``(path, leaf id of layer 0, shape, kind, std)`` of a layer of
    ``kind`` but for its routed experts."""
    D, H = c["hidden_size"], c["num_attention_heads"]
    Rq, Rkv = c["q_lora_rank"], c["kv_lora_rank"]
    nope, rope, vd = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    std = init_std(c)
    res = std / math.sqrt(2 * c["num_hidden_layers"])
    b = _LAYER_BASE
    spec = [
        (("attn_norm", "scale"), b + 20, (D,), "ones", 0.0),
        (("attn", "q_a", "kernel"), b + 0, (D, Rq), "normal", std),
        (("attn", "q_norm", "scale"), b + 20, (Rq,), "ones", 0.0),
        (("attn", "q_b", "kernel"), b + 1, (Rq, H * (nope + rope)), "normal", std),
        (("attn", "kv_a", "kernel"), b + 2, (D, Rkv + rope), "normal", std),
        (("attn", "kv_norm", "scale"), b + 20, (Rkv,), "ones", 0.0),
        (("attn", "kv_b", "kernel"), b + 3, (Rkv, H * (nope + vd)), "normal", std),
        (("attn", "o", "kernel"), b + 4, (H * vd, D), "normal", res),
        (("ffn_norm", "scale"), b + 20, (D,), "ones", 0.0),
    ]
    if kind == "dense":
        name, first, F = "ffn", b + 10, c["intermediate_size"]
    else:
        name, first = "shared", b + 13
        F = c["n_shared_experts"] * c["moe_intermediate_size"]
        spec.append((("moe", "router", "kernel"), b + 16, (D, c["n_routed_experts"]),
                     "normal", std * c.get("router_std_factor", 1.0)))
    return spec + [
        ((name, "gate", "kernel"), first + 0, (D, F), "normal", std),
        ((name, "up", "kernel"), first + 1, (D, F), "normal", std),
        ((name, "down", "kernel"), first + 2, (F, D), "normal", res),
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
    """Routed expert ``e`` (its GLOBAL id; it may be traced) of ``layer``:
    ``gate, up [D, F]``, ``down [F, D]``."""
    D, F = c["hidden_size"], c["moe_intermediate_size"]
    base = _EXPERT_BASE + 3 * (c["n_routed_experts"] * layer + e)
    std = init_std(c)
    res = std / math.sqrt(2 * c["num_hidden_layers"])
    leaf = lambda j, shape, s=std: weights.make_leaf(
        key, base + j, shape, "normal", s).astype(dtype)
    return {"gate": leaf(0, (D, F)), "up": leaf(1, (D, F)), "down": leaf(2, (F, D), res)}


def tree(c: dict, key, dtype=jnp.bfloat16) -> dict:
    """The whole parameter tree of the share in the served type, named as
    the program names it, the held experts stacked (trace it under one
    jit)."""
    first, n = held(c)
    out = build(top_spec(c), key, dtype=dtype)
    for i in range(c["num_hidden_layers"]):
        kind = layer_kind(c, i)
        layer_ = build(layer_spec(c, kind), key, layer=i, dtype=dtype)
        if kind == "moe":
            layer_["moe"].update(jax.vmap(
                lambda e: expert(c, key, i, e, dtype))(first + jnp.arange(n)))
        out[f"layer_{i}"] = layer_
    return out


def _f32(tree_):
    return jax.tree.map(lambda x: x.astype(jnp.float32), tree_)


# -- the layers ----------------------------------------------------------------


def _rmsnorm(p, x, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * p["scale"]


def mscale(c: dict, a: float) -> float:
    """``m(a)``: 1 where nothing is stretched."""
    return 1.0 if c["rope_factor"] <= 1 else 0.1 * a * math.log(c["rope_factor"]) + 1.0


def correction_range(c: dict) -> tuple[int, int]:
    dim, theta = c["qk_rope_head_dim"], float(c["rope_theta"])
    corr = lambda r: (dim * math.log(c["rope_original_max_position_embeddings"]
                                     / (r * 2 * math.pi))) / (2 * math.log(theta))
    return (max(math.floor(corr(c["rope_beta_fast"])), 0),
            min(math.ceil(corr(c["rope_beta_slow"])), dim - 1))


def inv_freq(c: dict) -> np.ndarray:
    """The ``rope // 2`` frequencies of the YaRN law (module docstring)."""
    dim, theta = c["qk_rope_head_dim"], float(c["rope_theta"])
    freq_extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    freq_inter = freq_extra / c["rope_factor"]
    low, high = correction_range(c)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0, 1)
    keep = 1.0 - ramp
    return (freq_inter * (1 - keep) + freq_extra * keep).astype(np.float32)


def _rope(c: dict, x, pos):
    """``x [.., dim]`` at positions ``pos`` (broadcasting against ``x[...,
    0]``), the source's way: pairs ``(2 i, 2 i + 1)`` re-ordered to ``(i, i +
    dim / 2)``, then halves rotated.  The result is in the re-ordered layout,
    on queries and keys alike."""
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    a = pos[..., None] * jnp.asarray(inv_freq(c))
    m = mscale(c, c["rope_mscale"]) / mscale(c, c["rope_mscale_all_dim"])
    cos = jnp.concatenate([jnp.cos(a), jnp.cos(a)], axis=-1) * m
    sin = jnp.concatenate([jnp.sin(a), jnp.sin(a)], axis=-1) * m
    half = x.shape[-1] // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rotated * sin


def softmax_scale(c: dict) -> float:
    m = mscale(c, c["rope_mscale_all_dim"])
    return (c["qk_nope_head_dim"] + c["qk_rope_head_dim"]) ** -0.5 * m * m


def mla(c: dict, p, h, mode: str):
    """h ``[B, L, D]`` float32, normed -> ``[B, L, D]``."""
    B, L, _ = h.shape
    H, eps = c["num_attention_heads"], c["rms_norm_eps"]
    nope, rope, vd = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    Rkv = c["kv_lora_rank"]
    mm = lambda x, name: precision.matmul(x, p[name]["kernel"], mode)
    q = mm(_rmsnorm(p["q_norm"], mm(h, "q_a"), eps), "q_b").reshape(B, L, H, nope + rope)
    ckv = mm(h, "kv_a")
    kv = mm(_rmsnorm(p["kv_norm"], ckv[..., :Rkv], eps), "kv_b").reshape(B, L, H, nope + vd)
    pos = jnp.arange(L, dtype=jnp.float32)
    q = jnp.concatenate([q[..., :nope], _rope(c, q[..., nope:], pos[None, :, None])], axis=-1)
    kr = _rope(c, ckv[..., Rkv:], pos[None, :])
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
        s = jnp.einsum("bqhd,bthd->bhqt", qs, k, precision=HIGHEST) * softmax_scale(c)
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


def _kernels(p):
    return {k: v["kernel"] for k, v in p.items()}


def choose(c: dict, s):
    """The source's ``group_limited_greedy`` on scores ``s [.., n]``:
    ``(choice [.., k], their scores [.., k])``."""
    G, per = c["n_group"], c["n_routed_experts"] // c["n_group"]
    group_scores = s.reshape(s.shape[:-1] + (G, per)).max(axis=-1)
    _, group_idx = jax.lax.top_k(group_scores, c["topk_group"])
    group_mask = jax.nn.one_hot(group_idx, G, dtype=s.dtype).sum(axis=-2)  # [.., G]
    score_mask = jnp.broadcast_to(
        group_mask[..., None], group_mask.shape + (per,)).reshape(s.shape)
    tmp_scores = jnp.where(score_mask > 0, s, 0.0)
    w, choice = jax.lax.top_k(tmp_scores, c["num_experts_per_tok"])
    return choice, w


def route(c: dict, p, u):
    """``(choice [.., k] routed expert ids, weights [.., k])``, in float32
    whatever the mode."""
    s = jax.nn.softmax(jnp.matmul(u, p["router"]["kernel"], precision=HIGHEST), axis=-1)
    choice, w = choose(c, s)
    return choice, c["routed_scaling_factor"] * w


def routed(c: dict, p, expert_fn, u, mode: str):
    """The share's part of ``sum_i w_i E_i(u)``: a loop over the held ids,
    each expert applied to every token under a mask.  ``expert_fn(e)`` gives
    expert ``e``'s float32 matrices."""
    first, n = held(c)
    choice, w = route(c, p, u)

    def one(i, m):
        e = first + i
        w_e = jnp.sum(jnp.where(choice == e, w, 0.0), axis=-1, keepdims=True)
        return m + w_e * _gated(u, expert_fn(e), mode)

    return jax.lax.fori_loop(0, n, one, jnp.zeros_like(u))


def moe(c: dict, p, expert_fn, u, mode: str):
    """The expert layer of the normed ``u``: the share's routed part and the
    shared experts (``p``: the layer's ``moe`` and ``shared`` leaves)."""
    return routed(c, p["moe"], expert_fn, u, mode) + _gated(u, _kernels(p["shared"]), mode)


def layer(c: dict, kind: str, p, expert_fn, x, mode: str):
    eps = c["rms_norm_eps"]
    x = x + mla(c, p["attn"], _rmsnorm(p["attn_norm"], x, eps), mode)
    u = _rmsnorm(p["ffn_norm"], x, eps)
    if kind == "dense":
        return x + _gated(u, _kernels(p["ffn"]), mode)
    return x + moe(c, p, expert_fn, u, mode)


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
            c, kind, _f32(build(spec, key, layer=i)),
            lambda e: _f32(expert(c, key, i, e)), h, mode))

    @jax.jit
    def head(key, h_rows):
        top = _f32(build(top_spec(c)[1:], key))
        y = _rmsnorm(top["norm_f"], h_rows, c["rms_norm_eps"])
        return precision.matmul(y, top["head"]["kernel"], mode)

    return embed, {"dense": one("dense"), "moe": one("moe")}, head


def _hidden(c: dict, seed: int, tokens, mode: str):
    embed, one, head = _programs(tuple(sorted(c.items())), mode)
    key = weights.base_key(seed)
    h = embed(key, jnp.asarray(tokens, jnp.int32))
    for i in range(c["num_hidden_layers"]):
        h = one[layer_kind(c, i)](key, jnp.int32(i), h)
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
