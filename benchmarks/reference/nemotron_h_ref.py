"""Plain float32 reference of Nemotron-H (NVIDIA's ``nemotron_h``: layers of
ONE sub-layer each - a Mamba-2 mixer, grouped-query attention without
positional encoding, or a LatentMoE feed-forward of ungated squared-ReLU
experts in a narrower latent beside a shared expert), for one chip's share of
one pipeline stage of it.

Follows the published description: ``config.json``'s keys, read from the dict
``c``, for every size, ``hybrid_override_pattern``, ``layer_norm_epsilon``,
``routed_scaling_factor`` and the counts (``n_group`` 1, ``norm_topk_prob``
true, ``mlp_hidden_act`` ``relu2``, ``use_conv_bias`` true, no other bias and
an untied head are what this file writes); and the catalog's description with
the family's published modelling code AS RECALLED for the rest: no positional
encoding in attention, the gate before the grouped norm, latent projections
that are plain products.  Embedding, then layer ``i`` is ``x <- x + part_i(N(x))``
with ``N`` an RMSNorm and ``part_i`` by ``hybrid_override_pattern[i]``:

  M  z, xBC, dt = split(u W_in, [d_inner, d_inner + 2 G N, H]);  xBC = silu(conv(xBC) + bias),
     causal, depthwise, ``conv_kernel`` taps;  x, B, C = split(xBC) as [H, P], [G, N], [G, N];
     dt = softplus(dt + dt_bias);  A = -exp(A_log);  head h of group h // (H / G):
         S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] x_t[h] (outer) B_t[g(h)]
         y_t[h] = S_t[h] C_t[g(h)] + D[h] x_t[h]
     out = (rmsnorm within each of G groups of channels (y * silu(z)) * weight) W_out
  *  q, k, v = u Wq, u Wk, u Wv;  softmax(q k / sqrt(head_dim)) over j <= t, query head g
     reading K/V head g // (heads / kv);  out = o Wo.  NO positional encoding.
  E  s = sigmoid(u Wr);  choice = the num_experts_per_tok largest of s + bias;
     w = routed_scaling_factor s_i / (sum of the chosen s + 1e-20);  l = u W_li;
     out = (sum_i w_i W2_i relu(W1_i l)^2) W_lo + V2 relu(V1 u)^2

Final RMSNorm, untied head.  THE RECURRENCE IS THE RECURRENCE: a ``lax.scan``
over positions, one at a time - not the chunked form the program's kernel
computes.  Positions go through a Mamba layer ``POSITION_BLOCK`` at a time,
the tail and the state carried from block to block, through the experts the
same, through attention a block of queries at a time, so that ``[3, 25600]``
fits; there is no cache, no kernel, no plan.

THE SHARE: ``held_layers`` lists the PUBLISHED layers that are here (none:
all); of every ``E`` layer's experts ``experts_held`` from ``expert_first`` on
(0: all) - a loop over THEIR ids with a mask, a choice on any other adding
nothing; of the vocabulary the first ``vocab_rows`` ids (0: all).  It imports
nothing of the program and makes its own weights from the seed, one layer at
a time and one expert at a time.

Seeded leaves (the source publishes no initialisation; ``assumed`` in the
configuration's file): the table normal ``table_std`` (1: a row has unit rms,
what every reader sees after its norm - every reader of the stream norms it,
so the stream's own size means nothing and only the RATIO of a write to the
table row matters); every projection that READS normal ``1 / sqrt(fan in)``;
every projection that WRITES the stream - ``W_out``, ``Wo``, ``W_lo``, ``V2`` -
normal ``out_factor / sqrt(fan in)``, every one alike; a routed expert's
``W2`` ``expert_down_factor / sqrt(fan in)``; the router's kernel
``router_spread / sqrt(hidden_size)``, its bias normal ``expert_bias_std``;
conv taps normal ``conv_std``, the conv's bias normal ``conv_bias_std``;
``dt_bias`` the inverse softplus of steps log-spaced over the heads from
0.001 to 0.1, ``A_log = log(1 + h mod 16)``, ``D`` and the norms 1.  Every
leaf depends on ``(seed, leaf id)`` alone and is rounded ONCE to bfloat16.  A
layer's leaf ids are ``1000 + 64 layer + j`` with ``layer`` the PUBLISHED
index; an expert's ``1000000 + 2 (n_routed_experts x layer + expert) + j``:
any share of any stage, and the whole model, come from one seed.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import precision, weights

HIGHEST = precision.HIGHEST
MAMBA, EXPERTS, ATTENTION = "M", "E", "*"
_LAYER_BASE, _LAYER_STRIDE, _EXPERT_BASE = 1000, 64, 1_000_000
#: Query positions that go through attention at a time, and positions that
#: go through a Mamba or an expert layer at a time.
QUERY_BLOCK = 128
POSITION_BLOCK = 2048
NORMALISE_EPS = 1e-20


def held_layers(c: dict) -> tuple:
    return tuple(c.get("held_layers") or range(c["num_hidden_layers"]))


def held_experts(c: dict) -> tuple[int, int]:
    """``(first, count)`` of the routed experts that are here."""
    return c.get("expert_first", 0), c.get("experts_held") or c["n_routed_experts"]


def vocab(c: dict) -> int:
    return c.get("vocab_rows") or c["vocab_size"]


def kind(c: dict, i: int) -> str:
    return c["hybrid_override_pattern"][i]


def d_inner(c: dict) -> int:
    return c["mamba_num_heads"] * c["mamba_head_dim"]


def conv_dim(c: dict) -> int:
    return d_inner(c) + 2 * c["n_groups"] * c["ssm_state_size"]


# -- seeded leaves ------------------------------------------------------------


def top_spec(c: dict) -> list:
    D, V = c["hidden_size"], vocab(c)
    return [
        (("emb", "table"), 0, (V, D), "normal", c.get("table_std", 1.0)),
        (("norm_f", "scale"), 1, (D,), "ones", 0.0),
        (("head", "kernel"), 2, (D, V), "normal", 1 / math.sqrt(D)),
    ]


def layer_spec(c: dict, i: int) -> list:
    """Rows ``(path, leaf id of layer 0, shape, kind, std)`` of published
    layer ``i`` but for its routed experts."""
    return kind_spec(c, kind(c, i))


def kind_spec(c: dict, kind_: str) -> list:
    """:func:`layer_spec` of a layer of ``kind_``, whichever its index."""
    D, b = c["hidden_size"], _LAYER_BASE
    into = lambda fan_in: 1 / math.sqrt(fan_in)
    out = lambda fan_in: c.get("out_factor", 1.0) / math.sqrt(fan_in)
    norm = [(("norm", "scale"), b + 20, (D,), "ones", 0.0)]
    if kind_ == MAMBA:
        Di, H, Cd = d_inner(c), c["mamba_num_heads"], conv_dim(c)
        return norm + [
            (("mamba", "in_proj", "kernel"), b + 0, (D, Di + Cd + H), "normal", into(D)),
            (("mamba", "conv", "kernel"), b + 1, (c["conv_kernel"], Cd), "normal",
             c.get("conv_std", 0.5)),
            (("mamba", "conv", "bias"), b + 2, (Cd,), "normal", c.get("conv_bias_std", 0.0)),
            (("mamba", "dt_bias",), b + 20, (H,), "dt_bias", 0.0),
            (("mamba", "A_log",), b + 20, (H,), "a_log", 0.0),
            (("mamba", "D",), b + 20, (H,), "ones", 0.0),
            (("mamba", "norm", "scale"), b + 20, (Di,), "ones", 0.0),
            (("mamba", "out_proj", "kernel"), b + 3, (Di, D), "normal", out(Di)),
        ]
    if kind_ == ATTENTION:
        A, KV, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
        return norm + [
            (("attn", "q", "kernel"), b + 4, (D, A * hd), "normal", into(D)),
            (("attn", "k", "kernel"), b + 5, (D, KV * hd), "normal", into(D)),
            (("attn", "v", "kernel"), b + 6, (D, KV * hd), "normal", into(D)),
            (("attn", "o", "kernel"), b + 7, (A * hd, D), "normal", out(A * hd)),
        ]
    E, Lt, Fs = c["n_routed_experts"], c["moe_latent_size"], c["moe_shared_expert_intermediate_size"]
    return norm + [
        (("moe", "router", "kernel"), b + 8, (D, E), "normal",
         c.get("router_spread", 1.0) / math.sqrt(D)),
        (("moe", "router", "bias"), b + 9, (E,), "normal", c.get("expert_bias_std", 0.0)),
        (("moe", "latent_in", "kernel"), b + 10, (D, Lt), "normal", into(D)),
        (("moe", "latent_out", "kernel"), b + 11, (Lt, D), "normal", out(Lt)),
        (("moe", "shared", "up", "kernel"), b + 12, (D, Fs), "normal", into(D)),
        (("moe", "shared", "down", "kernel"), b + 13, (Fs, D), "normal", out(Fs)),
    ]


def _leaf(key, leaf_id, shape, kind_: str, std: float):
    if kind_ == "a_log":
        return jnp.log(1.0 + jnp.arange(shape[0]) % 16).astype(jnp.float32)
    if kind_ == "dt_bias":
        step = jnp.exp(jnp.linspace(math.log(0.001), math.log(0.1), shape[0]))
        return step + jnp.log(-jnp.expm1(-step))
    return weights.make_leaf(key, leaf_id, shape, kind_, std)


def build(spec: list, key, layer=None, dtype=jnp.bfloat16) -> dict:
    """The nested dict of ``spec``'s leaves, each rounded to ``dtype`` (the
    served type); ``layer`` (it may be traced) offsets the ids."""
    tree_: dict = {}
    for path, leaf_id, shape, kind_, std in spec:
        if layer is not None:
            leaf_id = leaf_id + _LAYER_STRIDE * layer
        node = tree_
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = _leaf(key, leaf_id, shape, kind_, std).astype(dtype)
    return tree_


def expert(c: dict, key, layer, e, dtype=jnp.bfloat16) -> dict:
    """Routed expert ``e`` (its id in the MODEL) of PUBLISHED layer ``layer``
    (either may be traced): ``up [latent, F]``, ``down [F, latent]``."""
    Lt, F = c["moe_latent_size"], c["moe_intermediate_size"]
    base = _EXPERT_BASE + 2 * (c["n_routed_experts"] * layer + e)
    leaf = lambda j, shape, std: weights.make_leaf(
        key, base + j, shape, "normal", std).astype(dtype)
    return {"up": leaf(0, (Lt, F), 1 / math.sqrt(Lt)),
            "down": leaf(1, (F, Lt), c.get("expert_down_factor", 1.0) / math.sqrt(F))}


def tree(c: dict, key, dtype=jnp.bfloat16) -> dict:
    """The whole parameter tree of the share in the served type, named as the
    program names it (a layer by its published index), the held experts
    stacked (trace it under one jit)."""
    out = build(top_spec(c), key, dtype=dtype)
    first, n = held_experts(c)
    for i in held_layers(c):
        layer_ = build(layer_spec(c, i), key, layer=i, dtype=dtype)
        if kind(c, i) == EXPERTS:
            layer_["moe"].update(jax.vmap(lambda e: expert(c, key, i, e, dtype))(
                first + jnp.arange(n)))
        out[f"layer_{i}"] = layer_
    return out


def _f32(tree_):
    return jax.tree.map(lambda x: x.astype(jnp.float32), tree_)


# -- the layers ----------------------------------------------------------------


def _rmsnorm(p, x, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * p["scale"]


def _blocks(L: int, want: int) -> tuple[int, int]:
    """``(block, count)`` with ``block x count >= L`` and ``block <= want``."""
    n = -(-L // want)
    return -(-L // n), n


def _by_position_blocks(fn, carry, h, want: int):
    """``fn(carry, block [B, b, D]) -> (carry, out [B, b, D])`` over ``h [B,
    L, D]`` a block of positions at a time, in order (the last one padded
    with zeros; causal, so padding after the end is inert)."""
    B, L, D = h.shape
    b, n = _blocks(L, want)
    padded = jnp.pad(h, ((0, 0), (0, b * n - L), (0, 0)))
    carry, out = jax.lax.scan(
        fn, carry, jnp.moveaxis(padded.reshape(B, n, b, D), 1, 0))
    return jnp.moveaxis(out, 0, 1).reshape(B, b * n, -1)[:, :L]


def mamba_mixer(c: dict, p, u, mode: str):
    """u ``[B, L, D]`` float32, normed, every sequence from the zero state."""
    B = u.shape[0]
    H, P, N, G = (c["mamba_num_heads"], c["mamba_head_dim"], c["ssm_state_size"],
                  c["n_groups"])
    Di, Cd, K = d_inner(c), conv_dim(c), c["conv_kernel"]
    a = -jnp.exp(p["A_log"])  # [H]
    mm = lambda x, name: precision.matmul(x, p[name]["kernel"], mode)

    def block(carry, ub):
        tail, s = carry  # [B, K - 1, Cd], [B, H, P, N]
        b_len = ub.shape[1]
        z, xbc, dt = jnp.split(mm(ub, "in_proj"), [Di, Di + Cd], axis=-1)
        window = jnp.concatenate([tail, xbc], axis=1)
        xbc = jax.nn.silu(p["conv"]["bias"] + sum(
            p["conv"]["kernel"][k] * window[:, k:k + b_len] for k in range(K)))
        x, bm, cm = jnp.split(xbc, [Di, Di + G * N], axis=-1)
        x = x.reshape(B, b_len, H, P)
        # Head h reads group h // (H / G)'s B and C.
        bm = jnp.repeat(bm.reshape(B, b_len, G, N), H // G, axis=2)
        cm = jnp.repeat(cm.reshape(B, b_len, G, N), H // G, axis=2)
        dt = jax.nn.softplus(dt + p["dt_bias"])  # [B, b, H]

        def step(s, inp):
            xt, dtt, bt, ct = inp  # [B, H, P], [B, H], [B, H, N], [B, H, N]
            s = (jnp.exp(dtt * a)[..., None, None] * s
                 + (dtt[..., None] * xt)[..., None] * bt[:, :, None, :])
            return s, jnp.sum(s * ct[:, :, None, :], axis=-1) + p["D"][:, None] * xt

        time_major = lambda v: jnp.moveaxis(v, 1, 0)
        s, y = jax.lax.scan(step, s, tuple(time_major(v) for v in (x, dt, bm, cm)))
        y = jnp.moveaxis(y, 0, 1).reshape(B, b_len, Di) * jax.nn.silu(z)
        y = y.reshape(B, b_len, G, Di // G)
        y = y / jnp.sqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True)
                         + c["layer_norm_epsilon"])
        y = y.reshape(B, b_len, Di) * p["norm"]["scale"]
        return (window[:, b_len:], s), mm(y, "out_proj")

    carry = (jnp.zeros((B, K - 1, Cd), jnp.float32), jnp.zeros((B, H, P, N), jnp.float32))
    return _by_position_blocks(block, carry, u, POSITION_BLOCK)


def attention(c: dict, p, h, mode: str):
    """h ``[B, L, D]`` float32, normed -> ``[B, L, D]``."""
    B, L, _ = h.shape
    A, KV, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    mm = lambda x, name: precision.matmul(x, p[name]["kernel"], mode)
    # Query head g of K/V head k is head k (A / KV) + g: it reads K/V head k.
    q = mm(h, "q").reshape(B, L, KV, A // KV, hd)
    k = mm(h, "k").reshape(B, L, KV, hd)
    v = mm(h, "v").reshape(B, L, KV, hd)
    qb = min(QUERY_BLOCK, L)
    starts = jnp.arange(0, L, qb)

    def block(start):
        # The last block is read shifted back inside the sequence; its rows
        # are put where they belong below.
        start = jnp.minimum(start, L - qb)
        qs = jax.lax.dynamic_slice_in_dim(q, start, qb, axis=1)
        s = jnp.einsum("bqkgd,btkd->bkgqt", qs, k, precision=HIGHEST) / math.sqrt(hd)
        seen = (start + jnp.arange(qb))[:, None] >= jnp.arange(L)[None, :]
        w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("bkgqt,btkd->bqkgd", w, v, precision=HIGHEST)

    o = jax.lax.map(block, starts)  # [n, B, qb, KV, A / KV, hd]
    rows = jnp.minimum(starts, L - qb)[:, None] + jnp.arange(qb)[None, :]
    out = jnp.zeros((B, L, A, hd), jnp.float32).at[:, rows.reshape(-1)].set(
        jnp.moveaxis(o, 0, 1).reshape(B, -1, A, hd))
    return mm(out.reshape(B, L, A * hd), "o")


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


def route(c: dict, p, u):
    """``(choice [.., k] expert ids, weights [.., k])``, in float32 whatever
    the mode: the bias picks, the scores weigh."""
    s = jax.nn.sigmoid(jnp.matmul(u, p["router"]["kernel"], precision=HIGHEST))
    _, choice = jax.lax.top_k(s + p["router"]["bias"], c["num_experts_per_tok"])
    w = jnp.take_along_axis(s, choice, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + NORMALISE_EPS)
    return choice, c["routed_scaling_factor"] * w


def latent_moe(c: dict, p, expert_fn, u, mode: str):
    """u ``[.., D]`` normed -> the share's part of the routed sum, back
    through ``latent_out``, plus the shared expert.  ``expert_fn(e)`` gives
    expert ``e``'s float32 matrices."""
    first, n = held_experts(c)
    choice, w = route(c, p, u)
    latent = precision.matmul(u, p["latent_in"]["kernel"], mode)

    def one(j, m):
        e = first + j
        w_e = jnp.sum(jnp.where(choice == e, w, 0.0), axis=-1, keepdims=True)
        pe = expert_fn(e)
        y = precision.matmul(_relu2(precision.matmul(latent, pe["up"], mode)), pe["down"], mode)
        return m + w_e * y

    m = jax.lax.fori_loop(0, n, one, jnp.zeros_like(latent))
    routed = precision.matmul(m, p["latent_out"]["kernel"], mode)
    shared = precision.matmul(
        _relu2(precision.matmul(u, p["shared"]["up"]["kernel"], mode)),
        p["shared"]["down"]["kernel"], mode)
    return routed + shared


def layer(c: dict, kind_: str, p, expert_fn, x, mode: str):
    u = _rmsnorm(p["norm"], x, c["layer_norm_epsilon"])
    if kind_ == MAMBA:
        return x + mamba_mixer(c, p["mamba"], u, mode)
    if kind_ == ATTENTION:
        return x + attention(c, p["attn"], u, mode)
    part = lambda _, ub: (None, latent_moe(c, p["moe"], expert_fn, ub, mode))
    return x + _by_position_blocks(part, None, u, POSITION_BLOCK)


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

    def one(kind_):
        # A layer's kind is static, its published index traced (it offsets
        # the leaf ids): one program a KIND, three compilations a sequence
        # length and not one a layer.
        spec = kind_spec(c, kind_)
        return jax.jit(lambda key, i, h: layer(
            c, kind_, _f32(build(spec, key, layer=i)),
            lambda e: _f32(expert(c, key, i, e)), h, mode))

    @jax.jit
    def head(key, h_rows):
        top = _f32(build(top_spec(c)[1:], key))
        y = _rmsnorm(top["norm_f"], h_rows, c["layer_norm_epsilon"])
        return precision.matmul(y, top["head"]["kernel"], mode)

    return embed, {k: one(k) for k in {kind(c, i) for i in held_layers(c)}}, head


def _hidden(c: dict, seed: int, tokens, mode: str):
    embed, one, head = _programs(_hashable(c), mode)
    key = weights.base_key(seed)
    h = embed(key, jnp.asarray(tokens, jnp.int32))
    for i in held_layers(c):
        h = one[kind(c, i)](key, jnp.int32(i), h)
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
