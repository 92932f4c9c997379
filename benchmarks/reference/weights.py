"""Seeded weights, leaf by leaf.

Every leaf's values depend only on ``(seed, leaf id)``, so the harness can
make the whole tree on the device in one jitted call for the program, and
the reference can make one layer at a time without ever touching what the
program holds.  The seed enters as a traced key: one compiled program
serves every seed.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def base_key(seed: int):
    """A key from any non-negative ``--seed``, beyond 31 bits too."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def make_leaf(key, leaf_id, shape, kind: str, std: float):
    if kind == "ones":
        return jnp.ones(shape, jnp.float32)
    if kind == "zeros":
        return jnp.zeros(shape, jnp.float32)
    k = jax.random.fold_in(key, leaf_id)
    return std * jax.random.normal(k, shape, jnp.float32)


def build(spec: list, key, layer=None) -> dict:
    """``spec`` rows are ``(path, leaf_id, shape, kind, std)``; returns the
    nested dict.  With ``layer`` given (it may be traced), ids are offset
    by ``16 * layer``: one compiled builder serves every layer."""
    tree: dict = {}
    for path, leaf_id, shape, kind, std in spec:
        if layer is not None:
            leaf_id = leaf_id + 16 * layer
        node = tree
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = make_leaf(key, leaf_id, shape, kind, std)
    return tree


def leaf_norms(tree) -> dict:
    """``{"a/b/c": l2 norm}`` of every leaf, computed on the device."""
    norms = jax.jit(lambda t: jax.tree.map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), t))(tree)
    return {
        "/".join(str(getattr(p, "key", p)) for p in path): float(v)
        for path, v in jax.tree_util.tree_leaves_with_path(norms)
    }


# -- transformer (the program's models/transformer.py tree) ------------------

_BLOCK_BASE = 1000


def transformer_top_spec(c: dict) -> list:
    V, D, T = c["vocab_size"], c["dim"], c["max_seq_len"]
    return [
        (("emb", "table"), 0, (V, D), "normal", 0.02),
        (("pos", "table"), 1, (T, D), "normal", 0.01),
        (("ln_f", "scale"), 2, (D,), "ones", 0.0),
        (("ln_f", "bias"), 3, (D,), "zeros", 0.0),
        (("head", "kernel"), 4, (D, V), "normal", 0.02),
    ]


def transformer_block_spec(c: dict) -> list:
    """Ids for layer 0; layer ``i`` adds ``16 * i``."""
    D, H = c["dim"], c["dim"] * c["mlp_ratio"]
    res = 0.02 / (2 * c["n_layers"]) ** 0.5
    b = _BLOCK_BASE
    return [
        (("ln1", "scale"), b + 0, (D,), "ones", 0.0),
        (("ln1", "bias"), b + 1, (D,), "zeros", 0.0),
        (("qkv", "kernel"), b + 2, (D, 3 * D), "normal", 0.02),
        (("proj", "kernel"), b + 3, (D, D), "normal", res),
        (("ln2", "scale"), b + 4, (D,), "ones", 0.0),
        (("ln2", "bias"), b + 5, (D,), "zeros", 0.0),
        (("mlp_in", "kernel"), b + 6, (D, H), "normal", 0.02),
        (("mlp_in", "bias"), b + 7, (H,), "zeros", 0.0),
        (("mlp_out", "kernel"), b + 8, (H, D), "normal", res),
        (("mlp_out", "bias"), b + 9, (D,), "zeros", 0.0),
    ]


def transformer_tree(c: dict, key) -> dict:
    """The whole parameter tree (trace it under one jit)."""
    tree = build(transformer_top_spec(c), key)
    block = transformer_block_spec(c)
    for i in range(c["n_layers"]):
        tree[f"block_{i}"] = build(block, key, layer=i)
    return tree


# -- resnet (the program's models/resnet.py trees) ---------------------------


def _conv(path, leaf_id, kh, kw, cin, cout):
    std = (2.0 / (kh * kw * cin)) ** 0.5
    return (path + ("kernel",), leaf_id, (kh, kw, cin, cout), "normal", std)


def _bn(path, c):
    return [
        (path + ("scale",), 0, (c,), "ones", 0.0),
        (path + ("bias",), 0, (c,), "zeros", 0.0),
    ]


def resnet_blocks(c: dict) -> list:
    """``(key, cin, mid, stride, has_proj)`` per bottleneck, in order."""
    out, cin = [], c["width"]
    for stage, n in enumerate(c["stage_sizes"]):
        mid = c["width"] * 2 ** stage
        for block in range(n):
            stride = 2 if (stage > 0 and block == 0) else 1
            out.append((f"stage{stage}/block{block}", cin, mid, stride, cin != 4 * mid))
            cin = 4 * mid
    return out


def resnet_spec(c: dict) -> list:
    w = c["width"]
    spec = [_conv(("stem",), 10, 7, 7, 3, w)] + _bn(("bn_stem",), w)
    leaf = 100
    for key, cin, mid, _stride, has_proj in resnet_blocks(c):
        cout = 4 * mid
        spec += [_conv((key, "conv1"), leaf, 1, 1, cin, mid)] + _bn((key, "bn1"), mid)
        spec += [_conv((key, "conv2"), leaf + 1, 3, 3, mid, mid)] + _bn((key, "bn2"), mid)
        spec += [_conv((key, "conv3"), leaf + 2, 1, 1, mid, cout)] + _bn((key, "bn3"), cout)
        if has_proj:
            spec += [_conv((key, "proj"), leaf + 3, 1, 1, cin, cout)]
            spec += _bn((key, "bn_proj"), cout)
        leaf += 4
    cin = 4 * w * 2 ** (len(c["stage_sizes"]) - 1)
    spec += [
        (("head", "kernel"), 11, (cin, c["num_classes"]), "normal", 0.01),
        (("head", "bias"), 0, (c["num_classes"],), "zeros", 0.0),
    ]
    return spec


def resnet_trees(c: dict, key):
    """``(params, model_state)``: batch-norm running mean 0 and variance 1."""
    spec = resnet_spec(c)
    params = build(spec, key)
    state: dict = {}
    for path, _id, shape, _kind, _std in spec:
        if path[-1] == "scale":
            node = state
            for p in path[:-2]:
                node = node.setdefault(p, {})
            node[path[-2]] = {
                "mean": jnp.zeros(shape, jnp.float32),
                "var": jnp.ones(shape, jnp.float32),
            }
    return params, state
