"""Plain float32 reference of ResNet-50 training (He et al. 2015, Table 1).

Bottlenecks 3-4-6-3 at width 64, the literal 7x7/2 stem, batch norm over
the whole batch, softmax cross-entropy plus L2 on every kernel, SGD with
momentum.  Departures it shares with the cell: the stride sits on the 3x3
(v1.5) and convolutions pad as TensorFlow's SAME.  Each bottleneck is
rematerialised so that a float32 batch of 256 fits one chip; that changes
no number.  It imports nothing of the program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import precision, weights

HIGHEST = precision.HIGHEST


def _conv(x, k, stride: int, mode: str):
    x, k = precision.operands(x, k, mode)
    return jax.lax.conv_general_dilated(
        x, k, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST,
    )


def _bn(p, x, eps=1e-5):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _bottleneck(p, x, stride: int, mode: str):
    y = jax.nn.relu(_bn(p["bn1"], _conv(x, p["conv1"]["kernel"], 1, mode)))
    y = jax.nn.relu(_bn(p["bn2"], _conv(y, p["conv2"]["kernel"], stride, mode)))
    y = _bn(p["bn3"], _conv(y, p["conv3"]["kernel"], 1, mode))
    if "proj" in p:
        x = _bn(p["bn_proj"], _conv(x, p["proj"]["kernel"], stride, mode))
    return jax.nn.relu(y + x)


def loss(c: dict, params, images, labels, *, l2: float, mode: str = "float32"):
    y = _conv(images, params["stem"]["kernel"], 2, mode)
    y = jax.nn.relu(_bn(params["bn_stem"], y))
    y = jax.lax.reduce_window(
        y, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
        ((0, 0), (1, 1), (1, 1), (0, 0)),
    )
    for key, _cin, _mid, stride, _proj in weights.resnet_blocks(c):
        f = functools.partial(_bottleneck, stride=stride, mode=mode)
        y = jax.checkpoint(f)(params[key], y)
    y = jnp.mean(y, axis=(1, 2))
    logits = precision.matmul(y, params["head"]["kernel"], mode) + params["head"]["bias"]
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    ce = jnp.mean(logz - gold)
    kernels = [
        leaf for path, leaf in jax.tree_util.tree_leaves_with_path(params)
        if path[-1].key == "kernel"
    ]
    return ce + l2 * sum(jnp.sum(jnp.square(k)) for k in kernels)


@functools.lru_cache(maxsize=None)
def _step_program(c_items: tuple, lr: float, momentum: float, l2: float, mode: str):
    c = dict(c_items)
    c["stage_sizes"] = tuple(c["stage_sizes"])

    @jax.jit
    def init(key):
        return weights.resnet_trees(c, key)[0]

    @jax.jit
    def step(params, trace, images, labels):
        value, grads = jax.value_and_grad(
            lambda p: loss(c, p, images, labels, l2=l2, mode=mode)
        )(params)
        trace = jax.tree.map(lambda t, g: momentum * t + g, trace, grads)
        params = jax.tree.map(lambda p, t: p - lr * t, params, trace)
        return params, trace, value, grads

    return init, step


def train(c: dict, opt: dict, seed: int, batches: list, mode: str = "float32") -> dict:
    """Follow ``batches`` (``(images, labels)`` numpy pairs) from the seeded
    weights.  Returns each step's loss, the first gradient (and its norm
    per leaf) and the parameters' change per leaf after the last step."""
    cc = {k: (tuple(v) if isinstance(v, list) else v) for k, v in c.items()}
    init, step = _step_program(
        tuple(sorted(cc.items())), float(opt["learning_rate"]),
        float(opt["momentum"]), float(opt["l2"]), mode,
    )
    params0 = init(weights.base_key(seed))
    params = params0
    trace = jax.tree.map(jnp.zeros_like, params)
    losses, first_grads = [], None
    for images, labels in batches:
        params, trace, value, grads = step(
            params, trace, jnp.asarray(images), jnp.asarray(labels, jnp.int32)
        )
        losses.append(float(value))
        if first_grads is None:
            first_grads = grads
        del grads
    return {
        "losses": losses,
        "first_grads": first_grads,
        "grad_norms": weights.leaf_norms(first_grads),
        "delta_norms": weights.leaf_norms(jax.tree.map(jnp.subtract, params, params0)),
    }
