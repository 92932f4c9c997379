"""Functional layer library: init/apply pairs over plain dict pytrees.

The building blocks the reference gets from TF ops/Keras (dense, conv2d,
batch-norm, LSTM cell, embedding — SURVEY.md section 1 L4) rebuilt as pure
functions.  Compute-dtype policy: params live in float32; ``apply`` functions
accept a ``dtype`` to run activations/matmuls in bfloat16 on the MXU while
accumulating in float32 (``preferred_element_type``).  ``dense`` without a
``dtype`` multiplies what it is given and returns float32: handed bfloat16
activations and bfloat16 kernels (a model served in the type it is
published in, models/jamba.py) that is the MXU's native product.
"""

from __future__ import annotations

import math
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


# ----------------------------------------------------------------------------
# Initializers (TF analogs: glorot_uniform, he_normal, truncated_normal)
# ----------------------------------------------------------------------------


def glorot_uniform(rng, shape, in_axis=-2, out_axis=-1, dtype=jnp.float32):
    fan_in, fan_out = shape[in_axis], shape[out_axis]
    limit = jnp.sqrt(6.0 / (fan_in + fan_out))
    return jax.random.uniform(rng, shape, dtype, -limit, limit)


def he_normal_conv(rng, shape, dtype=jnp.float32):
    """He init for HWIO conv kernels (fan_in = h*w*cin)."""
    fan_in = shape[0] * shape[1] * shape[2]
    std = jnp.sqrt(2.0 / fan_in)
    return std * jax.random.normal(rng, shape, dtype)


def he_normal(rng, shape, in_axis=-2, dtype=jnp.float32):
    """He (fan-in) init for dense kernels — the relu-correct scale
    (glorot averages fan_in/fan_out and under-scales a relu stack by
    sqrt(2), which compounds per layer)."""
    std = jnp.sqrt(2.0 / shape[in_axis])
    return std * jax.random.normal(rng, shape, dtype)


def uniform_embedding(rng, shape, scale=None, dtype=jnp.float32):
    """word2vec-style U[-1/dim, 1/dim] embedding init."""
    scale = scale if scale is not None else 1.0 / shape[-1]
    return jax.random.uniform(rng, shape, dtype, -scale, scale)


# ----------------------------------------------------------------------------
# Dense
# ----------------------------------------------------------------------------


def dense_init(
    rng, in_dim: int, out_dim: int, *, use_bias: bool = True,
    init: str = "glorot",
):
    """``init``: "glorot" (the default every linear/softmax layer keeps)
    or "he" (fan-in — the relu-correct scale for hidden layers)."""
    kr, _ = jax.random.split(rng)
    if init == "he":
        kernel = he_normal(kr, (in_dim, out_dim))
    elif init == "glorot":
        kernel = glorot_uniform(kr, (in_dim, out_dim))
    else:
        raise ValueError(f"unknown dense init {init!r}")
    p = {"kernel": kernel}
    if use_bias:
        p["bias"] = jnp.zeros((out_dim,), jnp.float32)
    return p


def dense(params, x, *, dtype=None):
    k = params["kernel"]
    if dtype is not None:
        # Pure compute-dtype matmul: on TPU the MXU accumulates bf16 inputs
        # in f32 internally; keeping in/out dtypes uniform keeps the autodiff
        # transpose well-typed (mixed bf16/f32 transposes are rejected).
        x, k = x.astype(dtype), k.astype(dtype)
        y = jnp.matmul(x, k)
        if "bias" in params:
            y = y + params["bias"].astype(dtype)
        return y
    y = jnp.matmul(x, k, preferred_element_type=jnp.float32)
    if "bias" in params:
        y = y + params["bias"]
    return y


# ----------------------------------------------------------------------------
# RMSNorm, gated SiLU feed-forward (the block of today's open models)
# ----------------------------------------------------------------------------


def rmsnorm_init(d: int, dtype=jnp.float32):
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(params, x, eps: float = 1e-6):
    """``x / rms(x) * scale`` over the last axis, in float32 whatever comes
    in (the mean of squares is what a low precision loses first); returns
    float32.  Over ``[.., heads, head_dim]`` with a scale of ``head_dim`` it
    is QK-norm: each head's query or key over its own width
    (models/afmoe.py)."""
    x = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(ms + eps) * params["scale"].astype(jnp.float32)


def gated_mlp_init(rng, dim: int, hidden: int, *, std: float = 0.02,
                   out_std: float | None = None, dtype=jnp.float32):
    """``down(silu(gate(x)) * up(x))``, no biases.  ``out_std`` is the scale
    of the projection back into the residual stream."""
    kg, ku, kd = jax.random.split(rng, 3)
    normal = lambda k, shape, s: (s * jax.random.normal(k, shape)).astype(dtype)
    return {
        "gate": {"kernel": normal(kg, (dim, hidden), std)},
        "up": {"kernel": normal(ku, (dim, hidden), std)},
        "down": {"kernel": normal(kd, (hidden, dim), std if out_std is None else out_std)},
    }


def gated_mlp(params, x, *, dtype):
    """Products in ``dtype`` accumulated in float32; the gate's SiLU and
    its product with ``up`` in float32; returns float32."""
    x = x.astype(dtype)
    g = dense(params["gate"], x)
    u = dense(params["up"], x)
    return dense(params["down"], (jax.nn.silu(g) * u).astype(dtype))


# ----------------------------------------------------------------------------
# Rotary positions
# ----------------------------------------------------------------------------


def rope_frequencies(dim: int, theta: float):
    """``[dim // 2]`` float32: pair ``i`` of a ``dim``-wide vector turns by
    ``theta ** (-2 i / dim)`` a position.  No scaling of long contexts."""
    return jnp.exp(-jnp.log(theta) * jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)


def yarn_mscale(factor: float, a: float) -> float:
    """``m(a) = 0.1 a ln(factor) + 1`` (1 where nothing is stretched): what
    YaRN multiplies attention's logits by, once from the query's side and
    once from the key's."""
    return 1.0 if factor <= 1 else 0.1 * a * math.log(factor) + 1.0


def yarn_correction_range(dim: int, theta: float, original_max: int,
                          beta_fast: float, beta_slow: float) -> tuple[int, int]:
    """``(low, high)``: the pairs that turn ``beta_fast`` / ``beta_slow``
    times over ``original_max`` positions, rounded outwards and clipped to
    the vector."""
    corr = lambda r: dim * math.log(original_max / (2 * math.pi * r)) / (2 * math.log(theta))
    return max(math.floor(corr(beta_fast)), 0), min(math.ceil(corr(beta_slow)), dim - 1)


def yarn_frequencies(dim: int, theta: float, factor: float, original_max: int,
                     beta_fast: float, beta_slow: float):
    """:func:`rope_frequencies` scaled for contexts ``factor`` times the
    ``original_max`` positions trained on (YaRN, as DeepSeek-V2 publishes
    it): pairs up to ``low`` keep their frequency (they turn often enough
    over the original context to be told apart at any length), pairs from
    ``high`` on turn ``factor`` times slower (plain interpolation), those
    between are blended linearly."""
    f = rope_frequencies(dim, theta)
    low, high = yarn_correction_range(dim, theta, original_max, beta_fast, beta_slow)
    ramp = jnp.clip(
        (jnp.arange(dim // 2, dtype=jnp.float32) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return f * (1.0 - ramp) + (f / factor) * ramp


def rope_angles_at(pos, inv_freq):
    """``(cos, sin)``, each ``pos.shape + inv_freq.shape`` float32, of the
    angles ``pos x inv_freq``."""
    a = jnp.asarray(pos, jnp.float32)[..., None] * inv_freq
    return jnp.cos(a), jnp.sin(a)


def rope_angles(pos, dim: int, theta: float):
    """:func:`rope_angles_at` the unscaled frequencies of ``(dim, theta)``."""
    return rope_angles_at(pos, rope_frequencies(dim, theta))


def rope_interleaved(x, cos, sin):
    """Rotate ``x [..., dim]`` in float32 with the pairs INTERLEAVED - pair
    ``i`` is elements ``(2 i, 2 i + 1)``, as LongCat's and DeepSeek's
    checkpoints store them, not ``(i, i + dim / 2)`` as GPT-NeoX's do.
    ``cos``, ``sin`` broadcast against ``x[..., 0::2]``."""
    x = x.astype(jnp.float32)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1).reshape(x.shape)


# ----------------------------------------------------------------------------
# Conv2D (NHWC x HWIO -> NHWC; the MXU-friendly layout)
# ----------------------------------------------------------------------------


def conv_init(rng, kh: int, kw: int, cin: int, cout: int, *, use_bias: bool = True):
    p = {"kernel": he_normal_conv(rng, (kh, kw, cin, cout))}
    if use_bias:
        p["bias"] = jnp.zeros((cout,), jnp.float32)
    return p


def conv2d(params, x, *, stride=1, padding="SAME", dtype=None):
    k = params["kernel"]
    if dtype is not None:
        x, k = x.astype(dtype), k.astype(dtype)
    strides = (stride, stride) if isinstance(stride, int) else stride
    y = lax.conv_general_dilated(
        x,
        k,
        window_strides=strides,
        padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        # Uniform in/out dtype (see dense): MXU accumulation is f32 either
        # way; mixed-dtype conv transposes fail under autodiff.
        preferred_element_type=None if dtype is not None else jnp.float32,
    )
    if "bias" in params:
        b = params["bias"]
        y = y + (b.astype(dtype) if dtype is not None else b)
    return y


# ----------------------------------------------------------------------------
# BatchNorm (params + mutable running stats threaded through model_state)
# ----------------------------------------------------------------------------


def batchnorm_init(c: int, *, ghost_slices: int = 0):
    """``ghost_slices > 0``: running stats carry a leading per-slice dim
    [S, C] (sharded P('slice', None) by the model's rules) so their EMA
    update never crosses the slice boundary — see batchnorm's ghost path."""
    params = {"scale": jnp.ones((c,), jnp.float32), "bias": jnp.zeros((c,), jnp.float32)}
    shape = (ghost_slices, c) if ghost_slices > 0 else (c,)
    stats = {"mean": jnp.zeros(shape, jnp.float32), "var": jnp.ones(shape, jnp.float32)}
    return params, stats


def _batchnorm_ghost(
    params, stats, x, *, momentum, eps, mesh, relu, ghost_slices: int
):
    """Ghost-batch (slice-local) BN statistics for multi-slice meshes.

    Full SyncBN reduces batch statistics over the WHOLE data axis — on a
    multi-slice deployment that is 2 tiny all-reduces per BN layer
    CROSSING DCN (98 per ResNet-50 step).  Here the batch dim is reshaped
    [B] -> [S, B/S] with S pinned to the mesh's outermost ('slice') axis,
    so the statistics reduce runs only over the slice-LOCAL sub-axis of data
    (rides ICI) and each slice normalises with its own "ghost batch"
    (batch/S) statistics — the standard mitigation, with the standard
    statistics change (normalisation noise of a batch/S batch; quantified
    in tests/test_models.py).  Running stats stay per-slice [S, C]
    (sharded P('slice', None)) so the EMA update is collective-free;
    evaluation averages them once.  Result: NO BatchNorm traffic ever
    touches DCN — only the gradient all-reduce crosses."""
    S = ghost_slices
    B = x.shape[0]
    if B % S:
        raise ValueError(f"ghost BN: batch {B} not divisible by {S} slices")
    spec_x = P("slice", "data", *([None] * (x.ndim - 1)))

    def pin(t, spec):
        if mesh is None:
            return t
        return jax.lax.with_sharding_constraint(
            t, jax.sharding.NamedSharding(mesh, spec)
        )

    xr = pin(x.reshape(S, B // S, *x.shape[1:]), spec_x)
    xf = xr.astype(jnp.float32)
    axes = tuple(range(1, xr.ndim - 1))  # slice-local batch + spatial
    mean = pin(jnp.mean(xf, axis=axes), P("slice", None))  # [S, C]
    mean_sq = pin(jnp.mean(jnp.square(xf), axis=axes), P("slice", None))
    var = jnp.maximum(mean_sq - jnp.square(mean), 0.0)
    new_stats = {
        "mean": momentum * stats["mean"] + (1 - momentum) * mean,
        "var": momentum * stats["var"] + (1 - momentum) * var,
    }
    bshape = (S,) + (1,) * (x.ndim - 1) + (-1,)
    inv = lax.rsqrt(var + eps) * params["scale"]
    y = (xr - mean.reshape(bshape).astype(x.dtype)) * inv.reshape(bshape).astype(
        x.dtype
    ) + params["bias"].astype(x.dtype)
    if relu:
        y = jax.nn.relu(y)
    return pin(y.reshape(x.shape), P(("slice", "data"), *([None] * (x.ndim - 1)))), new_stats


def batchnorm(
    params, stats, x, *, train: bool, momentum=0.9, eps=1e-5, mesh=None,
    relu: bool = False, ghost_slices: int = 0,
):
    """Returns (y, new_stats).  In train mode the batch statistics are
    computed over the *global* batch: under jit with the batch sharded on the
    data axis, the mean/var reductions become cross-replica (XLA inserts the
    all-reduce) — matching SyncBatchNorm semantics, which is what mirrored
    data-parallel training wants.

    ``mesh``: used by the ghost-batch path alone, which pins its shardings
    with it.

    ``relu``: apply ReLU to the output inside this layer; identical to
    relu(batchnorm(x))."""
    if train and ghost_slices > 0:
        return _batchnorm_ghost(
            params, stats, x, momentum=momentum, eps=eps, mesh=mesh,
            relu=relu, ghost_slices=ghost_slices,
        )
    if train:
        axes = tuple(range(x.ndim - 1))
        # One-pass stats: E[x] and E[x^2] share a single read of the
        # activation (XLA fuses sibling reductions), where mean+var is two
        # passes — measured ~15% of the ResNet-50 fwd step on v5e.
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=axes)
        mean_sq = jnp.mean(jnp.square(xf), axis=axes)
        # Clamp: f32 cancellation can push E[x^2]-E[x]^2 slightly negative
        # for near-constant channels, and rsqrt(var+eps) would NaN.
        var = jnp.maximum(mean_sq - jnp.square(mean), 0.0)
        new_stats = {
            "mean": momentum * stats["mean"] + (1 - momentum) * mean,
            "var": momentum * stats["var"] + (1 - momentum) * var,
        }
    else:
        mean, var = stats["mean"], stats["var"]
        if mean.ndim == 2:
            # Ghost-trained stats [S, C]: evaluation recovers the exact
            # GLOBAL moments by the law of total variance — mean of the
            # within-slice variances PLUS the variance of the slice means
            # (averaging the variances alone systematically undershoots
            # when slices are not iid).  This is the one cross-slice
            # reduction, paid at EVAL, not per step.
            gmean = jnp.mean(mean, axis=0)
            var = jnp.mean(var, axis=0) + jnp.mean(
                jnp.square(mean - gmean), axis=0
            )
            mean = gmean
        new_stats = stats
    inv = lax.rsqrt(var + eps) * params["scale"]
    y = (x - mean.astype(x.dtype)) * inv.astype(x.dtype) + params["bias"].astype(x.dtype)
    if relu:
        y = jax.nn.relu(y)
    return y, new_stats


# ----------------------------------------------------------------------------
# Embedding
# ----------------------------------------------------------------------------


def embedding_init(rng, vocab: int, dim: int):
    return {"table": uniform_embedding(rng, (vocab, dim))}


def embedding_lookup(params, ids, *, dtype=None):
    """Gather rows.  When the table is sharded over the ``model`` mesh axis
    (rule: ``("embedding/table", P("model", None))``), XLA turns this into a
    per-shard gather + collective — the in-compiler equivalent of the
    reference's cross-network PS-shard gather (SURVEY.md section 3.5).

    The rows are gathered in the table's own type and THEN cast to ``dtype``:
    a convert is element-wise, so the values are the same to the bit, but XLA
    does not move a convert through a gather - cast first and every launch
    converts the whole ``[vocab, dim]`` table to pick ``ids.size`` rows of it
    (0.92 ms of a 9.2 ms decode launch at 50,304 x 2,048 float32 -> bfloat16).
    Backward, the table's gradient is scattered in the table's type."""
    rows = jnp.take(params["table"], ids, axis=0)
    return rows if dtype is None else rows.astype(dtype)


# ----------------------------------------------------------------------------
# LSTM cell (the legacy_rnn BasicLSTMCell analog, scan-ready)
# ----------------------------------------------------------------------------


def lstm_cell_init(rng, in_dim: int, hidden: int):
    kr, _ = jax.random.split(rng)
    return {
        "kernel": glorot_uniform(kr, (in_dim + hidden, 4 * hidden)),
        "bias": jnp.zeros((4 * hidden,), jnp.float32),
    }


def lstm_cell(params, carry, x, *, forget_bias=1.0, dtype=None):
    """One LSTM step: carry = (c, h).  Gate order i, g, f, o.  Designed to be
    the body of ``lax.scan`` over time (compiler-friendly control flow — no
    Python loops inside jit)."""
    c, h = carry
    k = params["kernel"]
    if dtype is not None:
        x, h, k = x.astype(dtype), h.astype(dtype), k.astype(dtype)
        z = jnp.matmul(jnp.concatenate([x, h], axis=-1), k)
        z = (z + params["bias"].astype(dtype)).astype(jnp.float32)
    else:
        z = jnp.matmul(
            jnp.concatenate([x, h], axis=-1), k, preferred_element_type=jnp.float32
        )
        z = z + params["bias"]
    i, g, f, o = jnp.split(z, 4, axis=-1)
    new_c = jax.nn.sigmoid(f + forget_bias) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
    new_h = jax.nn.sigmoid(o) * jnp.tanh(new_c)
    return (new_c, new_h), new_h


# ----------------------------------------------------------------------------
# Losses / metrics
# ----------------------------------------------------------------------------


def softmax_cross_entropy(logits, labels, num_classes=None):
    """Mean cross-entropy over the batch (global mean under jit+sharding —
    this mean is what makes data-parallel gradient averaging automatic)."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None].astype(jnp.int32), axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def accuracy(logits, labels):
    return jnp.mean((jnp.argmax(logits, axis=-1) == labels).astype(jnp.float32))
