"""Fused BatchNorm(+ReLU) statistics kernels (Pallas TPU) + custom-VJP path.

Why this exists (r3 profile, BASELINE.md): at batch 256 the ResNet-50 step
spent 42% of its device time in BN-adjacent reductions — the forward
E[x]/E[x^2] passes (``convert_reduce`` fusions, 17.8 ms) and the backward
sum(dy)/sum(dy*xhat) passes (``multiply_reduce`` fusions, 23.7 ms) — running
at ~260-440 GB/s against an ~820 GB/s HBM roofline, while the convs
themselves already ran near the MXU roofline.  These kernels do each
direction's statistics in ONE near-bandwidth pass; all elementwise work
(normalise, scale, dx) stays in XLA so it keeps fusing into the adjacent
convolutions exactly as before.

Two design points learned the hard way (first cut was 1.7x SLOWER than the
XLA path it replaced):
- Blocks are 4-D [bn, H, W, C] views of the activation, NOT a reshape to
  [M, C]: the host-level reshape materialised layout copies (+58 ms/step).
- The backward kernel takes the RAW upstream cotangent and recomputes the
  ReLU mask from xhat (mask = xhat*(inv*scale)+bias > 0), so the masked
  gradient dy = do * mask never materialises in HBM — in the XLA path that
  mask application fused into the reduction; a Pallas operand would have
  forced it into its own full-size pass (+29 ms/step).

SyncBN contract (layers.batchnorm): statistics are over the GLOBAL batch —
per-shard partial sums inside ``shard_map``, ``psum`` over the ``data``
axis (the explicit form of the reduction GSPMD inserts for the XLA path;
reference role: MirroredStrategy's synchronized BN, SURVEY.md W3).

Backward math (standard BN, biased variance, matching the E[x^2]-E[x]^2
forward):  xhat = (x - mean) * inv;  dy = do * relu_mask;  s1 = sum(dy);
s2 = sum(dy * xhat);  dbeta = s1;  dgamma = s2;
dx = gamma * inv * (dy - s1/n - xhat * s2/n).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..parallel import collectives
from . import flash_attention
from .common import largest_divisor as _largest_divisor

#: Test hook: force the fused path off-TPU so CPU parity tests exercise the
#: same code (Pallas kernels run interpreted).
FORCE_PALLAS = False

#: Statistics implementation: "pallas" (hand-written reduction kernels) or
#: "matmul" (MXU 1^T.x / block-diag Gram contractions).  STATUS (BASELINE.md
#: r3 measured table): on the r3 XLA stack BOTH lose to the plain
#: XLA path end-to-end on ResNet-50 — Pallas operands force layout-
#: conversion copies and break conv fusion chains; the matmul forms get
#: algebraically simplified back into the same slow reduces.  The module is
#: retained as the measured evidence for that ceiling and for stacks where
#: Pallas operands stop forcing layout copies; nothing in the shipped
#: models threads a mesh into batchnorm by default.
IMPL = "pallas"


def _use_pallas() -> bool:
    """Gate for the fused BN path as a whole (name kept for callers)."""
    return FORCE_PALLAS or not flash_attention.interpret_mode()


def _gram_diag(a2d, b2d, blk: int = 128):
    """sum_m a[m,c]*b[m,c] per channel via BLOCK-DIAGONAL MXU contractions:
    channels split into ``blk``-wide groups, one batched [blk, blk] Gram per
    group, diagonal extracted.  2*M*C*blk FLOPs — the full [C, C] Gram
    (first cut) cost 2*M*C^2, which at C=1024/2048 added ~4.8 TF/step to
    the ResNet bench, ~24 ms of pure waste.  The contraction streams both
    operands once at near-HBM-bandwidth where XLA's reduce emitter measured
    260-440 GB/s."""
    m, c = a2d.shape
    if c <= blk:
        g = jax.lax.dot_general(
            a2d, b2d, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return jnp.diagonal(g)
    # One [blk, blk] Gram per channel-block, via column SLICES: in the
    # tiled C-minor layout each 128-wide channel slice is layout-native,
    # where a batched dot_general with the batch dim in the middle made XLA
    # transpose-copy the whole operand first (measured slower than the full
    # Gram it was meant to fix).
    diags = []
    for i in range(0, c, blk):
        ga = jax.lax.dot_general(
            a2d[:, i : i + blk],
            b2d[:, i : i + blk],
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        diags.append(jnp.diagonal(ga))
    return jnp.concatenate(diags)


def _mm_sums(x2d):
    ones = jnp.ones((1, x2d.shape[0]), x2d.dtype)
    s = jax.lax.dot(ones, x2d, preferred_element_type=jnp.float32)[0]
    return s


def mm_stats(x):
    """Matmul-form statistics: (sum [C], sumsq [C]) f32."""
    c = x.shape[-1]
    x2d = x.reshape(-1, c)
    return _mm_sums(x2d), _gram_diag(x2d, x2d)


def mm_bwd_stats(do, x, mean, inv, scale, bias, *, relu: bool):
    """Matmul-form backward sums: s1 = sum(dy), s2 = sum(dy * xhat), with
    dy = do * relu_mask and s2 folded onto RAW operands:
    s2 = inv * (diag(dy^T x) - mean * s1) — no xhat tensor materialises."""
    c = x.shape[-1]
    do2, x2 = do.reshape(-1, c), x.reshape(-1, c)
    if relu:
        ivs = (inv * scale).astype(x.dtype)
        pre = (x2 - mean.astype(x.dtype)) * ivs + bias.astype(x.dtype)
        do2 = do2 * (pre > 0).astype(do.dtype)
    s1 = _mm_sums(do2)
    s2 = inv * (_gram_diag(do2, x2) - mean * s1)
    return s1, s2


_BLOCK_BYTES = 1 << 20


def _pick_blocks(n: int, h: int, w: int, c: int, itemsize: int):
    """(bn, bh): block [bn, bh, W, C] stays ~<=1 MB — two double-buffered
    bf16 input streams PLUS the kernel's f32 temporaries (xf, xhat,
    products: ~5 block-sized f32 arrays in the backward) must fit the
    16 MB scoped-VMEM budget.  Large images (112^2 x 64 = 1.6 MB each)
    additionally block over H; small ones batch several images per step."""
    per_image = h * w * c * itemsize
    if per_image <= _BLOCK_BYTES:
        return _largest_divisor(n, _BLOCK_BYTES // per_image), h
    return 1, _largest_divisor(h, _BLOCK_BYTES // (w * c * itemsize))


def _row_specs(bn, bh, w, c):
    return pl.BlockSpec((bn, bh, w, c), lambda i, j: (i, j, 0, 0))


def _vec_spec(c):
    return pl.BlockSpec((1, c), lambda i, j: (0, 0))


def _is_first():
    return jnp.logical_and(pl.program_id(0) == 0, pl.program_id(1) == 0)


def _is_last():
    return jnp.logical_and(
        pl.program_id(0) == pl.num_programs(0) - 1,
        pl.program_id(1) == pl.num_programs(1) - 1,
    )


def _stats_kernel(x_ref, s_ref, ss_ref, acc_s, acc_ss):
    @pl.when(_is_first())
    def _init():
        acc_s[:] = jnp.zeros_like(acc_s)
        acc_ss[:] = jnp.zeros_like(acc_ss)

    c = x_ref.shape[-1]
    xf = x_ref[...].astype(jnp.float32).reshape(-1, c)
    acc_s[:] += jnp.sum(xf, axis=0, keepdims=True)
    acc_ss[:] += jnp.sum(xf * xf, axis=0, keepdims=True)

    @pl.when(_is_last())
    def _done():
        s_ref[...] = acc_s[:]
        ss_ref[...] = acc_ss[:]


def bn_stats(x):
    """x [N, H, W, C] -> (sum [1, C] f32, sumsq [1, C] f32), one pass."""
    n, h, w, c = x.shape
    bn, bh = _pick_blocks(n, h, w, c, x.dtype.itemsize)
    return pl.pallas_call(
        _stats_kernel,
        grid=(n // bn, h // bh),
        in_specs=[_row_specs(bn, bh, w, c)],
        out_specs=[_vec_spec(c), _vec_spec(c)],
        out_shape=[
            jax.ShapeDtypeStruct((1, c), jnp.float32),
            jax.ShapeDtypeStruct((1, c), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, c), jnp.float32),
            pltpu.VMEM((1, c), jnp.float32),
        ],
        compiler_params=flash_attention.compiler_params(
            ("arbitrary", "arbitrary")
        ),
        interpret=flash_attention.interpret_mode(),
    )(x)


def bn_bwd_stats(do, x, mean, inv, scale, bias, *, relu: bool):
    """(s1, s2) = (sum(dy), sum(dy*xhat)) with dy = do * relu_mask computed
    in-kernel (relu=True) or dy = do (relu=False); one two-stream pass."""
    n, h, w, c = x.shape
    bn, bh = _pick_blocks(n, h, w, c, x.dtype.itemsize)
    return pl.pallas_call(
        functools.partial(_bwd_stats_kernel, relu=relu),
        grid=(n // bn, h // bh),
        in_specs=[
            _row_specs(bn, bh, w, c),
            _row_specs(bn, bh, w, c),
            _vec_spec(c),
            _vec_spec(c),
            _vec_spec(c),
            _vec_spec(c),
        ],
        out_specs=[_vec_spec(c), _vec_spec(c)],
        out_shape=[
            jax.ShapeDtypeStruct((1, c), jnp.float32),
            jax.ShapeDtypeStruct((1, c), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, c), jnp.float32),
            pltpu.VMEM((1, c), jnp.float32),
        ],
        compiler_params=flash_attention.compiler_params(
            ("arbitrary", "arbitrary")
        ),
        interpret=flash_attention.interpret_mode(),
    )(do, x, mean, inv, scale, bias)


def _bwd_stats_kernel(
    do_ref, x_ref, mean_ref, inv_ref, scale_ref, bias_ref, s1_ref, s2_ref,
    a1, a2, *, relu,
):
    @pl.when(_is_first())
    def _init():
        a1[:] = jnp.zeros_like(a1)
        a2[:] = jnp.zeros_like(a2)

    c = x_ref.shape[-1]
    dof = do_ref[...].astype(jnp.float32).reshape(-1, c)
    xf = x_ref[...].astype(jnp.float32).reshape(-1, c)
    xhat = (xf - mean_ref[...]) * inv_ref[...]
    if relu:
        pre = xhat * scale_ref[...] + bias_ref[...]
        dof = dof * (pre > 0)
    a1[:] += jnp.sum(dof, axis=0, keepdims=True)
    a2[:] += jnp.sum(dof * xhat, axis=0, keepdims=True)

    @pl.when(_is_last())
    def _done():
        s1_ref[...] = a1[:]
        s2_ref[...] = a2[:]


def _shard_stats(fn, mesh, n_sharded, n_rep, **kw):
    """Run a local-partial-sums kernel under shard_map with a psum over the
    'data' axis (SyncBN's cross-replica reduction, made explicit)."""
    spec_x = jax.sharding.PartitionSpec("data")
    spec_r = jax.sharding.PartitionSpec()
    in_specs = (spec_x,) * n_sharded + (spec_r,) * n_rep

    def local(*args):
        outs = fn(*args, **kw)
        return tuple(jax.lax.psum(o, "data") for o in outs)

    return collectives.shard_map(
        local, mesh, in_specs=in_specs, out_specs=(spec_r, spec_r)
    )


def _count(x):
    # ``x`` is the jit-level GLOBAL array (shard_map only sees shards of
    # it), so its row count already IS the SyncBN global count.
    return x.size // x.shape[-1]


def _stats_of(x, mesh):
    if IMPL == "matmul":
        # Native XLA contractions: GSPMD partial-sums + all-reduces them
        # over the sharded row dim itself — no shard_map needed for SyncBN.
        s, ss = mm_stats(x)
    elif mesh is not None and mesh.shape.get("data", 1) > 1:
        s, ss = _shard_stats(bn_stats, mesh, 1, 0)(x)
        s, ss = s[0], ss[0]
    else:
        s, ss = bn_stats(x)
        s, ss = s[0], ss[0]
    n = _count(x)
    mean = s / n
    var = jnp.maximum(ss / n - jnp.square(mean), 0.0)  # one-pass, clamped
    return mean, var


def _bn_fwd_impl(scale, bias, x, eps, mesh, relu):
    mean, var = _stats_of(x, mesh)
    inv = jax.lax.rsqrt(var + eps)
    # Same elementwise formula (and compute dtype) as layers.batchnorm's
    # XLA path; stays in XLA so it fuses into the consuming conv.
    y = (x - mean.astype(x.dtype)) * (inv * scale).astype(x.dtype) + bias.astype(
        x.dtype
    )
    if relu:
        y = jax.nn.relu(y)
    return y, mean, var


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def batchnorm_train(scale, bias, x, eps, mesh, relu=False):
    """(y, mean, var); y is post-ReLU when ``relu``.  mean/var feed the
    caller's running-stats update (stop-gradded there — their cotangents
    are zero and the backward ignores them)."""
    return _bn_fwd_impl(scale, bias, x, eps, mesh, relu)


def _bn_train_fwd(scale, bias, x, eps, mesh, relu):
    y, mean, var = _bn_fwd_impl(scale, bias, x, eps, mesh, relu)
    inv = jax.lax.rsqrt(var + eps)
    return (y, mean, var), (scale, bias, x, mean, inv)


def _bn_train_bwd(eps, mesh, relu, res, cts):
    do, _, _ = cts  # mean/var cotangents are zero (running stats stop-grad)
    scale, bias, x, mean, inv = res
    if IMPL == "matmul":
        s1, s2 = mm_bwd_stats(do, x, mean, inv, scale, bias, relu=relu)
    else:
        mean2d, inv2d = mean[None], inv[None]
        s2d = scale[None].astype(jnp.float32)
        b2d = bias[None].astype(jnp.float32)
        if mesh is not None and mesh.shape.get("data", 1) > 1:
            s1, s2 = _shard_stats(bn_bwd_stats, mesh, 2, 4, relu=relu)(
                do, x, mean2d, inv2d, s2d, b2d
            )
        else:
            s1, s2 = bn_bwd_stats(do, x, mean2d, inv2d, s2d, b2d, relu=relu)
        s1, s2 = s1[0], s2[0]
    n = _count(x)
    # Elementwise dx stays in XLA: the ReLU mask recompute and the rank-1
    # broadcasts fuse into the consuming conv-backward ops, as they did on
    # the all-XLA path.
    xhat = (x - mean.astype(x.dtype)) * inv.astype(x.dtype)
    dy = do
    if relu:
        pre = xhat * scale.astype(x.dtype) + bias.astype(x.dtype)
        dy = do * (pre > 0).astype(x.dtype)
    g = (scale * inv).astype(x.dtype)
    dx = g * (dy - (s1 / n).astype(x.dtype) - xhat * (s2 / n).astype(x.dtype))
    return s2, s1, dx  # dgamma, dbeta, dx


batchnorm_train.defvjp(_bn_train_fwd, _bn_train_bwd)
