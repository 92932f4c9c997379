"""Attention ops: reference MHA + BOTH canonical sequence/context-parallel
layouts — the ring and (r4) Ulysses all-to-all.

No reference analog (SURVEY.md section 5.7: the reference has no attention
model; its longest-sequence workload scales only by TBPTT unroll).  This is
the framework's long-context growth path, first-class per the blueprint:
sequences shard over the mesh ``seq`` axis, and attention runs either

- as a RING — queries stay local while key/value blocks rotate around the
  axis via ``ppermute`` (one hop per step, riding ICI neighbor links), with
  the online-softmax accumulation of flash attention so no shard ever
  materialises the full [T, T] score matrix; works for any head count — or
- as ULYSSES all-to-all CP — one ``all_to_all`` per tensor trades the
  sequence sharding for head sharding, attention runs locally over the
  full sequence (no cross-hop softmax bookkeeping; the fused flash
  backward's regime), one ``all_to_all`` back; needs local heads
  divisible by the seq shards (:func:`ulysses_attention`).

Numerical contract (tested): either layout over a seq-sharded mesh ==
full-sequence attention on one device, for both causal and full attention,
values and gradients.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..parallel import collectives

#: Finite "minus infinity" for masked logits: keeps the online-softmax
#: recurrence NaN-free when a block is fully masked (exp(-1e30 - m) == 0 for
#: any finite m), where a true -inf would produce inf-inf = NaN.
NEG_INF = -1e30


def mha(q, k, v, *, causal: bool = False, q_offset: int = 0, k_offset: int = 0):
    """Reference multi-head attention.  q: [B, H, Tq, D], k/v: [B, H, Tk, D].

    ``q_offset``/``k_offset`` are the global positions of the first row of
    q/k — the pieces ring attention needs for causal masking across shards.
    """
    d = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(d)
    if causal:
        qpos = q_offset + jnp.arange(q.shape[2])[:, None]
        kpos = k_offset + jnp.arange(k.shape[2])[None, :]
        s = jnp.where(kpos > qpos, NEG_INF, s)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", w, v)


def _block(q, k, v, carry, *, scale, causal, q_offset, k_offset):
    """One online-softmax accumulation step (the flash-attention recurrence)
    against a single k/v block.  carry = (o, m, l):
    o [B,H,Tq,D] unnormalised output, m [B,H,Tq,1] running max,
    l [B,H,Tq,1] running sum of exp."""
    o, m, l = carry
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale  # [B,H,Tq,Tk]
    if causal:
        qpos = q_offset + jnp.arange(q.shape[2])[:, None]
        kpos = k_offset + jnp.arange(k.shape[2])[None, :]
        s = jnp.where(kpos > qpos, NEG_INF, s)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    # Valid (unmasked) entries only: a fully-masked block contributes 0.
    p = jnp.exp(s - m_new) * (s > NEG_INF / 2)
    alpha = jnp.exp(m - m_new)
    o = o * alpha + jnp.einsum("bhqk,bhkd->bhqd", p, v)
    l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
    return o, m_new, l


def ring_attention(q, k, v, *, axis_name: str, causal: bool = False):
    """Sequence-parallel attention inside ``shard_map``: queries stay local,
    k/v blocks rotate ``axis_size`` hops around the ring (permuter.h role —
    SURVEY.md D11 — but emitted as XLA ``ppermute`` on ICI).

    Shapes per shard: q/k/v [B, H, T_local, D]; the global sequence is the
    concatenation over the axis in index order.
    """
    n = lax.axis_size(axis_name)
    my = collectives.axis_index(axis_name)
    t_local = q.shape[2]
    scale = 1.0 / math.sqrt(q.shape[-1])
    q32, dtype = q.astype(jnp.float32), q.dtype
    o = jnp.zeros(q.shape[:3] + (v.shape[-1],), jnp.float32)
    m = jnp.full(q.shape[:3] + (1,), NEG_INF, jnp.float32)
    l = jnp.zeros(q.shape[:3] + (1,), jnp.float32)

    def body(carry, i):
        o, m, l, k, v = carry
        src = (my + i) % n
        o, m, l = _block(
            q32,
            k.astype(jnp.float32),
            v.astype(jnp.float32),
            (o, m, l),
            scale=scale,
            causal=causal,
            q_offset=my * t_local,
            k_offset=src * t_local,
        )
        # Receive-from-next rotation (shift=-1): after i hops we hold shard
        # (my + i) % n's k/v; every shard does n identical hops => a clean
        # ICI ring schedule.  The nth hop returns k/v to their owners; XLA
        # drops it as dead code since the outputs are unused.
        k, v = jax.tree.map(
            lambda x: collectives.ring_permute(x, axis_name, shift=-1), (k, v)
        )
        return (o, m, l, k, v), None

    (o, m, l, k, v), _ = lax.scan(body, (o, m, l, k, v), jnp.arange(n))
    return (o / jnp.maximum(l, 1e-30)).astype(dtype)


# ----------------------------------------------------------------------------
# Ring attention with Pallas flash block compute (fwd + bwd)
# ----------------------------------------------------------------------------
#
# The plain ring above computes each hop's block attention in XLA f32 ops —
# correct, but the per-hop [Tq, Tk] scores run at the f32 MXU rate and live
# in HBM.  This variant runs the SAME ring schedule with the Pallas flash
# kernel as the per-hop compute (bf16 MXU rate, O(block) VMEM), merging hops
# by their log-sum-exp.  Causal structure exploited statically: hop 0 is
# ALWAYS the diagonal shard (kernel compiled causal), later hops are never
# diagonal (kernel compiled non-causal; whole-block visibility is a traced
# where-mask, since under causal masking a later shard's k/v block is either
# fully visible or fully masked).  The backward runs the flash dq/dkv
# kernels per hop, with dk/dv accumulators rotating in lockstep with their
# k/v blocks so every gradient arrives home after the full circle.


def _merge(o1, lse1, o2, lse2):
    """Merge two normalised attention partials by their lse (f32)."""
    lse = jnp.logaddexp(lse1, lse2)
    w1 = jnp.exp(lse1 - lse)
    w2 = jnp.exp(lse2 - lse)
    return o1 * w1 + o2 * w2, lse


def _fold_heads(x):
    B, H, T, D = x.shape
    return x.reshape(B * H, T, D)


def ring_flash_attention(
    q, k, v, *, axis_name: str, causal: bool = False, block_q: int = 1024,
    block_k: int = 1024,
):
    """Ring attention whose per-hop block compute is the Pallas flash kernel
    (inside ``shard_map``; shapes per shard [B, H, T_local, D]).

    Differentiable via a hand-written ring backward (flash dq/dkv kernels
    per hop).  Exact-parity contract with :func:`ring_attention` (tested).
    """
    return _ring_flash(q, k, v, axis_name, causal, block_q, block_k)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _ring_flash(q, k, v, axis_name, causal, block_q, block_k):
    o, _ = _ring_flash_fwd_impl(q, k, v, axis_name, causal, block_q, block_k)
    return o


def _ring_flash_fwd_impl(q, k, v, axis_name, causal, block_q, block_k):
    from . import flash_attention as fa

    n = lax.axis_size(axis_name)
    # Shard identity is only consumed by the causal visibility test; tracing
    # it unconditionally leaves a DEAD axis_index in the jaxpr (the
    # custom_vjp boundary blocks DCE), which lowers to an unannotated
    # partition-id the CPU SPMD partitioner rejects outright.
    my = collectives.axis_index(axis_name) if causal else None
    B, H, T, D = q.shape
    dtype = q.dtype
    qf, kf, vf = _fold_heads(q), _fold_heads(k), _fold_heads(v)
    bq = fa._pick_block(T, block_q)
    bk = fa._pick_block(T, block_k)

    # Hop 0: the diagonal shard — statically causal.  All partials emit f32
    # straight from the kernel's accumulator: rounding each hop to bf16
    # before merging would accumulate O(n_hops) quantization error.
    o, lse = fa.fwd_call(
        qf, kf, vf, causal=causal, block_q=bq, block_k=bk, out_dtype=jnp.float32
    )

    def body(carry, i):
        o, lse, kr, vr = carry
        kr, vr = jax.tree.map(
            lambda x: collectives.ring_permute(x, axis_name, shift=-1), (kr, vr)
        )
        src = (my + i) % n if causal else None

        # Never the diagonal for i in 1..n-1 — statically non-causal kernel;
        # under causal masking the whole block is visible iff src < my.
        # lax.cond skips the kernel entirely on masked hops (no wasted
        # compute, and nothing numerically suspect ever materialises).
        def visit(o, lse):
            o_h, lse_h = fa.fwd_call(
                qf, kr, vr, causal=False, block_q=bq, block_k=bk,
                out_dtype=jnp.float32,
            )
            return _merge(o, lse, o_h, lse_h)

        if causal:
            o, lse = lax.cond(src < my, visit, lambda o, lse: (o, lse), o, lse)
        else:
            o, lse = visit(o, lse)
        return (o, lse, kr, vr), None

    if n > 1:
        (o, lse, _, _), _ = lax.scan(body, (o, lse, kf, vf), jnp.arange(1, n))
    return o.astype(dtype).reshape(B, H, T, D), lse


def _ring_flash_fwd_rule(q, k, v, axis_name, causal, block_q, block_k):
    o, lse = _ring_flash_fwd_impl(q, k, v, axis_name, causal, block_q, block_k)
    return o, (q, k, v, o, lse)


def _ring_flash_bwd_rule(axis_name, causal, block_q, block_k, res, do):
    from . import flash_attention as fa

    q, k, v, o, lse = res
    n = lax.axis_size(axis_name)
    my = collectives.axis_index(axis_name)
    B, H, T, D = q.shape
    qf, kf, vf = _fold_heads(q), _fold_heads(k), _fold_heads(v)
    dof = _fold_heads(do)
    delta = fa.compute_delta(dof, _fold_heads(o))
    bq = fa._pick_block(T, block_q)
    bk = fa._pick_block(T, block_k)

    # Hop 0 (diagonal, statically causal); all partials f32 (see fwd).
    f32 = jnp.float32

    def hop_bwd(kh, vh, *, hop_causal):
        """Per-hop (dq, dk, dv) partials: the fused single-pass kernel when
        the per-shard block counts reach its dispatch regime (long-context
        shards), the split kernels otherwise — same contract either way."""
        if fa._use_fused_bwd(T // bq, kh.shape[1] // bk, T, D):
            return fa.fused_bwd_call(
                qf, kh, vh, dof, lse, delta, causal=hop_causal,
                block_q=bq, block_k=bk, out_dtype=f32,
            )
        dq_h = fa.dq_call(
            qf, kh, vh, dof, lse, delta, causal=hop_causal, block_q=bq,
            block_k=bk, out_dtype=f32,
        )
        dk_h, dv_h = fa.dkv_call(
            qf, kh, vh, dof, lse, delta, causal=hop_causal, block_q=bq,
            block_k=bk, out_dtype=f32,
        )
        return dq_h, dk_h, dv_h

    dq, dk0, dv0 = hop_bwd(kf, vf, hop_causal=causal)

    def body(carry, i):
        dq, kr, vr, dk, dv = carry
        # dk/dv accumulators rotate in LOCKSTEP with their k/v blocks, so
        # after the full circle every block's gradient is back home.
        kr, vr, dk, dv = jax.tree.map(
            lambda x: collectives.ring_permute(x, axis_name, shift=-1),
            (kr, vr, dk, dv),
        )
        src = (my + i) % n

        # lax.cond, NOT a multiply-by-zero mask: on a fully-masked hop the
        # non-causal kernel computes exp(s - lse) where lse covers only
        # VISIBLE keys — a masked score exceeding lse by ~88 overflows f32
        # exp, and 0 * inf would poison the gradients with NaN.  The cond
        # never runs the kernel there (and skips ~half the off-diagonal
        # backward FLOPs under causal masking).
        def visit(dq, dk, dv):
            dq_h, dk_h, dv_h = hop_bwd(kr, vr, hop_causal=False)
            return dq + dq_h, dk + dk_h, dv + dv_h

        if causal:
            dq, dk, dv = lax.cond(
                src < my, visit, lambda dq, dk, dv: (dq, dk, dv), dq, dk, dv
            )
        else:
            dq, dk, dv = visit(dq, dk, dv)
        return (dq, kr, vr, dk, dv), None

    if n > 1:
        (dq, _, _, dk, dv), _ = lax.scan(
            body, (dq, kf, vf, dk0, dv0), jnp.arange(1, n)
        )
        # One final rotation brings the accumulators home (they have moved
        # n-1 hops with their blocks).
        dk, dv = jax.tree.map(
            lambda x: collectives.ring_permute(x, axis_name, shift=-1), (dk, dv)
        )
    else:
        dk, dv = dk0, dv0

    unfold = lambda x, ref: x.astype(ref.dtype).reshape(ref.shape)
    return unfold(dq, q), unfold(dk, k), unfold(dv, v)


_ring_flash.defvjp(_ring_flash_fwd_rule, _ring_flash_bwd_rule)


def sequence_parallel_attention(
    mesh: Mesh,
    q,
    k,
    v,
    *,
    causal: bool = False,
    seq_axis: str = "seq",
    batch_axis="data",
    head_axis: str = "model",
    impl: str = "auto",
):
    """Global-array entry point: [B, H, T, D] inputs with T sharded over
    ``seq_axis`` (and heads over ``head_axis`` when present — ring SP and
    Megatron TP compose).  Internally a ``shard_map`` running the ring.
    Falls back to plain (XLA-partitioned) attention when the mesh has no seq
    axis.

    ``impl``: per-hop block compute — "xla" (the reference ring), "flash"
    (Pallas kernels fwd+bwd), "ulysses" (all-to-all head-resharding CP —
    see :func:`ulysses_attention`), or "auto" (flash ring on TPU, xla
    elsewhere — interpret-mode Pallas inside a scan is prohibitively slow
    on CPU).

    ``batch_axis`` may be a tuple of axes (('data','expert') for MoE
    models whose batches shard over both — models/transformer.data_axes).
    """
    if impl not in ("auto", "xla", "flash", "ulysses"):
        raise ValueError(f"impl must be auto|xla|flash|ulysses, got {impl!r}")
    if mesh.shape.get(seq_axis, 1) == 1:
        return mha(q, k, v, causal=causal)
    if impl == "ulysses":
        return ulysses_attention(
            mesh, q, k, v, causal=causal, seq_axis=seq_axis,
            batch_axis=batch_axis, head_axis=head_axis,
        )
    h_entry = head_axis if mesh.shape.get(head_axis, 1) > 1 else None
    spec = P(batch_axis, h_entry, seq_axis, None)

    if impl == "auto":
        from .flash_attention import flash_viable

        impl = "flash" if flash_viable(q.shape[2] // mesh.shape[seq_axis]) else "xla"
    if impl == "flash":
        fn = functools.partial(
            ring_flash_attention, axis_name=seq_axis, causal=causal
        )
    else:
        fn = functools.partial(ring_attention, axis_name=seq_axis, causal=causal)
    mapped = collectives.shard_map(
        fn, mesh, in_specs=(spec, spec, spec), out_specs=spec
    )
    return mapped(q, k, v)


def ulysses_attention(
    mesh: Mesh,
    q,
    k,
    v,
    *,
    causal: bool = False,
    seq_axis: str = "seq",
    batch_axis="data",
    head_axis: str = "model",
):
    """All-to-all sequence/context parallelism (the DeepSpeed-Ulysses
    layout; SURVEY.md section 7 growth path #7 names it next to the ring):
    instead of rotating k/v shards around a ring, ONE ``all_to_all`` per
    tensor re-shards [B, H_loc, T/s, D] -> [B, H_loc/s, T, D] — sequence
    gathered, heads scattered — then attention runs LOCALLY over the full
    sequence (plain causal flag, no cross-hop online-softmax bookkeeping),
    and one ``all_to_all`` brings the output back to the sequence layout.

    Trade vs the ring: 4 all_to_alls moving activation-sized payloads per
    layer and full-T local compute (which puts the per-shard shape squarely
    in the fused flash backward's regime), against the ring's n-1
    latency-chained permutes of k/v; Ulysses needs heads divisible by the
    seq shards, the ring does not.  Same entry contract as
    :func:`sequence_parallel_attention` (composes with Megatron head
    sharding over ``head_axis``).
    """
    s = mesh.shape.get(seq_axis, 1)
    if s == 1:
        return mha(q, k, v, causal=causal)
    H = q.shape[1]
    h_shards = mesh.shape.get(head_axis, 1)
    h_entry = head_axis if h_shards > 1 else None
    if (H // h_shards) % s:
        raise ValueError(
            f"ulysses: {H} heads / {h_shards} '{head_axis}' shards leaves "
            f"{H // h_shards} local heads, not divisible by {seq_axis}={s}; "
            "use the ring (impl='flash'/'xla') for this shape"
        )
    spec = P(batch_axis, h_entry, seq_axis, None)

    from .flash_attention import flash_attention, flash_viable

    T = q.shape[2]
    use_flash = flash_viable(T)  # full T is local after the reshard

    def local(q, k, v):
        # [b, h_loc, T/s, D] -> heads scattered, sequence gathered.
        a2a = functools.partial(
            lax.all_to_all, axis_name=seq_axis, tiled=True
        )
        q, k, v = (a2a(t, split_axis=1, concat_axis=2) for t in (q, k, v))
        if use_flash:
            o = flash_attention(q, k, v, causal=causal)
        else:
            o = mha(q, k, v, causal=causal)
        # Back to the sequence-sharded layout for the rest of the layer.
        return a2a(o, split_axis=2, concat_axis=1)

    return collectives.shard_map(
        local, mesh, in_specs=(spec, spec, spec), out_specs=spec
    )(q, k, v)
