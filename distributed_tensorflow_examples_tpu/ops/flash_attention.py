"""Flash attention as a Pallas TPU kernel (forward + FA2 backward).

The reference's "custom native op" slot is hand-written C++ compiled into
libtensorflow (SURVEY.md D11/D12); the TPU-native equivalent is a Pallas
kernel lowered through Mosaic.  This is the framework's flagship custom
kernel: O(block) VMEM attention — neither the [T, T] score matrix nor the
full k/v sequence is ever resident on-chip, so sequence length is bounded by
HBM, not VMEM (plain XLA attention materialises [T, T] scores and dies at
moderate T; a full-k/v-in-VMEM kernel dies at ~16k).

Design (per /opt/skills/guides/pallas_guide.md):
- 3D grid (batch*heads, q blocks, k blocks); the k dimension is innermost
  and "arbitrary" (sequential), so the online-softmax state for one q block
  lives in VMEM scratch across k steps and the output block is written on
  the last k step.
- Causal: blocks fully above the diagonal skip their compute via ``pl.when``
  (grid steps still occur, but no matmuls issue).
- Online softmax in f32; NEG_INF finite mask keeps partially-masked blocks
  NaN-free (same contract as ops.attention).
- Backward: two kernels with the same structure — dq (grid over q blocks,
  inner over k) and dk/dv (grid over k blocks, inner over q) — using the
  saved LSE and the FA2 recurrence: p = exp(s - lse); ds = p*(do.v^T - D);
  D = rowsum(do * o).
- ``interpret=True`` on the CPU platform only (``common.interpret_mode``),
  so CPU tests run the same kernels; on TPU they compile through Mosaic or
  raise.

Composes with ring attention (ops.attention): the ring rotates k/v shards
between chips; this kernel is the per-chip block compute.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import compiler_params, interpret_mode

NEG_INF = -1e30

#: log2(e): the kernels run the online softmax in BASE 2 — ``exp2`` is the
#: hardware primitive (``exp`` lowers to exp2 plus a multiply per element,
#: and the [bq, bk] score tile is exactly where per-element VPU work
#: competes with the MXU at head_dim 64).  The 1/sqrt(d) scale is folded
#: into the same constant and applied ONCE to q (O(T*d)) instead of to
#: every score tile (O(T^2)).
LOG2E = 1.4426950408889634
LN2 = 0.6931471805599453

#: q/k.T with K-dim contraction (dim 1 of both operands).
_TRANS_B = (((1,), (1,)), ((), ()))
#: Contract dim 0 of both operands: a.T @ b without materialising a.T.
_TRANS_A = (((0,), (0,)), ((), ()))


def _dot_nt(a, b):
    """a @ b.T at the MXU's native input rate: operands keep their storage
    dtype (bf16 runs 8x the f32 rate on v5e) and accumulate in f32 via
    ``preferred_element_type`` — f32-casting the inputs first (the r1 kernel)
    silently ran every matmul at the f32 rate."""
    return jax.lax.dot_general(a, b, _TRANS_B, preferred_element_type=jnp.float32)


def _dot(a, b):
    """a @ b, f32 accumulation; ``a`` is cast to ``b``'s dtype first (the
    softmax weights are f32 — feed the MXU its native input width)."""
    return jax.lax.dot(a.astype(b.dtype), b, preferred_element_type=jnp.float32)


def _dot_tn(a, b):
    """a.T @ b via dot_general (no explicit transpose of the score tile)."""
    return jax.lax.dot_general(
        a.astype(b.dtype), b, _TRANS_A, preferred_element_type=jnp.float32
    )


def _params():
    return compiler_params(("parallel", "parallel", "arbitrary"))


def _mask(s, qi, kj, bq, bk):
    qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    kpos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(kpos > qpos, NEG_INF, s)


def _visible(qi, kj, bq, bk):
    """False iff the (qi, kj) block is entirely above the causal diagonal."""
    return kj * bk <= (qi + 1) * bq - 1


def _fully_visible(qi, kj, bq, bk):
    """True iff no element of the (qi, kj) block is masked (block entirely
    on/below the diagonal) — such blocks skip the iota/where mask and the
    masked-row guard entirely.  With bq == bk tiles only the diagonal
    blocks take the masked branch."""
    return kj * bk + bk - 1 <= qi * bq


def _causal_dispatch(step, causal, qi, kj, bq, bk):
    """Shared three-way block dispatch for every kernel: mask-free compute
    on fully-visible blocks, masked compute on diagonal-straddling blocks,
    nothing above the diagonal.  ``step(masked)`` returns the traced block
    body (the per-kernel compute closure)."""
    if causal:
        full = _fully_visible(qi, kj, bq, bk)
        pl.when(full)(step(masked=False))
        pl.when(
            jnp.logical_and(_visible(qi, kj, bq, bk), jnp.logical_not(full))
        )(step(masked=True))
    else:
        step(masked=False)()


# ----------------------------------------------------------------------------
# Forward
# ----------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc, acc_sc, *, causal, bq, bk):
    """q arrives PRE-SCALED by scale*log2(e); softmax state is base-2 (m/l
    in exp2 units), converted to the natural-log lse contract at the end."""
    qi, kj = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        m_sc[:] = jnp.full_like(m_sc, NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)
        acc_sc[:] = jnp.zeros_like(acc_sc)

    def _step(masked: bool):
        def _compute():
            q, k, v = q_ref[0], k_ref[0], v_ref[0]  # native dtype into the MXU
            s = _dot_nt(q, k)  # [bq, bk] f32, base-2 logits
            if masked:
                s = _mask(s, qi, kj, bq, bk)
            m_prev, l_prev = m_sc[:], l_sc[:]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp2(s - m_new)
            if masked:
                p = p * (s > NEG_INF / 2)  # fully-masked rows contribute 0
            alpha = jnp.exp2(m_prev - m_new)
            acc_sc[:] = acc_sc[:] * alpha + _dot(p, v)
            l_sc[:] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
            m_sc[:] = m_new

        return _compute

    _causal_dispatch(_step, causal, qi, kj, bq, bk)

    @pl.when(kj == nk - 1)
    def _finish():
        l_safe = jnp.maximum(l_sc[:], 1e-30)
        o_ref[0] = (acc_sc[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0] = m_sc[:] * LN2 + jnp.log(l_safe)


def _fwd(q, k, v, *, causal, block_q, block_k, out_dtype=None):
    bh, t, d = q.shape
    scale = 1.0 / math.sqrt(d)
    bq, bk = min(block_q, t), min(block_k, t)
    q = q * jnp.asarray(scale * LOG2E, q.dtype)  # fold scale+base-2 into q
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, causal=causal, bq=bq, bk=bk),
        grid=(bh, t // bq, t // bk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, out_dtype or q.dtype),
            jax.ShapeDtypeStruct((bh, t, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),  # running max
            pltpu.VMEM((bq, 1), jnp.float32),  # running sum
            pltpu.VMEM((bq, d), jnp.float32),  # output accumulator
        ],
        compiler_params=_params(),
        interpret=interpret_mode(),
    )(q, k, v)
    return o, lse


# ----------------------------------------------------------------------------
# Backward (FA2)
# ----------------------------------------------------------------------------


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_sc, *, scale, causal, bq, bk):
    """q arrives PRE-SCALED by scale*log2(e) (the forward's fold); the saved
    natural-log lse is converted to base 2 once per [bq, 1] block."""
    qi, kj = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        dq_sc[:] = jnp.zeros_like(dq_sc)

    def _step(masked: bool):
        def _compute():
            q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
            lse2 = lse_ref[0] * LOG2E  # [bq, 1] natural -> base-2
            delta = delta_ref[0]
            s = _dot_nt(q, k)  # base-2 logits
            if masked:
                s = _mask(s, qi, kj, bq, bk)
            p = jnp.exp2(s - lse2)
            if masked:
                p = p * (s > NEG_INF / 2)
            ds = p * (_dot_nt(do, v) - delta)
            dq_sc[:] = dq_sc[:] + _dot(ds, k)

        return _compute

    _causal_dispatch(_step, causal, qi, kj, bq, bk)

    @pl.when(kj == nk - 1)
    def _finish():
        dq_ref[0] = (dq_sc[:] * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_sc, dv_sc, *, scale, causal, bq, bk):
    """q PRE-SCALED as in _dq_kernel; dk's pending 1/sqrt(d)*base-2 factors
    are unwound once at the final write, not per block."""
    kj, qi = pl.program_id(1), pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_sc[:] = jnp.zeros_like(dk_sc)
        dv_sc[:] = jnp.zeros_like(dv_sc)

    def _step(masked: bool):
        def _compute():
            q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
            lse2 = lse_ref[0] * LOG2E
            delta = delta_ref[0]
            s = _dot_nt(q, k)
            if masked:
                s = _mask(s, qi, kj, bq, bk)
            p = jnp.exp2(s - lse2)
            if masked:
                p = p * (s > NEG_INF / 2)
            dv_sc[:] = dv_sc[:] + _dot_tn(p, do)
            ds = p * (_dot_nt(do, v) - delta)
            # ds.T @ q with q still carrying the scale*log2(e) fold: the
            # extra LOG2E is divided back out in _finish.
            dk_sc[:] = dk_sc[:] + _dot_tn(ds, q)

        return _compute

    _causal_dispatch(_step, causal, qi, kj, bq, bk)

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0] = (dk_sc[:] * (1.0 / LOG2E)).astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[:].astype(dv_ref.dtype)


def _bwd(causal, block_q, block_k, res, do):
    q, k, v, o, lse = res
    delta = compute_delta(do, o)
    tq, d = q.shape[1], q.shape[2]
    bq = _pick_block(tq, block_q)
    bk = _pick_block(k.shape[1], block_k)
    if _fused_bwd_policy(tq // bq, k.shape[1] // bk):
        if tq * d * 4 <= _FUSED_MAX_ACC_BYTES:
            return fused_bwd_call(
                q, k, v, do, lse, delta, causal=causal, block_q=bq, block_k=bk
            )
        seg = _fused_segment_rows(tq, d, bq, bk)
        if seg:
            return fused_bwd_segmented(
                q, k, v, do, lse, delta,
                causal=causal, block_q=bq, block_k=bk, seg=seg,
            )
    dq = dq_call(q, k, v, do, lse, delta, causal=causal, block_q=bq, block_k=bk)
    dk, dv = dkv_call(q, k, v, do, lse, delta, causal=causal, block_q=bq, block_k=bk)
    return dq, dk, dv


#: Fused-backward dispatch override: None = auto (the nq/nk >= 4 regime the
#: r3 expected-value analysis funds — BASELINE.md), True/False = force.
_FUSED_BWD_OVERRIDE: bool | None = None

#: Hardware-validation latch (ADVICE r4 medium): the fused kernel's
#: running-flush dq scheme depends on Mosaic writing the revisited dq output
#: window every grid step with last-write-wins ordering — semantics CPU
#: interpret mode cannot validate.  Until ``tools/flash_parity.py`` has
#: PASSED on a real chip, auto-dispatch stays on the split kernels; opt in
#: per-process with DTX_FUSED_BWD=1, after running the parity gate first.
#: Flip to True once PERF.md records the TPU parity + bitwise-determinism
#: pass.
_FUSED_BWD_VALIDATED = False

#: Upper bound on the fused kernel's [tq, d] f32 dq accumulator (VMEM
#: scratch).  8 MB = T=16384 at head_dim 128 — beyond that the split
#: kernels take over (VMEM is ~tens of MB and the s/p tiles need most of
#: it).
_FUSED_MAX_ACC_BYTES = 8 * 1024 * 1024


def _use_fused_bwd(nq: int, nk: int, tq: int, d: int) -> bool:
    """The fused dq+dk+dv kernel removes the split kernels' s/p recompute
    (2 of 7 block matmuls, half the exp2) at the cost of a [tq, d] f32
    VMEM accumulator and nk running dq flushes; it starts paying at
    nq/nk >= 4 — exactly the long-context (T >= 4k per shard at 1024
    tiles) regime the r3 analysis funds.  The T=2048 flagship (nk=2)
    keeps the split kernels.

    DTX_FUSED_BWD=0 forces split, =1 opts into the auto regime without the
    ``_FUSED_BWD_VALIDATED`` latch (read at trace time, like the block-size
    env vars — one setting per process).

    This predicate answers "single fused call?"; beyond the VMEM cap the
    dispatcher (``_bwd``) may still serve the fused MECHANISM via the
    r5 segmented wrapper (``fused_bwd_segmented``)."""
    return _fused_bwd_policy(nq, nk) and tq * d * 4 <= _FUSED_MAX_ACC_BYTES


def _fused_bwd_policy(nq: int, nk: int) -> bool:
    """Override/env/latch + the nq/nk regime — everything about WANTING the
    fused mechanism; the VMEM-cap/segmentation split is the dispatcher's."""
    import os

    if _FUSED_BWD_OVERRIDE is not None:
        return _FUSED_BWD_OVERRIDE
    env = os.environ.get("DTX_FUSED_BWD", "")
    if env not in ("", "0", "1"):
        # Same contract as the DTX_FLASH_BQ/BK guard: an A/B typo
        # (=true, =yes) must not silently record a split-kernel run
        # under a fused label.
        raise ValueError(f"DTX_FUSED_BWD={env!r}: must be '0' or '1'")
    if env == "0":
        return False
    if env != "1" and not _FUSED_BWD_VALIDATED:
        return False
    return nq >= 4 and nk >= 4


def _fused_segment_rows(tq: int, d: int, bq: int, bk: int) -> int:
    """Largest q-segment length that (a) fits the [seg, d] f32 accumulator
    cap, (b) divides tq, (c) is a multiple of BOTH blocks (the diagonal and
    prefix calls tile k in bk-sized blocks over seg-multiples) — or 0 when
    no such segmentation exists (dispatcher falls back to the split
    kernels)."""
    cap_rows = _FUSED_MAX_ACC_BYTES // (d * 4)
    for m in range(2, tq // bq + 1):
        if tq % m:
            continue
        seg = tq // m
        if seg % bq or seg % bk:
            continue
        if seg <= cap_rows:
            return seg
    return 0


def fused_bwd_segmented(
    q, k, v, do, lse, delta, *, causal, block_q, block_k, seg,
):
    """r5: the fused backward past its VMEM cap — T splits into q segments
    whose [seg, d] dq accumulators fit, each running the SAME hardware-
    validated kernel against only the k/v it can see:

    - causal: segment s pairs one square DIAGONAL call (q_s x k_s, local
      causal == global causal because both carry the same offset) with one
      rectangular full-visibility PREFIX call (q_s x k[:s*seg],
      causal=False); k beyond the segment is fully masked and never runs.
    - non-causal: one rectangular call per segment (q_s x full k).

    dq is exact per segment (summed across its calls); dk/dv arrive as
    per-call partials accumulated in f32 outside the kernel.  Extra HBM
    traffic vs the in-cap path is the f32 dk/dv partial accumulation —
    O(nseg) passes over k-prefix-sized buffers — which the 7->5 matmul
    saving dominates at the T >= 32k shapes this serves (BASELINE.md r5).
    Parity: tests/test_flash_attention.py segmented sweep."""
    bh, tq, d = q.shape
    tk = k.shape[1]
    nseg = tq // seg
    f32 = jnp.float32
    dk_acc = jnp.zeros((bh, tk, d), f32)
    dv_acc = jnp.zeros((bh, tk, d), f32)
    dq_parts = []
    for s in range(nseg):
        rows = slice(s * seg, (s + 1) * seg)
        q_s, do_s = q[:, rows], do[:, rows]
        lse_s, delta_s = lse[:, rows], delta[:, rows]
        if not causal:
            dq_s, dk_p, dv_p = fused_bwd_call(
                q_s, k, v, do_s, lse_s, delta_s,
                causal=False, block_q=block_q, block_k=block_k, out_dtype=f32,
            )
            dk_acc = dk_acc + dk_p
            dv_acc = dv_acc + dv_p
        else:
            kcols = slice(s * seg, (s + 1) * seg)
            dq_s, dk_d, dv_d = fused_bwd_call(
                q_s, k[:, kcols], v[:, kcols], do_s, lse_s, delta_s,
                causal=True, block_q=block_q, block_k=block_k, out_dtype=f32,
            )
            dk_acc = dk_acc.at[:, kcols].add(dk_d)
            dv_acc = dv_acc.at[:, kcols].add(dv_d)
            if s > 0:
                pre = slice(0, s * seg)
                dq_p, dk_p, dv_p = fused_bwd_call(
                    q_s, k[:, pre], v[:, pre], do_s, lse_s, delta_s,
                    causal=False, block_q=block_q, block_k=block_k,
                    out_dtype=f32,
                )
                dq_s = dq_s + dq_p
                dk_acc = dk_acc.at[:, pre].add(dk_p)
                dv_acc = dv_acc.at[:, pre].add(dv_p)
        dq_parts.append(dq_s.astype(q.dtype))
    return (
        jnp.concatenate(dq_parts, axis=1),
        dk_acc.astype(k.dtype),
        dv_acc.astype(v.dtype),
    )


def compute_delta(do, o):
    """FA2's D = rowsum(do * o), f32 — shared by the plain and ring paths."""
    return jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1, keepdims=True
    )


def dq_call(q, k, v, do, lse, delta, *, causal, block_q, block_k, out_dtype=None):
    """dq for one (q-block x k/v-block) pairing — exposed so ring attention
    can run the SAME Pallas backward per hop (q local, k/v visiting).
    q/do/lse/delta: [bh, tq, ...]; k/v: [bh, tk, d].  ``out_dtype``: f32 for
    ring partials (see fwd_call)."""
    bh, tq, d = q.shape
    tk = k.shape[1]
    scale = 1.0 / math.sqrt(d)
    bq, bk = min(block_q, tq), min(block_k, tk)
    q = q * jnp.asarray(scale * LOG2E, q.dtype)  # base-2 fold (see _fwd)

    return pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal, bq=bq, bk=bk),
        grid=(bh, tq // bq, tk // bk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),  # q
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),  # k
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),  # v
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),  # do
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),  # lse
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),  # delta
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, out_dtype or q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=_params(),
        interpret=interpret_mode(),
    )(q, k, v, do, lse, delta)


def dkv_call(q, k, v, do, lse, delta, *, causal, block_q, block_k, out_dtype=None):
    """dk/dv for one (q-block x k/v-block) pairing (ring-reusable, see
    dq_call)."""
    bh, tq, d = q.shape
    tk = k.shape[1]
    scale = 1.0 / math.sqrt(d)
    bq, bk = min(block_q, tq), min(block_k, tk)
    q = q * jnp.asarray(scale * LOG2E, q.dtype)  # base-2 fold (see _fwd)

    return pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal, bq=bq, bk=bk),
        grid=(bh, tk // bk, tq // bq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, 0)),  # q
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),  # k
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),  # v
            pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, 0)),  # do
            pl.BlockSpec((1, bq, 1), lambda b, j, i: (b, i, 0)),  # lse
            pl.BlockSpec((1, bq, 1), lambda b, j, i: (b, i, 0)),  # delta
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, out_dtype or k.dtype),
            jax.ShapeDtypeStruct(v.shape, out_dtype or v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        compiler_params=_params(),
        interpret=interpret_mode(),
    )(q, k, v, do, lse, delta)


def _fused_bwd_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dq_ref, dk_ref, dv_ref, dq_acc, dk_sc, dv_sc, *, scale, causal, bq, bk
):
    """dq+dk+dv from ONE s/p computation per (q, k) block pair (the split
    kernels compute s and do.v^T twice each — 7 block matmuls vs 5 here,
    and the exp2 softmax recompute twice vs once).

    Layout: grid (bh, k blocks, q blocks) with q innermost — dk/dv
    accumulate in [bk, d] VMEM scratch across the inner loop (written on
    its last step), while dq accumulates in a FULL-LENGTH [tq, d] f32
    scratch that persists across the whole grid.  Every step stores the
    RUNNING dq value of its q block to the output window: Pallas flushes
    the window once per step, earlier (incomplete) flushes are overwritten
    sequentially, and the LAST flush of each window — at the final k
    iteration — carries the completed sum.  No aliasing, no cross-step
    output reads: only documented Pallas semantics, so interpret mode and
    Mosaic agree (the r3-parked alias design did not — interpret re-reads
    pristine input on every visit).  Net HBM traffic is BELOW the split
    kernels' (nk bf16 dq flushes replace a full second operand pass), so
    the 7->5 matmul saving is pure win; the full-length accumulator is
    what gates dispatch via _FUSED_MAX_ACC_BYTES (VMEM)."""
    kj, qi = pl.program_id(1), pl.program_id(2)
    nq = pl.num_programs(2)
    rows = pl.ds(qi * bq, bq)

    @pl.when(qi == 0)
    def _init():
        dk_sc[:] = jnp.zeros_like(dk_sc)
        dv_sc[:] = jnp.zeros_like(dv_sc)

    def _step(masked: bool):
        def _compute():
            q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
            lse2 = lse_ref[0] * LOG2E
            delta = delta_ref[0]
            s = _dot_nt(q, k)  # base-2 logits (q pre-scaled)
            if masked:
                s = _mask(s, qi, kj, bq, bk)
            p = jnp.exp2(s - lse2)
            if masked:
                p = p * (s > NEG_INF / 2)
            dv_sc[:] = dv_sc[:] + _dot_tn(p, do)
            ds = p * (_dot_nt(do, v) - delta)
            dk_sc[:] = dk_sc[:] + _dot_tn(ds, q)
            contrib = _dot(ds, k) * scale
            # kj == 0 is visible from every q block (causal or not), so
            # the first visit (re)initialises this b's accumulator slice
            # (stale values from the previous b never leak).
            dq_acc[rows, :] = jnp.where(
                kj == 0, contrib, dq_acc[rows, :] + contrib
            )

        return _compute

    _causal_dispatch(_step, causal, qi, kj, bq, bk)

    # Store the RUNNING value every step (the window flushes regardless;
    # an unwritten buffer would flush garbage).  The last flush per q
    # block — at kj = nk-1 — is the complete sum.
    dq_ref[0] = dq_acc[rows, :].astype(dq_ref.dtype)

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0] = (dk_sc[:] * (1.0 / LOG2E)).astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[:].astype(dv_ref.dtype)


def fused_bwd_call(q, k, v, do, lse, delta, *, causal, block_q, block_k, out_dtype=None):
    """(dq, dk, dv) for one (q x k/v) pairing via the fused kernel (same
    contract as dq_call + dkv_call; ``out_dtype`` = f32 for ring
    partials).  Dispatch via ``_use_fused_bwd`` — the [tq, d] f32 dq
    accumulator lives in VMEM."""
    bh, tq, d = q.shape
    tk = k.shape[1]
    scale = 1.0 / math.sqrt(d)
    bq, bk = min(block_q, tq), min(block_k, tk)
    qs = q * jnp.asarray(scale * LOG2E, q.dtype)  # base-2 fold (see _fwd)

    return pl.pallas_call(
        functools.partial(
            _fused_bwd_kernel, scale=scale, causal=causal, bq=bq, bk=bk
        ),
        grid=(bh, tk // bk, tq // bq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, 0)),  # q
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),  # k
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),  # v
            pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, 0)),  # do
            pl.BlockSpec((1, bq, 1), lambda b, j, i: (b, i, 0)),  # lse
            pl.BlockSpec((1, bq, 1), lambda b, j, i: (b, i, 0)),  # delta
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, 0)),  # dq
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),  # dk
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),  # dv
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, out_dtype or q.dtype),
            jax.ShapeDtypeStruct(k.shape, out_dtype or k.dtype),
            jax.ShapeDtypeStruct(v.shape, out_dtype or v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((tq, d), jnp.float32),  # full-length dq accumulator
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        # Unlike the split kernels, BOTH k and q grid dims carry loop state
        # (dq_acc accumulates across kj with kj==0 as its reinit; dk/dv
        # scratch across qi) — only the batch*heads dim may be partitioned.
        compiler_params=compiler_params(
            ("parallel", "arbitrary", "arbitrary")
        ),
        interpret=interpret_mode(),
    )(qs, k, v, do, lse, delta)


def fwd_call(q, k, v, *, causal, block_q, block_k, out_dtype=None):
    """(o, lse) forward for one block pairing — ring attention's per-hop
    compute (lse enables exact cross-hop online-softmax merging).

    ``out_dtype``: set f32 when the result is a PARTIAL to be merged — the
    kernel's accumulator is f32 already, and rounding each hop's partial to
    bf16 before merging accumulates O(n_hops) quantization error."""
    return _fwd(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        out_dtype=out_dtype,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_bhd(q, k, v, causal, block_q, block_k):
    o, _ = _fwd(q, k, v, causal=causal, block_q=block_q, block_k=block_k)
    return o


def _flash_fwd_rule(q, k, v, causal, block_q, block_k):
    o, lse = _fwd(q, k, v, causal=causal, block_q=block_q, block_k=block_k)
    return o, (q, k, v, o, lse)


_flash_bhd.defvjp(_flash_fwd_rule, _bwd)


def _pick_block(t: int, want: int) -> int:
    """Largest divisor of ``t`` that is <= ``want``: any T works (e.g. 640 ->
    128 with the default 512), degrading to smaller tiles rather than raising
    at trace time.  Degenerate divisors (prime-ish T -> tiny tiles) get a
    warning: pad T to a multiple of 128 for MXU-shaped blocks."""
    from .common import largest_divisor

    b = largest_divisor(t, want)
    if b < 128 <= t:
        import warnings

        warnings.warn(
            f"flash_attention: seq len {t} has no block-sized divisor <= "
            f"{want}; using {b}-row tiles (slow on TPU). Pad T to a multiple "
            "of 128 for MXU-shaped blocks."
        )
    return b


def flash_viable(t: int) -> bool:
    """Shared auto-dispatch gate: flash pays off on TPU when the (per-shard)
    sequence tiles cleanly; awkward lengths degrade to tiny Pallas blocks,
    slower than XLA attention.  Used by both the non-ring auto path
    (models/transformer._use_flash) and the ring auto path
    (ops/attention.sequence_parallel_attention) so the two policies cannot
    drift."""
    return jax.default_backend() == "tpu" and t % 512 == 0


def flash_attention(
    q, k, v, *, causal: bool = False,
    block_q: int | None = None, block_k: int | None = None,
):
    """Drop-in for ``ops.attention.mha``: q/k/v [B, H, T, D] -> [B, H, T, D].

    Block sizes auto-shrink to the largest divisor of T (so any T traces);
    differentiable (custom FA2 VJP); interpreted on the CPU platform only.
    Default 1024x1024 tiles: the measured optimum of the v5e sweep (BASELINE.md;
    ~18% faster than 512x512, and 2048 tiles blow VMEM at D=64).  The
    DTX_FLASH_BQ / DTX_FLASH_BK env vars override the defaults — the
    in-step block-sweep knob (one fresh process per setting), read at
    trace time.
    """
    import os

    def _env_block(name: str) -> int:
        raw = os.environ.get(name, "1024")
        try:
            val = int(raw)
        except ValueError:
            raise ValueError(
                f"{name}={raw!r}: flash block overrides must be integers"
            ) from None
        if val < 128:
            # A sweep typo (0, '2k', 16) must not silently record a
            # pathological 1-row-tile run as a data point.
            raise ValueError(f"{name}={val}: flash blocks must be >= 128")
        return val

    if block_q is None:
        block_q = _env_block("DTX_FLASH_BQ")
    if block_k is None:
        block_k = _env_block("DTX_FLASH_BK")
    B, H, T, D = q.shape
    bq = _pick_block(T, block_q)
    bk = _pick_block(T, block_k)
    if "DTX_FLASH_BQ" in os.environ or "DTX_FLASH_BK" in os.environ:
        # Env overrides are read at TRACE time and do not key the jit cache:
        # an in-process sweep that re-sets them silently reuses the first
        # trace (ADVICE r4).  Each sweep point must be a fresh process;
        # this line only prints when a trace actually
        # happens, so a sweep log with a missing line is a stale-cache run.
        import sys

        print(
            f"flash_attention: traced with blocks bq={bq} bk={bk} (T={T})",
            file=sys.stderr,
        )
    fold = lambda x: x.reshape(B * H, T, D)
    o = _flash_bhd(fold(q), fold(k), fold(v), causal, bq, bk)
    return o.reshape(B, H, T, D)
