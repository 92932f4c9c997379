"""Pallas flash-attention kernel: forward + FA2 backward parity against the
reference mha (interpret mode on CPU — same kernel code that compiles via
Mosaic on TPU)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from distributed_tensorflow_examples_tpu.ops import attention as A
from distributed_tensorflow_examples_tpu.ops.flash_attention import flash_attention


def _qkv(b=1, h=2, t=64, d=16, seed=0):
    r = jax.random.split(jax.random.key(seed), 3)
    mk = lambda rr: jax.random.normal(rr, (b, h, t, d), jnp.float32)
    return mk(r[0]), mk(r[1]), mk(r[2])


@pytest.mark.parametrize("causal", [False, True])
def test_flash_forward_matches_mha(causal):
    q, k, v = _qkv()
    ref = A.mha(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_backward_matches_mha(causal):
    q, k, v = _qkv(t=32, d=8)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal, block_q=8, block_k=8) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(A.mha(q, k, v, causal=causal) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4)


def test_flash_indivisible_seq_auto_blocks():
    # T=48 with requested 32-blocks: auto-shrinks to the largest divisor
    # (24 or 16) instead of raising — any T must trace (ADVICE round 1).
    q, k, v = _qkv(t=48)
    ref = A.mha(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_flash_jits():
    q, k, v = _qkv(t=32)
    f = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True, block_q=16, block_k=16))
    out = f(q, k, v)
    assert np.isfinite(np.asarray(out)).all()


@pytest.mark.parametrize("causal", [False, True])
def test_fused_bwd_matches_split_kernels_and_reference(causal, monkeypatch):
    """r4 fused dq+dk+dv kernel (one s/p compute per block pair, dq
    accumulated in a full-length VMEM scratch with running flushes): grads
    must match BOTH the split dq/dkv kernels and the dense mha reference,
    at a shape in its nq/nk >= 4 dispatch regime."""
    from distributed_tensorflow_examples_tpu.ops import flash_attention as F

    q, k, v = _qkv(b=1, h=2, t=128, d=8, seed=3)

    def loss(fn):
        return lambda q, k, v: jnp.sum(
            fn(q, k, v, causal=causal, block_q=16, block_k=16) ** 2
        )

    monkeypatch.setattr(F, "_FUSED_BWD_OVERRIDE", True)
    g_fused = jax.grad(loss(F.flash_attention), argnums=(0, 1, 2))(q, k, v)
    monkeypatch.setattr(F, "_FUSED_BWD_OVERRIDE", False)
    g_split = jax.grad(loss(F.flash_attention), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(
        lambda q, k, v: jnp.sum(A.mha(q, k, v, causal=causal) ** 2),
        argnums=(0, 1, 2),
    )(q, k, v)
    for gf, gs, gr in zip(g_fused, g_split, g_ref):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gs), rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr), rtol=2e-4, atol=2e-4)


def test_fused_bwd_deterministic(monkeypatch):
    """Two identical fused-backward runs must agree BITWISE.  Off-TPU this
    exercises interpret mode (sequential, so it cannot catch hardware
    races); ON TPU — where the benches run it — run-to-run jitter here
    would expose a Mosaic pipelining/ordering bug in the running-flush dq
    scheme.  The hardware-meaningful run is the bench-day TPU pass
    (BASELINE.md records it)."""
    from distributed_tensorflow_examples_tpu.ops import flash_attention as F

    monkeypatch.setattr(F, "_FUSED_BWD_OVERRIDE", True)
    q, k, v = _qkv(b=1, h=4, t=256, d=16, seed=7)
    grad = jax.jit(
        jax.grad(
            lambda q, k, v: jnp.sum(
                F.flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
            ),
            argnums=(0, 1, 2),
        )
    )
    a = grad(q, k, v)
    b = grad(q, k, v)
    for x, y in zip(a, b):
        assert np.array_equal(np.asarray(x), np.asarray(y))


def test_fused_bwd_dispatch_gate(monkeypatch):
    from distributed_tensorflow_examples_tpu.ops import flash_attention as F
    from distributed_tensorflow_examples_tpu.ops.flash_attention import _use_fused_bwd

    # With the hardware-validation latch open, the nq/nk >= 4 regime gate:
    monkeypatch.delenv("DTX_FUSED_BWD", raising=False)
    monkeypatch.setattr(F, "_FUSED_BWD_VALIDATED", True)
    assert _use_fused_bwd(4, 4, 4096, 128)
    assert _use_fused_bwd(16, 16, 16384, 128)
    assert not _use_fused_bwd(2, 2, 2048, 128)   # T=2048 flagship @1024 tiles
    assert not _use_fused_bwd(8, 2, 8192, 128)
    # VMEM cap on the [tq, d] accumulator: T=32768 @ d=128 stays split.
    assert not _use_fused_bwd(32, 32, 32768, 128)
    # DTX_FUSED_BWD=0 forces split even when the latch is open:
    monkeypatch.setenv("DTX_FUSED_BWD", "0")
    assert not _use_fused_bwd(4, 4, 4096, 128)


def test_fused_bwd_validation_latch(monkeypatch):
    """ADVICE r4 (medium): until tools/flash_parity.py passes on real
    Mosaic, the in-regime shapes must NOT auto-dispatch to the fused kernel
    — opt-in is per-process via DTX_FUSED_BWD=1 (set after running the
    parity gate)."""
    from distributed_tensorflow_examples_tpu.ops import flash_attention as F
    from distributed_tensorflow_examples_tpu.ops.flash_attention import _use_fused_bwd

    monkeypatch.setattr(F, "_FUSED_BWD_VALIDATED", False)
    monkeypatch.delenv("DTX_FUSED_BWD", raising=False)
    assert not _use_fused_bwd(4, 4, 4096, 128)
    monkeypatch.setenv("DTX_FUSED_BWD", "1")
    assert _use_fused_bwd(4, 4, 4096, 128)
    assert not _use_fused_bwd(2, 2, 2048, 128)  # opt-in keeps the regime gate
    # The explicit override (tests) beats everything:
    monkeypatch.setenv("DTX_FUSED_BWD", "0")
    monkeypatch.setattr(F, "_FUSED_BWD_OVERRIDE", True)
    assert _use_fused_bwd(2, 2, 2048, 128)


def test_fused_bwd_bf16_matches_split(monkeypatch):
    """The flagship runs bf16 operands; the fused kernel's bf16 handling
    (native-dtype MXU inputs, f32 accumulation, bf16 dq output flushes)
    must agree with the split kernels at bf16 within bf16 tolerance."""
    from distributed_tensorflow_examples_tpu.ops import flash_attention as F

    r = jax.random.split(jax.random.key(11), 4)
    mk = lambda rr: jax.random.normal(rr, (1, 2, 128, 16), jnp.bfloat16)
    q, k, v = mk(r[0]), mk(r[1]), mk(r[2])

    def loss(q, k, v):
        return jnp.sum(
            F.flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
            .astype(jnp.float32) ** 2
        )

    monkeypatch.setattr(F, "_FUSED_BWD_OVERRIDE", True)
    g_fused = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    monkeypatch.setattr(F, "_FUSED_BWD_OVERRIDE", False)
    g_split = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for gf, gs in zip(g_fused, g_split):
        assert gf.dtype == jnp.bfloat16 and gs.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(gf, dtype=np.float32), np.asarray(gs, dtype=np.float32),
            rtol=0.05, atol=0.05,
        )


def test_fused_bwd_regime_shape_sweep(monkeypatch):
    """r5 hardening before the hardware window: fused-vs-reference parity
    across the dispatch regime's corners — uneven nq != nk grids, rectangular
    blocks, both dtypes — in one bounded test.  The fixed-shape parity tests
    cover the center of the regime; the corners are where a grid-indexing
    bug in the running-flush dq scheme would hide."""
    from distributed_tensorflow_examples_tpu.ops import flash_attention as F

    monkeypatch.setattr(F, "_FUSED_BWD_OVERRIDE", True)
    cases = [
        # (t, d, bq, bk, causal, dtype): nq=t/bq, nk=t/bk — all >= 4
        (128, 8, 32, 16, True, jnp.float32),    # nq=4, nk=8 (rectangular)
        (128, 8, 16, 32, False, jnp.float32),   # nq=8, nk=4
        (256, 16, 32, 32, True, jnp.float32),   # nq=nk=8
        (192, 8, 48, 16, True, jnp.float32),    # non-power-of-two blocks
        (128, 16, 16, 16, True, jnp.bfloat16),  # bf16 corner, nq=nk=8
    ]
    for i, (t, d, bq, bk, causal, dtype) in enumerate(cases):
        r = jax.random.split(jax.random.key(100 + i), 3)
        mk = lambda rr: (jax.random.normal(rr, (1, 2, t, d), jnp.float32) * 0.5).astype(dtype)
        q, k, v = mk(r[0]), mk(r[1]), mk(r[2])

        def loss(q, k, v):
            return jnp.sum(
                F.flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk)
                .astype(jnp.float32) ** 2
            )

        def loss_ref(q, k, v):
            return jnp.sum(A.mha(q, k, v, causal=causal).astype(jnp.float32) ** 2)

        gf = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        tol = 0.06 if dtype == jnp.bfloat16 else 3e-4
        for name, a, b in zip(("dq", "dk", "dv"), gf, gr):
            np.testing.assert_allclose(
                np.asarray(a, dtype=np.float32), np.asarray(b, dtype=np.float32),
                rtol=tol, atol=tol,
                err_msg=f"case {i} {name} t={t} d={d} bq={bq} bk={bk} causal={causal} {dtype}",
            )


@pytest.mark.parametrize("causal", [False, True])
def test_fused_bwd_segmented_matches_reference(causal, monkeypatch):
    """r5 segmented fused backward (T past the VMEM cap): shrink the cap so
    a small T segments (here 4 segments of 64 rows), then demand parity
    with BOTH the split kernels and the dense reference.  The diagonal
    calls run local causal (== global: equal offsets), prefix calls run
    full-visibility — a wrong offset/mask would fail loudly here."""
    from distributed_tensorflow_examples_tpu.ops import flash_attention as F

    monkeypatch.setattr(F, "_FUSED_BWD_OVERRIDE", True)
    # cap -> 64 rows at d=8: T=256 with bq=bk=16 segments into 4 x 64.
    monkeypatch.setattr(F, "_FUSED_MAX_ACC_BYTES", 64 * 8 * 4)
    q, k, v = _qkv(b=1, h=2, t=256, d=8, seed=5)

    def loss(fn):
        return lambda q, k, v: jnp.sum(
            fn(q, k, v, causal=causal, block_q=16, block_k=16) ** 2
        )

    assert F._fused_segment_rows(256, 8, 16, 16) == 64
    g_seg = jax.grad(loss(F.flash_attention), argnums=(0, 1, 2))(q, k, v)
    monkeypatch.setattr(F, "_FUSED_BWD_OVERRIDE", False)
    g_split = jax.grad(loss(F.flash_attention), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(
        lambda q, k, v: jnp.sum(A.mha(q, k, v, causal=causal) ** 2),
        argnums=(0, 1, 2),
    )(q, k, v)
    for name, gs, gp, gr in zip(("dq", "dk", "dv"), g_seg, g_split, g_ref):
        np.testing.assert_allclose(
            np.asarray(gs), np.asarray(gp), rtol=3e-5, atol=3e-5, err_msg=name
        )
        np.testing.assert_allclose(
            np.asarray(gs), np.asarray(gr), rtol=3e-4, atol=3e-4, err_msg=name
        )


def test_fused_segment_rows_picker():
    from distributed_tensorflow_examples_tpu.ops.flash_attention import (
        _FUSED_MAX_ACC_BYTES, _fused_segment_rows,
    )

    # Production case: T=32768 at d=128 halves into in-cap 16384 segments.
    assert _fused_segment_rows(32768, 128, 1024, 1024) == 16384
    # T=65536 -> 16384 (quarters); the picker returns the LARGEST fit.
    assert _fused_segment_rows(65536, 128, 1024, 1024) == 16384
    # No valid segmentation (prime split impossible below cap) -> 0.
    assert _fused_segment_rows(3 * 1024, 4096, 1024, 1024) == 0
    # In-cap shapes never reach the picker via _bwd, but it still behaves.
    assert _fused_segment_rows(8192, 128, 1024, 1024) == 4096


def test_fused_bwd_segmented_deterministic(monkeypatch):
    """Segmented path: two identical runs agree bitwise (same contract as
    the single-call kernel — the outside-kernel f32 accumulation is a
    fixed-order jnp program)."""
    from distributed_tensorflow_examples_tpu.ops import flash_attention as F

    monkeypatch.setattr(F, "_FUSED_BWD_OVERRIDE", True)
    monkeypatch.setattr(F, "_FUSED_MAX_ACC_BYTES", 64 * 8 * 4)
    q, k, v = _qkv(b=1, h=2, t=256, d=8, seed=9)
    grad = jax.jit(
        jax.grad(
            lambda q, k, v: jnp.sum(
                F.flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
            ),
            argnums=(0, 1, 2),
        )
    )
    a = grad(q, k, v)
    b = grad(q, k, v)
    for x, y in zip(a, b):
        assert np.array_equal(np.asarray(x), np.asarray(y))


def test_flash_parity_case_runs_in_interpret_mode():
    """run_case at a tiny shape: parity + bitwise determinism hold in
    interpret mode (the TPU run reuses this exact code path)."""
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools"))
    try:
        import flash_parity
    finally:
        sys.path.pop(0)

    rec = flash_parity.run_case(1, 2, 128, 16, jnp.float32, True, check_ref=True)
    assert rec["ok"], rec
    assert rec["bitwise_deterministic"]
    rec = flash_parity.run_case(1, 2, 128, 16, jnp.bfloat16, False, check_ref=False)
    assert rec["ok"], rec
