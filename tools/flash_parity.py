"""On-TPU fused-vs-split flash-backward parity + bitwise-determinism gate.

The r4 fused dq/dk/dv kernel's running-flush dq scheme ("store the RUNNING
accumulator value into the revisited dq output window every grid step; the
last write carries the sum") relies on Mosaic's documented last-write-wins
ordering for revisited output windows — exactly the semantics CPU interpret
mode cannot validate (ADVICE.md r4, medium).  This script is the hardware
test: it must PASS on the real chip before any bench trusts the fused path
and before the in-code default flips on.

Checks, at flagship-regime shapes (bf16, d=128, causal, nq/nk >= 4):
  1. fused vs split dq/dk/dv parity (bf16 tolerance, f32 compare)
  2. fused vs dense-mha reference parity (catches both-kernels-wrong)
  3. bitwise determinism: two identical fused grads agree exactly

Prints ONE JSON line {"parity_ok": bool, ...} and exits 0 (pass) / 1 (fail).
A run that sets DTX_FUSED_BWD=1 runs this first, and stays on the split
kernels (DTX_FUSED_BWD=0) if it fails.

Off-TPU it exits 2 without running a case — a CPU run says nothing about
Mosaic — unless ``--interpret`` declares the run an interpret-mode check of
the script itself (the record then says ``"interpret": true``).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def _qkv(b, h, t, d, dtype, seed=0):
    r = jax.random.split(jax.random.key(seed), 3)
    mk = lambda rr: (jax.random.normal(rr, (b, h, t, d), jnp.float32) * 0.5).astype(dtype)
    return mk(r[0]), mk(r[1]), mk(r[2])


def _grads(q, k, v, *, causal, fused):
    from distributed_tensorflow_examples_tpu.ops import flash_attention as F

    F._FUSED_BWD_OVERRIDE = fused

    def loss(q, k, v):
        return jnp.sum(F.flash_attention(q, k, v, causal=causal).astype(jnp.float32) ** 2)

    # jit argument differs only via the module flag, which is read at trace
    # time — use a fresh jit per setting so the cache cannot alias them.
    return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)


def _maxdiff(a, b):
    a = np.asarray(a, dtype=np.float32)
    b = np.asarray(b, dtype=np.float32)
    denom = max(np.abs(a).max(), np.abs(b).max(), 1e-6)
    return float(np.abs(a - b).max()), float(np.abs(a - b).max() / denom)


def run_case(b, h, t, d, dtype, causal, check_ref):
    from distributed_tensorflow_examples_tpu.ops import attention as A

    q, k, v = _qkv(b, h, t, d, dtype)
    gf = _grads(q, k, v, causal=causal, fused=True)
    gs = _grads(q, k, v, causal=causal, fused=False)
    gf2 = _grads(q, k, v, causal=causal, fused=True)

    rec = {"shape": [b, h, t, d], "dtype": str(dtype.__name__), "causal": causal}
    # bf16 operands: the two kernels order their f32 accumulations
    # differently, so agreement is bf16-level (same bound as the pytest
    # suite, tests/test_flash_attention.py::test_fused_bwd_bf16_matches_split).
    tol = 0.05 if dtype == jnp.bfloat16 else 2e-4
    ok = True
    for name, f, s in zip(("dq", "dk", "dv"), gf, gs):
        absd, reld = _maxdiff(f, s)
        rec[f"{name}_vs_split_rel"] = round(reld, 6)
        ok &= reld <= tol
    if check_ref:  # dense reference OOMs at long T; gate by caller
        gr = jax.jit(
            jax.grad(
                lambda q, k, v: jnp.sum(
                    A.mha(q, k, v, causal=causal).astype(jnp.float32) ** 2
                ),
                argnums=(0, 1, 2),
            )
        )(q, k, v)
        for name, f, r in zip(("dq", "dk", "dv"), gf, gr):
            _, reld = _maxdiff(f, r)
            rec[f"{name}_vs_ref_rel"] = round(reld, 6)
            ok &= reld <= max(tol, 0.05)
    bitwise = all(
        np.array_equal(
            np.asarray(a).view(np.uint16 if a.dtype == jnp.bfloat16 else np.uint8),
            np.asarray(c).view(np.uint16 if c.dtype == jnp.bfloat16 else np.uint8),
        )
        for a, c in zip(gf, gf2)
    )
    rec["bitwise_deterministic"] = bitwise
    rec["ok"] = bool(ok and bitwise)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="skip the T=8192 case")
    ap.add_argument(
        "--segmented", action="store_true",
        help="add a T=32768 case exercising the r5 segmented fused path "
        "(two 16384-row segments) — slow",
    )
    ap.add_argument(
        "--interpret", action="store_true",
        help="allow a CPU run: an interpret-mode check of this script, NOT "
        "the hardware gate",
    )
    args = ap.parse_args()

    platform = jax.devices()[0].platform
    if platform != "tpu" and not (args.interpret and platform == "cpu"):
        print(
            f"flash_parity: platform is {platform!r}, not 'tpu' — the "
            "hardware gate cannot pass here (--interpret for a CPU "
            "interpret-mode check)",
            file=sys.stderr,
        )
        sys.exit(2)
    from distributed_tensorflow_examples_tpu.utils import compile_cache

    compile_cache.enable()

    def case(b, h, t, d, dtype, causal, check_ref):
        # A kernel the compiler refuses fails ITS case with the error text
        # on record; the remaining cases still run (the text is the fact
        # the next decision needs, and a chip call is too dear to lose the
        # later cases to the first one).
        try:
            return run_case(b, h, t, d, dtype, causal, check_ref)
        except Exception as e:  # noqa: BLE001 — recorded, and fails the gate
            return {
                "shape": [b, h, t, d], "dtype": str(dtype.__name__),
                "causal": causal, "ok": False,
                "error": f"{type(e).__name__}: {e}"[:3000],
            }

    cases = [
        # small: cross-checked against the dense reference too
        case(1, 2, 2048, 128, jnp.bfloat16, True, check_ref=True),
        case(1, 2, 2048, 128, jnp.float32, False, check_ref=True),
    ]
    if not args.quick:
        # flagship regime: the shape a T=8192 train step of the flagship dispatches
        cases.append(case(1, 8, 8192, 128, jnp.bfloat16, True, check_ref=False))
    if args.segmented:
        # past the VMEM cap: auto-dispatch routes through fused_bwd_segmented
        # (h=1 bounds compile+run time; the mechanism is per-head-batch).
        cases.append(case(1, 1, 32768, 128, jnp.bfloat16, True, check_ref=False))
    ok = all(c["ok"] for c in cases)
    print(json.dumps({
        "parity_ok": ok, "platform": platform,
        "interpret": platform != "tpu", "cases": cases,
    }))
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
