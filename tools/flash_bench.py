"""Flash-attention benchmark: repo Pallas kernel vs the strongest on-disk
competitors, fwd AND bwd (VERDICT r1 item 2).

Competitors:
- ``ours``    — distributed_tensorflow_examples_tpu.ops.flash_attention
- ``jaxpal``  — jax.experimental.pallas.ops.tpu.flash_attention (the tuned
  kernel JAX ships; the bar any custom kernel must meet)
- ``xla``     — ops.attention.mha (naive jnp attention, XLA-fused); OOMs at
  long T (materialises [T, T] scores), skipped there

Timing discipline (see bench.py): on-device operands, scalar host fetch to
close each window, best of 2 windows.

Usage:
  python tools/flash_bench.py                    # headline table, T=2k/8k/32k
  python tools/flash_bench.py --sweep --t 8192   # block-size sweep (ours)
  python tools/flash_bench.py --markdown         # BASELINE.md-ready rows
"""

from __future__ import annotations

import argparse
import functools
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp


def _fetch(x):
    """Force a host sync by fetching one scalar."""
    leaf = jax.tree.leaves(x)[0]
    return float(jnp.asarray(leaf).astype(jnp.float32).ravel()[0])


def timeit(fn, *args, steps: int = 10, warm: int = 3) -> float:
    out = None
    for _ in range(warm):
        out = fn(*args)
    _fetch(out)
    best = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        for _ in range(steps):
            out = fn(*args)
        _fetch(out)
        best = min(best, (time.perf_counter() - t0) / steps)
    return best


def make_qkv(b, h, t, d, dtype=jnp.bfloat16):
    ks = jax.random.split(jax.random.key(0), 3)
    mk = lambda k: (jax.random.normal(k, (b, h, t, d), jnp.float32) * 0.5).astype(dtype)
    return mk(ks[0]), mk(ks[1]), mk(ks[2])


def attn_tflops(b, h, t, d, *, causal: bool, bwd: bool) -> float:
    """2 matmuls fwd (QK^T, PV), 5 bwd-equivalent; causal halves the work."""
    per_mm = 2.0 * t * t * d
    mms = 2.0 + (5.0 if bwd else 0.0)
    f = b * h * mms * per_mm * (0.5 if causal else 1.0)
    return f / 1e12


def bench_ours(q, k, v, *, causal, bwd, block_q=512, block_k=512):
    from distributed_tensorflow_examples_tpu.ops.flash_attention import flash_attention

    f = functools.partial(flash_attention, causal=causal, block_q=block_q, block_k=block_k)
    if not bwd:
        g = jax.jit(f)
        return timeit(g, q, k, v)
    loss = jax.jit(jax.grad(lambda q, k, v: jnp.sum(f(q, k, v).astype(jnp.float32) ** 2), argnums=(0, 1, 2)))
    return timeit(loss, q, k, v)


def bench_jaxpal(q, k, v, *, causal, bwd):
    from jax.experimental.pallas.ops.tpu.flash_attention import flash_attention as jfa

    d = q.shape[-1]
    f = functools.partial(jfa, causal=causal, sm_scale=1.0 / math.sqrt(d))
    if not bwd:
        g = jax.jit(f)
        return timeit(g, q, k, v)
    loss = jax.jit(jax.grad(lambda q, k, v: jnp.sum(f(q, k, v).astype(jnp.float32) ** 2), argnums=(0, 1, 2)))
    return timeit(loss, q, k, v)


def bench_xla(q, k, v, *, causal, bwd):
    from distributed_tensorflow_examples_tpu.ops.attention import mha

    f = functools.partial(mha, causal=causal)
    if not bwd:
        g = jax.jit(f)
        return timeit(g, q, k, v)
    loss = jax.jit(jax.grad(lambda q, k, v: jnp.sum(f(q, k, v).astype(jnp.float32) ** 2), argnums=(0, 1, 2)))
    return timeit(loss, q, k, v)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--b", type=int, default=4)
    ap.add_argument("--h", type=int, default=8)
    ap.add_argument("--d", type=int, default=64)
    ap.add_argument("--t", type=int, default=0, help="single T (0 = 2k/8k/32k suite)")
    ap.add_argument("--causal", default=True, action=argparse.BooleanOptionalAction)
    ap.add_argument("--sweep", action="store_true", help="block-size sweep for ours")
    ap.add_argument(
        "--fused", choices=["auto", "0", "1"], default="auto",
        help="fused dq/dk/dv backward: auto = the nq/nk>=4 dispatch gate, "
        "0/1 force split/fused (r4 A/B comparisons)",
    )
    ap.add_argument("--markdown", action="store_true")
    ap.add_argument("--steps", type=int, default=10)
    args = ap.parse_args()
    from distributed_tensorflow_examples_tpu.utils import compile_cache

    compile_cache.enable()

    if args.fused != "auto":
        from distributed_tensorflow_examples_tpu.ops import flash_attention as F

        F._FUSED_BWD_OVERRIDE = args.fused == "1"

    ts = [args.t] if args.t else [2048, 8192, 32768]

    if args.sweep:
        t = args.t or 8192
        q, k, v = make_qkv(args.b, args.h, t, args.d)
        print(f"# block sweep  T={t} B={args.b} H={args.h} D={args.d} causal={args.causal}")
        for bq in (256, 512, 1024, 2048):
            for bk in (256, 512, 1024, 2048):
                if bq > t or bk > t:
                    continue
                try:
                    dt_f = bench_ours(q, k, v, causal=args.causal, bwd=False, block_q=bq, block_k=bk)
                    dt_b = bench_ours(q, k, v, causal=args.causal, bwd=True, block_q=bq, block_k=bk)
                except Exception as e:  # VMEM OOM at big blocks
                    print(f"bq={bq:5d} bk={bk:5d}  FAIL {type(e).__name__}")
                    continue
                tf_f = attn_tflops(args.b, args.h, t, args.d, causal=args.causal, bwd=False) / dt_f
                tf_b = attn_tflops(args.b, args.h, t, args.d, causal=args.causal, bwd=True) / dt_b
                print(
                    f"bq={bq:5d} bk={bk:5d}  fwd {dt_f*1e3:7.2f} ms ({tf_f:5.1f} TF/s)"
                    f"  fwd+bwd {dt_b*1e3:7.2f} ms ({tf_b:5.1f} TF/s)"
                )
        return

    rows = []
    for t in ts:
        q, k, v = make_qkv(args.b, args.h, t, args.d)
        row = {"T": t}
        for name, fn in (("ours", bench_ours), ("jaxpal", bench_jaxpal), ("xla", bench_xla)):
            for bwd in (False, True):
                key = f"{name}_{'bwd' if bwd else 'fwd'}"
                if name == "xla" and t > 16384:
                    row[key] = None  # [T,T] scores OOM
                    continue
                try:
                    dt = fn(q, k, v, causal=args.causal, bwd=bwd)
                    row[key] = dt
                except Exception as e:
                    print(f"# {key} T={t} failed: {type(e).__name__}: {e}", file=sys.stderr)
                    row[key] = None
        rows.append(row)
        print(f"# done T={t}: " + " ".join(
            f"{k}={v*1e3:.2f}ms" if isinstance(v, float) else f"{k}=-"
            for k, v in row.items() if k != "T"
        ))

    hdr = ["T", "ours fwd", "jax-pallas fwd", "XLA fwd", "ours fwd+bwd", "jax-pallas fwd+bwd", "XLA fwd+bwd"]
    keys = ["ours_fwd", "jaxpal_fwd", "xla_fwd", "ours_bwd", "jaxpal_bwd", "xla_bwd"]
    if args.markdown:
        print("| " + " | ".join(hdr) + " |")
        print("|" + "---|" * len(hdr))
    for row in rows:
        cells = [str(row["T"])]
        for key in keys:
            v = row[key]
            if v is None:
                cells.append("OOM" if "xla" in key else "–")
            else:
                bwd = key.endswith("bwd")
                tf = attn_tflops(args.b, args.h, row["T"], args.d, causal=args.causal, bwd=bwd) / v
                cells.append(f"{v*1e3:.2f} ms ({tf:.1f} TF/s)")
        print(("| " + " | ".join(cells) + " |") if args.markdown else "  ".join(cells))


if __name__ == "__main__":
    main()
