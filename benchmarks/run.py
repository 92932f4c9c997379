"""Run one cell of the benchmark once, on the chip.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process each time.  Earlier lines say what set-up did and which
numbers were compared with which limits; the last line of standard output
is one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``
(the cell's end-to-end metrics with ``--trace 0``, its per-layer metrics
with ``--trace 1``), ``device`` and, traced, ``breakdown``.  Without a TPU,
or with fewer chips than the cell asks for, it prints no result and exits
non-zero.
"""

from __future__ import annotations

import time

T_PROC0 = time.monotonic()

import argparse  # noqa: E402
import faulthandler  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, ROOT)

NO_CHIP = 3


def place_compile_cache() -> str:
    """Where ``JAX_COMPILATION_CACHE_DIR`` says, else one fixed directory
    in the checkout; set before JAX is imported, so that the program's own
    placement finds it set and sets nothing."""
    path = os.environ.setdefault(
        "JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".jax_cache")
    )
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.2")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    # A run that hangs says where, and ends: 1200 s is a first run's allowance.
    faulthandler.dump_traceback_later(1150, exit=True)

    if not os.path.isdir(os.path.join(ROOT, "distributed_tensorflow_examples_tpu")):
        print("bench: the system under test is not in this checkout", file=sys.stderr)
        return NO_CHIP
    from benchmarks.harness import manifest, report

    cell = manifest.Cell(args.workload)
    place_compile_cache()
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(
            f"bench: {cell.name} needs {cell.chips} TPU chip(s); JAX reports "
            f"{len(devs)} x {devs[0].platform} - nothing was run", file=sys.stderr,
        )
        return NO_CHIP
    manifest.peak_for(devs[0].device_kind)

    outcome = report.runner_for(cell).run(cell, args.seed, args.seconds, bool(args.trace), T_PROC0)
    line = report.result(cell, outcome, bool(args.trace))
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
