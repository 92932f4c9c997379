"""Read whether a serve cell's comparison sees a wrong weight: serve the cell
with some leaves of its seeded tree MIXED UP - each leaf whose path matches
``--roll`` rolled by one along its first axis, so that of a stack of experts
each computes with its neighbour's matrix - and compare the served tokens
with the reference of the weights as they should be.

    python3 benchmarks/tools/planted.py --workload deepseek-v2-serve-gen \
        --seeds 11 --roll 'layer_\\d+/moe/down' --seconds 48

Not part of a benchmark run.  Prints one JSON line per seed (``correct``
should read false: a planted fault that comes out as correct is one the
cell's limit does not guard) and writes them to
``chiprun_out/planted_<workload>.jsonl``.  ``--control`` also reads the
control's gap on the same sample, as ``readings.py`` does.
"""

from __future__ import annotations

import argparse
import faulthandler
import json
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def rolled(tree_fn, pattern: str, seen: list | None = None):
    """``tree_fn`` with every leaf whose ``/``-joined path matches
    ``pattern`` rolled by one along its first axis; the matched paths are
    appended to ``seen``."""
    import jax
    import jax.numpy as jnp

    want = re.compile(pattern)

    def planted(key):
        def one(path, leaf):
            name = "/".join(str(getattr(p, "key", p)) for p in path)
            if not want.fullmatch(name):
                return leaf
            if seen is not None:
                seen.append(name)
            return jnp.roll(leaf, 1, axis=0)

        return jax.tree_util.tree_map_with_path(one, tree_fn(key))

    return planted


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--roll", required=True, help="a regular expression over leaf paths")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--control", default=None, help="a precision, read on the same sample")
    args = ap.parse_args(argv)
    from benchmarks import run as run_mod
    from benchmarks.harness import manifest, report

    run_mod.place_compile_cache()
    cell = manifest.Cell(args.workload)
    family, build, seen = cell.family, cell.family.build, []

    def build_planted(config, *a, **kw):
        cfg, tree_fn = build(config, *a, **kw)
        return cfg, rolled(tree_fn, args.roll, seen)

    family.build = build_planted
    runner = report.runner_for(cell)
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"planted_{args.workload}.jsonl"), "a") as f:
        for seed in (int(s) for s in args.seeds.split(",")):
            faulthandler.dump_traceback_later(600, exit=True)
            del seen[:]
            outcome = runner.run(
                cell, seed, args.seconds, False, time.monotonic(), control=args.control)
            if not seen:
                raise SystemExit(f"no leaf of the tree matches {args.roll!r}")
            row = {"workload": args.workload, "seed": seed, "roll": args.roll,
                   "leaves": len(set(seen)), "correct": outcome["correct"],
                   "compared": outcome["check"], "failed": outcome["failed"],
                   "attempted": outcome["attempted"],
                   "end_to_end": outcome["end_to_end"]}
            print(json.dumps(row), flush=True)
            f.write(json.dumps(row) + "\n")
            f.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
