"""Read what a limit is set from: the program's number and the control's,
seed after seed, in one warm process, at the cell's own size and load.

    python3 benchmarks/tools/readings.py --workload <name> --seeds 11,12,13 --seconds 20

Not part of a benchmark run.  Prints one JSON line per seed and writes them
to ``chiprun_out/readings_<workload>.jsonl``.
"""

from __future__ import annotations

import argparse
import faulthandler
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--control", default=None, help="default: the configuration's")
    args = ap.parse_args(argv)
    from benchmarks import run as run_mod
    from benchmarks.harness import manifest, report

    run_mod.place_compile_cache()
    cell = manifest.Cell(args.workload)
    control = args.control or cell.config["precision"]["control"]
    runner = report.runner_for(cell)
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"readings_{args.workload}.jsonl"), "a") as f:
        for seed in (int(s) for s in args.seeds.split(",")):
            faulthandler.dump_traceback_later(600, exit=True)
            outcome = runner.run(
                cell, seed, args.seconds, False, time.monotonic(), control=control)
            row = {"workload": args.workload, "seed": seed, "control": control,
                   "compared": outcome["check"], "failed": outcome["failed"],
                   "attempted": outcome["attempted"],
                   "end_to_end": outcome["end_to_end"]}
            print(json.dumps(row), flush=True)
            f.write(json.dumps(row) + "\n")
            f.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
