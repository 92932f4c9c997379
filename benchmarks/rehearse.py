"""Rehearse a cell's control flow on the CPU at a tiny size.

    JAX_PLATFORMS=cpu python3 benchmarks/rehearse.py --workload <name> [--seed 1] [--seconds 3] [--trace 0]

The same runners as ``run.py`` (child-process generator included) on a
shrunken configuration and traffic.  It prints which metrics a real run
would report and whether the comparison passed at the rehearsal's own
loose limits - never a value: a CPU number is not a device number.
"""

from __future__ import annotations

import time

T_PROC0 = time.monotonic()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

os.environ["JAX_PLATFORMS"] = "cpu"

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def shrink(cell):
    """The cell at a size a CPU runs in seconds; every branch the real
    size takes is still taken.  The configuration's size is its family's
    (``tiny``), with the limits read at that size; the traffic's is here."""
    cell.config = cell.family.tiny(cell.config)
    cell.traffic = tr = copy.deepcopy(cell.traffic)
    rehearsal = cell.config["rehearsal"]
    tr["trace_s"] = 1.0
    tr["correct"]["limits"] = {k: rehearsal["limits"][k] for k in tr["correct"]["limits"]}
    if cell.path == "serve":
        for key in ("prompt_len", "output_len"):
            for k in ("median", "min", "max"):
                if k in tr[key]:
                    tr[key][k] = max(2, tr[key][k] // 8)
        tr["server"]["decode_max_len"] = cell.family.max_len(cell.config)
        tr["lead_s"] = 1.0
        if "arrivals" in tr:
            tr["arrivals"].update(rate_per_s=4.0, period_s=3.0)
    else:
        tr["data"].update(rehearsal["data"])
        tr["global_batch"] = 8
    return cell


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from benchmarks.harness import manifest, report

    cell = shrink(manifest.Cell(args.workload))
    # No peak is published for a CPU; the rehearsal prints no value, so a
    # stand-in lets the arithmetic run.
    manifest.peak_for = lambda kind: {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}
    outcome = report.runner_for(cell).run(cell, args.seed, args.seconds, bool(args.trace), T_PROC0)
    names = [e["name"] for e in cell.end_to_end
             if outcome["end_to_end"].get(e["name"]) is not None]
    print(json.dumps({
        "rehearsal": True, "platform": "cpu", "workload": cell.name,
        "correct": outcome["correct"], "attempted": outcome["attempted"],
        "failed": outcome["failed"], "would_report": names,
        "compared": outcome["check"],
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
