"""The spread of a cell's runs, as the bound's rule wants it.

    python3 benchmarks/tools/spread.py chiprun_out/sets_<cell>.jsonl

The file holds one result line per run, two sets of six in order.  For each
metric and set: median, and the distance between the first and third
quartile (``statistics.quantiles(n=4)``) as a share of the median.
"""

from __future__ import annotations

import json
import statistics
import sys


def spread(values: list[float]) -> float:
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main(path: str, per_set: int = 6) -> int:
    with open(path) as f:
        rows = [json.loads(line) for line in f if line.startswith("{")]
    rows = [r for r in rows if "busy_s" not in r.get("device", {})]
    print(f"{len(rows)} runs; correct: {sum(bool(r['correct']) for r in rows)}")
    for name in rows[0]["metrics"]:
        line = [name]
        for s in range(0, len(rows), per_set):
            v = [r["metrics"][name]["value"] for r in rows[s: s + per_set]]
            if len(v) >= 2:
                line.append(f"median {statistics.median(v):.6g} spread {100 * spread(v):.3f}% "
                            f"[{min(v):.6g}..{max(v):.6g}] n={len(v)}")
        print("  " + " | ".join(line))
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:2]))
