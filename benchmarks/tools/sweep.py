"""Find the knee of an open-loop cell once: one replica, one rate after
another, each on the mix's own schedule at that rate (lead-in, ring, order)
for one window.

    python3 benchmarks/tools/sweep.py --workload cgpt13b-serve-chat --rates 0.8,1.0,1.2 --seconds 48

Not part of a benchmark run.  Prints one JSON line per rate and appends
them to ``chiprun_out/sweep_<workload>.jsonl``.
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=48.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    from benchmarks import run as run_mod
    from benchmarks.harness import manifest, report, serve_cell, stats

    run_mod.place_compile_cache()
    cell = manifest.Cell(args.workload)
    phases = report.Phases(time.monotonic())
    run_dir = tempfile.mkdtemp(prefix="bench_sweep_")
    server = serve_cell.start_replica(
        cell, args.seed, os.path.join(run_dir, "registry"), phases)
    out_path = os.path.join(ROOT, "chiprun_out", f"sweep_{args.workload}.jsonl")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    try:
        serve_cell.warm_up(server, phases)
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            c = copy.copy(cell)
            c.traffic = tr = copy.deepcopy(cell.traffic)
            tr["arrivals"]["rate_per_s"] = rate
            sub = os.path.join(run_dir, f"rate{i}")
            os.makedirs(sub)
            schedule = serve_cell.schedule_for(c, server.port, args.seed + i, args.seconds)
            gen = serve_cell.start_generator(sub)
            ev = serve_cell.measure(
                c, server, gen, schedule, args.seconds, False, sub, phases,
                time.monotonic())
            gen[0].wait()
            s = serve_cell.client_series(ev["records"], ev["w0"], ev["w1"])
            half = ev["w0"] + (ev["w1"] - ev["w0"]) / 2
            late = serve_cell.client_series(ev["records"], half, ev["w1"])
            row = {
                "rate_per_s": rate, "attempted": s["attempted"],
                "completed": s["completed"], "failed": s["failed"],
                "ttft_p50_ms": stats.percentile(s["ttft_ms"], 50) if s["ttft_ms"] else None,
                "ttft_p50_ms_second_half": stats.percentile(late["ttft_ms"], 50) if late["ttft_ms"] else None,
                "itl_p95_ms": stats.percentile(s["itl_ms"], 95) if s["itl_ms"] else None,
                "tokens_per_s": s["tokens"] / (ev["w1"] - ev["w0"]),
                "queued_at_end": ev["counters"]["end"]["decode_sessions_queued"],
                "slots_active_at_end": ev["counters"]["end"]["decode_slots_active"],
            }
            print(json.dumps(row), flush=True)
            with open(out_path, "a") as f:
                f.write(json.dumps(row) + "\n")
            # Let the sessions the rate left behind drain before the next.
            while server.stats()["decode_slots_active"]:
                time.sleep(0.5)
    finally:
        server.stop()
        shutil.rmtree(run_dir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
