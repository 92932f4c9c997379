"""Transport perf regression gate (r7 satellite; data-service rows r8), run
by tier-1 tests on the rigs' quick rows and by an operator by hand.

Compares a ``tools/ps_transport_bench.py`` or ``tools/data_service_bench.py``
result against its checked-in host baseline (``tools/ps_transport_baseline
.json`` / ``tools/data_service_baseline.json`` — auto-selected from the
result's ``metric`` field) and flags regressions, so a future PR cannot
silently re-introduce the copy-per-send / O(n²)-receive framing r7 removed,
or regress remote batch streaming past the disaggregation acceptance bound.

Three kinds of checks, all deliberately host-portable:

1. **Normalized throughput** — every ``*_frac_memcpy`` row (socket MB/s as
   a fraction of the host's own memcpy bandwidth) must stay above
   ``tolerance`` x the baseline fraction.  Raw MB/s differs 10x across
   boxes; the memcpy fraction is stable, and a copy-per-send regression
   halves it no matter the host.
2. **if-newer ratio** — an unchanged-step ``get_if_newer`` round trip must
   be at least ``--if-newer-ratio`` x faster than a full large pull,
   computed entirely from the RESULT file (no cross-host compare at all):
   the check that the versioned pull still moves O(header), not O(params).
3. **remote/local ratio** (data-service results) — remote batch streaming
   must deliver at least ``--remote-local-ratio`` (default 0.5: the ISSUE 3
   "within 2x" acceptance bound) of the local filestream's MB/s, again from
   the result file alone.
4. **sharded pull speedup** (r9) — the shards=2 cold-pull row must beat
   shards=1 by at least ``--sharded-speedup`` (default 1.3: the ISSUE 4
   acceptance bound) at the full 64 MB payload, from the result file
   alone.  Host-portability condition, same spirit as the memcpy
   normalization: a loopback shard bench parallelizes over CPU CORES
   (each stream pins a server-writer and a client-reader thread), so on a
   host with < 4 cores one stream already saturates the box and NO
   implementation can express a speedup — there the check degrades to a
   no-collapse floor (shards=2 >= 0.6x shards=1, which still trips the
   catastrophic regressions: a serialized gather that re-pulls the full
   vector per shard halves the row).  The bench records ``cpus`` for
   this; on >= 4-core hosts the full 1.3x bound applies.
5. **serving batched speedup** (r10, ``tools/serving_bench.py`` results) —
   micro-batched throughput under N concurrent clients must be at least
   ``--serving-speedup`` (default 3.0: the ISSUE 5 acceptance bound) x the
   single-client one-at-a-time throughput at ``max_batch`` >= 32, from the
   result file alone: one jitted apply per coalesced batch, not one per
   request.
6. **concurrent p99 ratio** (r17, the unified server core) — on the
   serving bench's paced concurrency axis (``--clients=64,256``, each
   client at a fixed request rate), p99 at 256 connections must stay
   within ``--concurrent-p99-ratio`` (default 3.0) x p99 at 64, from the
   result file alone.  Per-client load is held constant, so the ratio
   prices the PER-CONNECTION cost of the runtime: bounded under the
   selector core, blown up by a regression toward thread-per-connection
   scheduling or any O(conns) pass on the hot path.

The default tolerance is generous (0.25: flag only when a normalized row
drops below a QUARTER of baseline) — this is a tripwire for structural
regressions, not a micro-perf ratchet.

Usage:
  python tools/ps_transport_bench.py --json /tmp/t.json
  python tools/perf_gate.py /tmp/t.json
  python tools/data_service_bench.py --json /tmp/d.json
  python tools/perf_gate.py /tmp/d.json     # baseline auto-selected
"""

from __future__ import annotations

import argparse
import json
import os
import sys

#: metric field -> checked-in baseline file next to this script.
BASELINES = {
    "ps_transport_set_get_mbs": "ps_transport_baseline.json",
    "data_service_stream_mbs": "data_service_baseline.json",
    "serving_qps": "serving_baseline.json",
    "loadsim_slo": "loadsim_baseline.json",
    # r15 live-resharding acceptance (tools/loadsim.py --scenario=reshard):
    # same binary slo_pass discipline as loadsim_slo — the reshard_slo
    # gate set (zero failed predicts, zero reseeds, both transitions
    # committed inside the wall-time bound, retired tasks drained exit 0,
    # every epoch visible to dtxtop) must hold, and a gate present in the
    # baseline must still be computed by the result.
    "loadsim_reshard_slo": "loadsim_reshard_baseline.json",
    # r18 graceful-degradation acceptance (tools/loadsim.py
    # --scenario=overload): binary slo_pass over the overload gate set —
    # goodput floor during a >=4x-capacity burst, zero lease expirations
    # for live members (control ops are never shed), p99 recovered to a
    # bounded multiple of baseline within the recovery window of burst
    # end (the no-metastability proof), step monotone, and the burst
    # genuinely tripping admission control (a run the cluster absorbed
    # without shedding proves nothing).  Gate-set shrink detection as
    # with the other loadsim verdicts.
    "loadsim_overload_slo": "loadsim_overload_baseline.json",
    # r19 rolling-deploy acceptance (tools/loadsim.py --scenario=canary):
    # binary slo_pass over the canary gate set — zero failed predicts
    # through a full stable→canary→promoted registry-version flip with a
    # kill/join cycle landing mid-flip, the canary traffic fraction
    # within tolerance of the routed weight, the served model_version
    # monotone and all-promoted at the end, and both versions visible to
    # dtxtop's per-version rollup mid-flip.  Gate-set shrink detection as
    # with the other loadsim verdicts.
    "loadsim_canary_slo": "loadsim_canary_baseline.json",
    # r20 multi-tenant isolation acceptance (tools/loadsim.py
    # --scenario=multitenant): binary slo_pass over the noisy-neighbor
    # gate set — two tenants' training stacks on one shared PS/serve
    # plane, the noisy tenant 4x-overloads the pool mid-run and is shed
    # ONLY via its per-tenant quota (shed_quota > 0 on its dtxtop rollup
    # row, zero sheds of any kind on the SLO tenant's), the SLO tenant
    # never fails a predict and its noisy-window p99 stays under a
    # bounded multiple of its own baseline, both tenants' PS namespaces
    # and members stay disjointly visible, zero lease expirations, step
    # monotone.  Gate-set shrink detection as with the other loadsim
    # verdicts.
    "loadsim_multitenant_slo": "loadsim_multitenant_baseline.json",
    # r16 static-analysis wall-time budget (tools/dtxlint_step.py): the
    # lint's repo gate runs inside tier-1, so a pass whose cost silently
    # explodes taxes every future test run — this gate fails first.
    "dtxlint": "dtxlint_time_baseline.json",
}


def _detail(rec: dict) -> dict:
    return rec.get("detail", rec)


def gate(
    result: dict, baseline: dict, *, tolerance: float, if_newer_ratio: float,
    remote_local_ratio: float = 0.5, sharded_speedup: float = 1.3,
    serving_speedup: float = 3.0, replicated_overhead: float = 1.6,
    loadsim_p99_ratio: float = 20.0, concurrent_p99_ratio: float = 3.0,
) -> list[str]:
    """Returns a list of human-readable regression lines (empty = pass)."""
    res, base = _detail(result), _detail(baseline)
    failures: list[str] = []
    # The r16 dtxlint wall-time budget: a hard per-run bound from the
    # checked-in baseline (generous cross-host headroom lives IN the
    # budget — no tolerance multiplier on top), plus the verdict itself —
    # a lint that stopped exiting clean is a failure regardless of how
    # fast it failed.
    if "budget_s" in base:
        secs = res.get("seconds")
        if secs is None:
            failures.append(
                "dtxlint: result carries no 'seconds' — the wall-time "
                "budget cannot be checked"
            )
        elif secs > base["budget_s"]:
            failures.append(
                f"dtxlint: {secs:.1f}s > budget {base['budget_s']:.1f}s — "
                "a lint pass got structurally slower (this gate runs "
                "inside tier-1 on every PR)"
            )
        if res.get("ok") is False:
            failures.append("dtxlint: run not clean (ok=false)")
        return failures  # budget baselines carry no bench rows below
    # The r14 elasticity acceptance (tools/loadsim.py verdicts): the SLO
    # verdict itself is binary — every gate (zero failed predicts, p99
    # under the checked-in bound, step monotone+advancing through the
    # kill/join/leave cycle, join lease observed) must hold — and a gate
    # PRESENT in the baseline must still be computed by the result (a
    # gutted loadsim cannot silently pass by dropping a check).  The p99
    # compare against baseline is a loose cross-host tripwire only; the
    # hard latency bound is the result's own p99_bound_ms gate.
    if "slo_pass" in res or "slo_pass" in base:
        if not res.get("slo_pass"):
            bad = sorted(
                g for g, ok in (res.get("gates") or {}).items() if not ok
            )
            failures.append(
                "loadsim: slo_pass False"
                + (f" (failing gates: {', '.join(bad)})" if bad else "")
            )
        for g in base.get("gates") or {}:
            if g not in (res.get("gates") or {}):
                failures.append(
                    f"loadsim: gate {g!r} missing from result — the SLO "
                    "check set shrank"
                )
        bp99, rp99 = base.get("p99_ms"), res.get("p99_ms")
        if bp99 and rp99 and rp99 > loadsim_p99_ratio * bp99:
            failures.append(
                f"loadsim: p99_ms {rp99:.1f} > {loadsim_p99_ratio} x "
                f"baseline {bp99:.1f} — serve latency structurally "
                "regressed under chaos"
            )
        return failures  # loadsim verdicts carry no bench rows below
    # The r10 serving acceptance bound, from the result alone: coalescing
    # concurrent requests into one jitted apply must genuinely amortize —
    # batched (N concurrent clients) throughput >= serving_speedup x the
    # one-at-a-time single-client throughput at the full max_batch=32
    # budget.  A batcher that stopped coalescing (one apply per request)
    # collapses this to ~1x no matter the host.
    if (
        isinstance(res.get("batched"), dict)
        and isinstance(res.get("single"), dict)
        and res.get("batched_speedup") is not None
        and res.get("max_batch", 0) >= 32
    ):
        sp = res["batched_speedup"]
        if sp < serving_speedup:
            failures.append(
                f"batched_speedup: {sp:.2f} < {serving_speedup} — "
                "micro-batching no longer amortizing the apply "
                "(coalescing broken?)"
            )
    if (
        isinstance(base.get("batched"), dict)
        and not isinstance(res.get("batched"), dict)
        and base.get("batched_speedup") is not None
    ):
        failures.append("batched: row missing from result")
    # The r17 server-core concurrency bound, from the result alone: with
    # each client issuing requests at a fixed rate, p99 at the widest
    # connection count (256) must stay within ``concurrent_p99_ratio`` x
    # p99 at the narrowest (64).  Per-client load is constant, so the
    # ratio isolates the PER-CONNECTION cost of the runtime — a
    # regression back to thread-per-connection scheduling (or an
    # O(conns) pass anywhere on the hot path) blows it up no matter the
    # host.
    def _conc_rows(detail: dict) -> dict:
        conc_d = detail.get("concurrency")
        if not (isinstance(conc_d, dict)
                and isinstance(conc_d.get("clients"), dict)):
            return {}
        return {
            int(k): v
            for k, v in conc_d["clients"].items()
            if isinstance(v, dict) and v.get("p99_ms")
        }

    rows = _conc_rows(res)
    if len(rows) >= 2:
        lo, hi = min(rows), max(rows)
        ratio = rows[hi]["p99_ms"] / rows[lo]["p99_ms"]
        if ratio > concurrent_p99_ratio:
            failures.append(
                f"concurrency.p99_ratio: {ratio:.2f} > "
                f"{concurrent_p99_ratio} (p99 {rows[hi]['p99_ms']:.1f} "
                f"ms at {hi} clients vs {rows[lo]['p99_ms']:.1f} ms at "
                f"{lo}) — per-connection cost no longer bounded "
                "(server core regressed toward thread-per-connection?)"
            )
    # The backstop keys on USABLE rows, not the key's mere presence: a
    # result that kept a "concurrency" dict but lost a client row (or
    # its p99) would otherwise skip the headline gate while reporting
    # PASS.
    if len(_conc_rows(base)) >= 2 and len(rows) < 2:
        failures.append(
            f"concurrency: only {len(rows)} gated client row(s) in the "
            "result (baseline gates 2) — the p99-ratio check silently "
            "stopped running"
        )
    # The r9 shard-scaling acceptance bound, from the result alone: the
    # sharded cold pull must genuinely parallelize.  Gated only at the
    # full 64 MB payload (the acceptance size); hosts too small to express
    # loopback parallelism (< 4 cores, see module docstring) get the
    # no-collapse floor instead of the speedup bound.
    shard_rows = res.get("shards")
    if (
        isinstance(shard_rows, dict)
        and isinstance(shard_rows.get("2"), dict)
        and res.get("large_mb", 0.0) >= 64.0
    ):
        bound = sharded_speedup if res.get("cpus", 0) >= 4 else 0.6
        sp = shard_rows["2"].get("sharded_pull_speedup")
        if sp is not None and sp < bound:
            failures.append(
                f"shards.2.sharded_pull_speedup: {sp:.2f} < {bound} "
                f"(host cpus={res.get('cpus', '?')}) — sharded gather no "
                "longer parallel?"
            )
    baseline_shards = base.get("shards")
    if (
        isinstance(baseline_shards, dict)
        and isinstance(baseline_shards.get("2"), dict)
        and not isinstance(shard_rows, dict)
    ):
        failures.append("shards: rows missing from result")
    # The r12 replication acceptance bound, from the result alone: the
    # replicated gradient push (the per-step hot path) mirrors its dedup
    # tag HEADER-ONLY to the backup, so its overhead over the unreplicated
    # push must stay under ``replicated_overhead`` (default 1.6 — one
    # extra small round trip, never a second payload transfer).  The
    # payload-carrying publish path (set) legitimately pays a second
    # transfer; it gets a loose no-catastrophe tripwire (<= 2x the push
    # bound) since loopback hosts cannot overlap the two streams.
    repl_rows = res.get("replicas")
    if (
        isinstance(repl_rows, dict)
        and isinstance(repl_rows.get("2"), dict)
        and res.get("large_mb", 0.0) >= 64.0
    ):
        ov = repl_rows["2"].get("replicated_push_overhead")
        if ov is not None and ov > replicated_overhead:
            failures.append(
                f"replicas.2.replicated_push_overhead: {ov:.2f} > "
                f"{replicated_overhead} — the dedup mirror forwarding "
                "payloads (or an extra blocking round trip) on the "
                "gradient hot path?"
            )
        sov = repl_rows["2"].get("replicated_set_overhead")
        if sov is not None and sov > 2 * replicated_overhead:
            failures.append(
                f"replicas.2.replicated_set_overhead: {sov:.2f} > "
                f"{2 * replicated_overhead} — replicated publish worse "
                "than a second full serialized transfer (forward no "
                "longer streamed?)"
            )
    if (
        isinstance(base.get("replicas"), dict)
        and isinstance(base["replicas"].get("2"), dict)
        and not isinstance(repl_rows, dict)
    ):
        failures.append("replicas: rows missing from result")
    # The disaggregation acceptance bound, from the result alone: remote
    # streaming within 1/ratio of the local in-process loader.  Applies in
    # the 1 MB+ batch regime the acceptance criterion names — per-batch
    # round-trip overhead legitimately dominates tiny (--quick) batches.
    if (
        isinstance(res.get("remote"), dict)
        and isinstance(res.get("local"), dict)
        and res.get("raw_batch_mb", 1.0) >= 1.0
    ):
        r, l = res["remote"].get("stream_mbs"), res["local"].get("stream_mbs")
        if r and l and r < remote_local_ratio * l:
            failures.append(
                f"remote.stream_mbs: {r:.1f} < {remote_local_ratio} x local "
                f"{l:.1f} MB/s — remote batch streaming outside the "
                "disaggregation acceptance bound"
            )
    for dtype, brow in base.items():
        if not isinstance(brow, dict):
            continue
        rrow = res.get(dtype)
        if not isinstance(rrow, dict):
            if any(k.endswith("_frac_memcpy") for k in brow):
                failures.append(f"{dtype}: row missing from result")
            continue
        for key, bval in brow.items():
            if not key.endswith("_frac_memcpy"):
                continue
            rval = rrow.get(key)
            if rval is None:
                failures.append(f"{dtype}.{key}: missing from result")
            elif rval < tolerance * bval:
                failures.append(
                    f"{dtype}.{key}: {rval:.4f} < {tolerance} x baseline "
                    f"{bval:.4f} (copy-per-send regression?)"
                )
        # The O(header) contract, from the result alone.
        if "if_newer_rtt_us" in rrow and rrow.get("get_mbs_large"):
            full_pull_us = res["large_mb"] / rrow["get_mbs_large"] * 1e6
            ratio = full_pull_us / max(rrow["if_newer_rtt_us"], 1e-9)
            if ratio < if_newer_ratio:
                failures.append(
                    f"{dtype}.if_newer_rtt_us: unchanged-step pull only "
                    f"{ratio:.1f}x faster than a full pull (< "
                    f"{if_newer_ratio}x) — get_if_newer moving O(params)?"
                )
    return failures


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("result", help="ps_transport_bench / data_service_bench JSON record")
    ap.add_argument(
        "--baseline", default="",
        help="baseline JSON; default: auto-selected next to this script "
        "from the result's 'metric' field",
    )
    ap.add_argument("--tolerance", type=float, default=0.25)
    ap.add_argument("--if-newer-ratio", type=float, default=20.0)
    ap.add_argument("--remote-local-ratio", type=float, default=0.5)
    ap.add_argument("--sharded-speedup", type=float, default=1.3)
    ap.add_argument("--serving-speedup", type=float, default=3.0)
    ap.add_argument("--replicated-overhead", type=float, default=1.6,
                    help="max replicated-push latency multiplier over the "
                    "unreplicated push (r12: the dedup mirror is "
                    "header-only, so ~1 extra small round trip)")
    ap.add_argument("--concurrent-p99-ratio", type=float, default=3.0,
                    help="r17 server-core bound: max p99 multiplier from "
                    "the narrowest to the widest connection count on the "
                    "serving bench's paced concurrency axis (64 -> 256 "
                    "clients at fixed per-client rate)")
    ap.add_argument("--loadsim-p99-ratio", type=float, default=20.0,
                    help="loose cross-host tripwire for loadsim verdicts: "
                    "max p99_ms multiplier over the checked-in baseline "
                    "(the hard bound is the verdict's own p99_bound_ms "
                    "gate)")
    args = ap.parse_args()
    with open(args.result) as f:
        result = json.load(f)
    baseline_path = args.baseline
    if not baseline_path:
        name = BASELINES.get(result.get("metric", ""))
        if name is None:
            # Name the registered fields: an auto-select miss is almost
            # always a typo'd/renamed metric, and the fix is picking one of
            # these — a bare error would send the operator source-diving.
            print(
                f"PERF_GATE FAIL\n  unknown metric {result.get('metric')!r} "
                "and no --baseline given\n  registered metric fields: "
                + ", ".join(sorted(BASELINES))
            )
            sys.exit(1)
        baseline_path = os.path.join(os.path.dirname(os.path.abspath(__file__)), name)
    with open(baseline_path) as f:
        baseline = json.load(f)
    failures = gate(
        result, baseline,
        tolerance=args.tolerance, if_newer_ratio=args.if_newer_ratio,
        remote_local_ratio=args.remote_local_ratio,
        sharded_speedup=args.sharded_speedup,
        serving_speedup=args.serving_speedup,
        replicated_overhead=args.replicated_overhead,
        loadsim_p99_ratio=args.loadsim_p99_ratio,
        concurrent_p99_ratio=args.concurrent_p99_ratio,
    )
    if failures:
        print("PERF_GATE FAIL")
        for line in failures:
            print("  " + line)
        sys.exit(1)
    print("PERF_GATE PASS")


if __name__ == "__main__":
    main()
