"""Render a measure_campaign out-file into BASELINE.md-ready markdown.

The campaign writes raw per-step records (tools/measure_campaign.py); this
turns them into the tables/sentences BASELINE.md wants, so the scarce
minutes after a hardware window close on bookkeeping, not reformatting.

Usage: python tools/campaign_report.py [chiprun_out/campaign.json]
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def fault_tag(rec: dict) -> str:
    # A step measured under an active fault plan must say so next to its
    # number — a fault-run throughput is a recovery measurement, not a
    # clean baseline.
    plan = (rec.get("env") or {}).get("DTX_FAULT_PLAN", "")
    return f" [faults: {plan}]" if plan else ""


def fmt_bench(rec: dict, ok: str) -> str:
    # The status tag renders like every other step type: a failed bench
    # whose stdout still held a stale JSON line must read as FAILED, not
    # as a clean measurement (ADVICE r5).
    j = rec.get("json") or {}
    d = j.get("detail", {})
    if not j:
        return f"- `{rec['name']}` [{ok}]{fault_tag(rec)}: NO JSON ({rec['seconds']}s)"
    mfu = d.get("mfu")
    mfu_s = f", {mfu*100:.1f}% MFU" if isinstance(mfu, (int, float)) else ""
    env = " ".join(f"{k}={v}" for k, v in rec.get("env", {}).items())
    return (
        f"- `{rec['name']}` [{ok}]{fault_tag(rec)}: **{j.get('value')} {j.get('unit')}**{mfu_s} "
        f"(vs_baseline {j.get('vs_baseline')}; {env or 'default env'}; "
        f"{rec['seconds']}s wall)"
    )


def fmt_transport(rec: dict, ok: str) -> str:
    """Host-side transport/streaming benches (ps_transport_bench,
    data_service_bench, serving_bench): one line per detail row,
    memcpy-normalized fractions included — the numbers perf_gate
    compares."""
    j = rec.get("json") or {}
    d = j.get("detail", {})
    if not j:
        return f"- `{rec['name']}` [{ok}]{fault_tag(rec)}: NO JSON ({rec['seconds']}s)"
    lines = [
        f"- `{rec['name']}` [{ok}]{fault_tag(rec)}: **{j.get('value')} {j.get('unit')}** "
        f"(memcpy {d.get('memcpy_mbs')} MB/s; {rec['seconds']}s wall)"
    ]
    for row_name, row in d.items():
        if row_name == "concurrency":
            continue  # rendered as the dedicated ratio line below
        if isinstance(row, dict):
            kv = " ".join(f"{k}={v}" for k, v in row.items())
            lines.append(f"    - {row_name}: {kv}")
    if "remote_over_local" in d:
        lines.append(
            f"    - remote_over_local={d['remote_over_local']} "
            "(disaggregation bound: >= 0.5)"
        )
    if "batched_speedup" in d:
        lines.append(
            f"    - batched_speedup={d['batched_speedup']} "
            "(micro-batching bound: >= 3.0 at max_batch=32)"
        )
    conc = d.get("concurrency")
    if isinstance(conc, dict):
        per = " ".join(
            f"{n}c:p99={row.get('p99_ms')}ms"
            for n, row in sorted(
                (conc.get("clients") or {}).items(), key=lambda kv: int(kv[0])
            )
            if isinstance(row, dict)
        )
        lines.append(
            f"    - concurrent_p99_ratio={conc.get('p99_ratio')} "
            f"({per}; server-core bound: <= 3.0 at 4x connections)"
        )
    repl = d.get("replicas")
    if isinstance(repl, dict) and isinstance(repl.get("2"), dict):
        lines.append(
            "    - replicated_push_overhead="
            f"{repl['2'].get('replicated_push_overhead')} "
            "(replication bound: <= 1.6; set_overhead="
            f"{repl['2'].get('replicated_set_overhead')})"
        )
    return "\n".join(lines)


def _dtxlint_budget():
    """The checked-in lint wall-time budget (perf_gate's bound), for the
    report line — '?' when the baseline is unreadable."""
    try:
        with open(os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "dtxlint_time_baseline.json",
        )) as f:
            return json.load(f).get("budget_s", "?")
    except (OSError, json.JSONDecodeError):
        return "?"


def fmt_dtxlint(rec: dict, ok: str) -> str:
    """Static-analysis step (r11): clean/dirty verdict plus the offending
    finding keys — a drifted wire invariant must be readable from the
    report without re-running the linter."""
    j = rec.get("json") or {}
    if not j:
        return f"- `dtxlint` [{ok}]: NO JSON ({rec['seconds']}s)"
    counts = j.get("counts", {})
    lines = [
        f"- `dtxlint` [{ok}]: {'clean' if j.get('ok') else 'FINDINGS'} — "
        f"{counts.get('active', '?')} active, "
        f"{counts.get('suppressed', '?')} suppressed, "
        f"{counts.get('stale_suppressions', '?')} stale "
        f"(schema v{j.get('schema_version')}; lint {j.get('seconds', '?')}s "
        f"of budget {_dtxlint_budget()}s; {rec['seconds']}s wall)"
    ]
    for f in j.get("findings", []):
        lines.append(f"    - {f.get('key')}: {f.get('message')}")
    for key in j.get("stale_suppressions", []):
        lines.append(f"    - stale suppression: {key}")
    return "\n".join(lines)


def fmt_tsan(rec: dict, ok: str) -> str:
    """Native ThreadSanitizer gate (r16): races / clean / skipped, the
    driver's throughput line, and the live suppression count — a growing
    suppression pile must be visible in every report."""
    j = rec.get("json") or {}
    if not j:
        return f"- `tsan_protocol` [{ok}]: NO JSON ({rec['seconds']}s)"
    if j.get("skipped"):
        return (
            f"- `tsan_protocol` [{ok}]: SKIPPED — {j['skipped']} "
            f"({rec['seconds']}s)"
        )
    if j.get("error"):
        return (
            f"- `tsan_protocol` [{ok}]: ERROR — {j['error']} "
            f"({rec['seconds']}s)"
        )
    lines = [
        f"- `tsan_protocol` [{ok}]: "
        f"{'clean' if j.get('ok') else 'RACES'} — {j.get('warnings')} "
        f"warning(s), {j.get('suppressions')} suppression(s), driver "
        f"rc={j.get('driver_rc')} ({j.get('driver_line') or 'no driver line'}; "
        f"{rec['seconds']}s wall)"
    ]
    for s in j.get("summaries", []):
        lines.append(f"    - {s}")
    return "\n".join(lines)


def fmt_obs(rec: dict, ok: str) -> str:
    """Observability acceptance step (r13): the dtxtop snapshot summary —
    which roles answered, the aggregated cluster counters, and any
    missing-counter findings — rendered next to the bench rows."""
    j = rec.get("json") or {}
    if not j:
        return f"- `obs_snapshot` [{ok}]: NO JSON ({rec['seconds']}s)"
    su = j.get("summary", {})
    lines = [
        f"- `obs_snapshot` [{ok}]: {'all roles scraped' if j.get('ok') else 'MISSING'}"
        f" — {j.get('roles_ok')}/{j.get('roles_total')} roles "
        f"({rec['seconds']}s wall)"
    ]
    if su:
        ps, dsvc, srv = su.get("ps", {}), su.get("dsvc", {}), su.get("serve", {})
        lines.append(
            f"    - ps_reqs={ps.get('requests')} dedup={ps.get('deduped')} "
            f"repl_syncs={ps.get('repl_syncs_served')} "
            f"diverged={ps.get('diverged') or 'none'} | "
            f"dsvc_batches={dsvc.get('batches_served')} | "
            f"serve_steps={srv.get('model_steps')} p99={srv.get('p99_ms')}ms"
        )
    for p in j.get("problems", []):
        lines.append(f"    - PROBLEM: {p}")
    return "\n".join(lines)


def fmt_loadsim(rec: dict, ok: str) -> str:
    """Elasticity acceptance step (r14): the loadsim SLO verdict — pass/
    fail per gate, the latency/qps numbers and the step-progress window —
    readable from the report without re-running the sim."""
    j = rec.get("json") or {}
    if not j:
        return f"- `loadsim` [{ok}]: NO JSON ({rec['seconds']}s)"
    gates = j.get("gates", {})
    bad = sorted(g for g, v in gates.items() if not v)
    lines = [
        f"- `loadsim` [{ok}]: SLO {'PASS' if j.get('slo_pass') else 'FAIL'}"
        f" — {j.get('predict_ok')} predicts, {j.get('predict_failed')} "
        f"failed, p99={j.get('p99_ms')}ms (bound {j.get('p99_bound_ms')}), "
        f"qps {j.get('qps_achieved')}/{j.get('qps_target')} "
        f"({rec['seconds']}s wall)"
    ]
    lines.append(
        f"    - step {j.get('step_first')} -> {j.get('step_last')} "
        f"(monotone={j.get('step_monotone')}, "
        f"post_chaos_advance={j.get('step_advanced_post_chaos')}); "
        f"members={((j.get('members_last') or {}).get('workers') or [])} + "
        f"{((j.get('members_last') or {}).get('serve') or [])}"
    )
    if bad:
        lines.append(f"    - FAILING GATES: {', '.join(bad)}")
    return "\n".join(lines)


def fmt_overload(rec: dict, ok: str) -> str:
    """Graceful-degradation acceptance step (r18): the overload SLO
    verdict — did the burst genuinely trip admission control, did goodput
    hold its floor while the excess shed, did anyone's lease expire, and
    how fast did p99 return to baseline after the burst ended."""
    j = rec.get("json") or {}
    if not j:
        return f"- `loadsim_overload` [{ok}]: NO JSON ({rec['seconds']}s)"
    gates = j.get("gates", {})
    bad = sorted(g for g, v in gates.items() if not v)
    lines = [
        f"- `loadsim_overload` [{ok}]: SLO "
        f"{'PASS' if j.get('slo_pass') else 'FAIL'} — burst goodput "
        f"{j.get('burst_goodput_qps')} qps (floor "
        f"{j.get('goodput_floor_qps')}), sheds "
        f"{j.get('shed_total', 0) + j.get('batcher_overloads', 0)} "
        f"(core {j.get('shed_total')} + batcher "
        f"{j.get('batcher_overloads')}), leases_expired "
        f"{j.get('leases_expired')} ({rec['seconds']}s wall)"
    ]
    lines.append(
        f"    - p99 baseline {j.get('baseline_p99_ms')}ms -> recovered in "
        f"{j.get('recovery_s')}s (target {j.get('recovery_target_ms')}ms, "
        f"bound {j.get('recovery_bound_s')}s); step {j.get('step_first')} "
        f"-> {j.get('step_last')} (monotone={j.get('step_monotone')}); "
        f"retry={j.get('retry')}"
    )
    if bad:
        lines.append(f"    - FAILING GATES: {', '.join(bad)}")
    return "\n".join(lines)


def main():
    path = sys.argv[1] if len(sys.argv) > 1 else os.path.join(ROOT, "chiprun_out", "campaign.json")
    with open(path) as f:
        state = json.load(f)
    print(f"# Campaign report — started {state.get('started')}, status {state.get('status')}")
    print(f"fused gate after parity: DTX_FUSED_BWD={state.get('fused_gate', '?')}\n")
    for rec in state.get("steps", []):
        name = rec["name"]
        ok = "ok" if rec["rc"] == 0 else f"FAILED rc={rec['rc']}" + (" (timeout)" if rec.get("timed_out") else "")
        if name in ("ps_transport_bench", "data_service_bench", "serving_bench"):
            print(fmt_transport(rec, ok))
        elif name == "dtxlint":
            print(fmt_dtxlint(rec, ok))
        elif name == "tsan_protocol":
            print(fmt_tsan(rec, ok))
        elif name == "obs_snapshot":
            print(fmt_obs(rec, ok))
        elif name == "loadsim":
            print(fmt_loadsim(rec, ok))
        elif name == "loadsim_overload":
            print(fmt_overload(rec, ok))
        elif name.startswith("bench_"):
            print(fmt_bench(rec, ok))
        elif name == "flash_parity":
            j = rec.get("json") or {}
            print(f"- `flash_parity` [{ok}]: parity_ok={j.get('parity_ok')} platform={j.get('platform')}")
            for c in j.get("cases", []):
                print(f"    - {c.get('shape')} {c.get('dtype')} causal={c.get('causal')}: "
                      f"ok={c.get('ok')} bitwise={c.get('bitwise_deterministic')} "
                      f"dq_rel={c.get('dq_vs_split_rel')}")
        elif name == "ulysses_ab":
            j = rec.get("json") or {}
            print(f"- `ulysses_ab` [{ok}] fused_env={j.get('fused_env')}:")
            for r in j.get("rows", []):
                print(f"    - sp={r['sp']}: ulysses {r['t_ulysses_ms']} ms vs "
                      f"ring >= {r['t_ring_ms']} ms (ratio >= {r['ring_over_ulysses']})")
        elif name == "ps_tpu_smoke":
            j = rec.get("json") or {}
            print(f"- `ps_tpu_smoke` [{ok}]: chief_platform={j.get('chief_platform')} "
                  f"final={j.get('final')}")
        else:
            # flash_bench / profile / comms: markdown or text — show the tail.
            print(f"- `{name}` [{ok}] ({rec['seconds']}s):")
            for line in (rec.get("stdout_tail") or "").splitlines()[-14:]:
                print(f"    {line}")
    print()


if __name__ == "__main__":
    main()
