"""One-command TPU measurement campaign (VERDICT r4 next-round #7).

Runs the full BASELINE.md measurement agenda serially — each step a FRESH
process (the block-size/fused env knobs are read at trace time, so sweep
points must not share a jit cache — ADVICE r4; and a chip belongs to one
process at a time, so this parent never imports jax) with its own timeout
— and appends machine-readable results to the out-file after every step,
so a mid-campaign failure loses nothing already measured.  On the machine
with the chip the device is there or the step fails; nothing here waits
for one.

Order (by value — the r4 perf agenda first):
  1.  flash_parity        fused-vs-split bwd parity + determinism ON TPU
                          (the advisor's Mosaic-risk gate: FAIL -> every
                          later step runs with DTX_FUSED_BWD=0)
  2.  bench T=8192 fused / split end-to-end A/B, block sweeps
  3.  flash_bench kernel-table rows T=8192/16384 x fused 0/1
  4.  batch-4 via --loss-chunks 8
  5.  MoE bench
  6.  headline re-measures (resnet, T=2048 flagship)
  7.  comms_scaling --measure (Ulysses t_step columns)
  8.  ulysses_ab (single-chip CP compute A/B)
  9.  decode rows: dense / moe / collapsed-pipeline
  10. T=16384 flagship (the fused kernel's deep regime)
  11. ps_tpu_smoke (chief-on-TPU PS cluster)

Usage:
  python tools/measure_campaign.py
  python tools/measure_campaign.py --only bench_t8192_fused,flash_parity
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PY = sys.executable


def last_json_line(text: str):
    for line in reversed(text.splitlines()):
        line = line.strip()
        if line.startswith("{") and line.endswith("}"):
            try:
                return json.loads(line)
            except json.JSONDecodeError:
                continue
    return None


def steps_plan() -> list[dict]:
    """The ordered agenda.  '{FUSED}' env placeholders are substituted at
    run time with the flash_parity outcome ('1' pass / '0' fail)."""
    bench = [PY, "bench.py"]
    t8192 = bench + ["--model", "transformer", "--seq-len", "8192", "--batch-per-chip", "2"]
    fb = [PY, "tools/flash_bench.py", "--b", "1", "--h", "8", "--d", "128", "--markdown"]
    plan = [
        dict(name="flash_parity", cmd=[PY, "tools/flash_parity.py"], timeout=1500),
        dict(name="bench_t8192_fused", cmd=t8192, env={"DTX_FUSED_BWD": "{FUSED}"}, timeout=1500),
        dict(name="bench_t8192_split", cmd=t8192, env={"DTX_FUSED_BWD": "0"}, timeout=1500),
        dict(name="bench_t8192_bq512_bk512", cmd=t8192,
             env={"DTX_FUSED_BWD": "{FUSED}", "DTX_FLASH_BQ": "512", "DTX_FLASH_BK": "512"}, timeout=1200),
        dict(name="bench_t8192_bq512_bk1024", cmd=t8192,
             env={"DTX_FUSED_BWD": "{FUSED}", "DTX_FLASH_BQ": "512", "DTX_FLASH_BK": "1024"}, timeout=1200),
        dict(name="bench_t8192_bq1024_bk512", cmd=t8192,
             env={"DTX_FUSED_BWD": "{FUSED}", "DTX_FLASH_BQ": "1024", "DTX_FLASH_BK": "512"}, timeout=1200),
        # The --fused 1 rows force the kernel via the explicit override —
        # deliberate even after a parity failure (they are diagnostic A/B
        # rows labeled f1, and state['fused_gate'] sits next to them in the
        # results file); everything that MEASURES A WORKLOAD (bench_*,
        # ulysses_ab) respects the '{FUSED}' gate instead.
        dict(name="flash_bench_t8192_f0", cmd=fb + ["--t", "8192", "--fused", "0"], timeout=1200),
        dict(name="flash_bench_t8192_f1", cmd=fb + ["--t", "8192", "--fused", "1"], timeout=1200),
        dict(name="flash_bench_t16384_f0", cmd=fb + ["--t", "16384", "--fused", "0"], timeout=1200),
        dict(name="flash_bench_t16384_f1", cmd=fb + ["--t", "16384", "--fused", "1"], timeout=1200),
        # r5 segmented fused regime (past the VMEM cap): parity first, then
        # the T=32768 A/B.
        dict(name="flash_parity_segmented",
             cmd=[PY, "tools/flash_parity.py", "--quick", "--segmented"],
             timeout=1500, optional=True),
        dict(name="flash_bench_t32768_f0", cmd=fb + ["--t", "32768", "--fused", "0"],
             timeout=1500, optional=True),
        dict(name="flash_bench_t32768_f1", cmd=fb + ["--t", "32768", "--fused", "1"],
             timeout=1500, optional=True),
        dict(name="bench_t8192_b4_chunks", cmd=bench + [
            "--model", "transformer", "--seq-len", "8192",
            "--batch-per-chip", "4", "--loss-chunks", "8",
        ], env={"DTX_FUSED_BWD": "{FUSED}"}, timeout=1500),
        dict(name="bench_moe", cmd=bench + ["--model", "moe"], timeout=1500),
        # Dispatch-share lever A/B: G=512 halves dispatch FLOPs/token vs the
        # G=1024 default (capacity semantics change with G — this is a
        # throughput A/B, not a parity pair).
        dict(name="bench_moe_g512", cmd=bench + ["--model", "moe", "--moe-group-size", "512"],
             timeout=1500, optional=True),
        dict(name="bench_resnet", cmd=bench[:], timeout=1500),
        dict(name="bench_t2048", cmd=bench + ["--model", "transformer"], timeout=1200),
        dict(name="comms_measure", cmd=[PY, "tools/comms_scaling.py", "--measure"], timeout=2400),
        dict(name="ulysses_ab", cmd=[PY, "tools/ulysses_ab.py"],
             env={"DTX_FUSED_BWD": "{FUSED}"}, timeout=1500),
        dict(name="bench_decode", cmd=bench + ["--model", "decode"], timeout=1200),
        dict(name="bench_decode_moe", cmd=bench + ["--model", "decode", "--decode-variant", "moe"], timeout=1500),
        dict(name="bench_decode_pipeline", cmd=bench + ["--model", "decode", "--decode-variant", "pipeline"], timeout=1500),
        dict(name="bench_t16384", cmd=bench + [
            "--model", "transformer", "--seq-len", "16384",
            "--batch-per-chip", "1", "--loss-chunks", "16",
        ], env={"DTX_FUSED_BWD": "{FUSED}"}, timeout=1800, optional=True),
        # Deep-regime flagship: T=32768 rides the r5 segmented fused path
        # (fails cleanly if the activations don't fit — optional row).
        dict(name="bench_t32768", cmd=bench + [
            "--model", "transformer", "--seq-len", "32768",
            "--batch-per-chip", "1", "--loss-chunks", "32",
        ], env={"DTX_FUSED_BWD": "{FUSED}"}, timeout=2400, optional=True),
        dict(name="ps_tpu_smoke", cmd=[PY, "tools/ps_tpu_smoke.py"], timeout=1100),
        # Host-side PS transport microbench (r7): needs NO accelerator —
        # ``cpu_ok`` steps run first, before any step that needs the chip.
        dict(name="ps_transport_bench",
             cmd=[PY, "tools/ps_transport_bench.py"], timeout=900,
             cpu_ok=True),
        # Disaggregated-input streaming bench (r8): local filestream vs the
        # remote data service on loopback — also accelerator-free.
        dict(name="data_service_bench",
             cmd=[PY, "tools/data_service_bench.py"], timeout=900,
             cpu_ok=True),
        # Online inference plane bench (r10): single vs micro-batched
        # predict throughput through a PS-tracking replica on loopback —
        # JAX-on-CPU only, so also a cpu_ok step.
        dict(name="serving_bench",
             cmd=[PY, "tools/serving_bench.py"], timeout=900,
             cpu_ok=True),
        # Static analysis (r11): wire conformance + concurrency +
        # fault-coverage + flag drift.  Pure AST/regex work, so cpu_ok; a
        # non-empty finding set fails the step (rc=1) and the campaign
        # records exactly which invariant drifted.
        dict(name="dtxlint",
             cmd=[PY, "tools/dtxlint_step.py"], timeout=600,
             cpu_ok=True),
        # Native ThreadSanitizer gate (r16): build the TSAN .so and run
        # the protocol driver (replicated pair + concurrent clients +
        # kill/restart/partition chaos) under libtsan; any unsuppressed
        # race fails the step, hosts without a TSAN toolchain record a
        # loud 'skipped'.  Pure host-side C++/sockets, so cpu_ok.
        # Timeout sits ABOVE the step's internal worst case (420s build +
        # 420s sanitized driver + probes): the step must always get to
        # emit its own JSON verdict before the campaign's SIGKILL.
        dict(name="tsan_protocol",
             cmd=[PY, "tools/tsan_step.py"], timeout=1100,
             cpu_ok=True),
        # Observability plane (r13): boot a mini train-and-serve cluster
        # under load, scrape it once with dtxtop, fail on any missing
        # role/counter — the cluster must stay scrape-able, release over
        # release.  JAX-on-CPU only, so also a cpu_ok step.
        dict(name="obs_snapshot",
             cmd=[PY, "tools/obs_snapshot_step.py"], timeout=600,
             cpu_ok=True),
        # Elasticity acceptance rig (r14): a short closed-loop chaos load
        # sim — real multi-process train+serve cluster, one kill/join/
        # leave cycle, SLO-gated verdict (zero failed predicts, p99 under
        # bound, step monotone through the chaos).  The standing
        # acceptance ROADMAP items 1-4 gate on; JAX-on-CPU, so cpu_ok.
        # Verdict gated against tools/loadsim_baseline.json by perf_gate.
        # r17: 4x the original closed-loop client count (16 generator
        # connections, qps 100) with the SLO gates unchanged — the serve
        # plane rides the unified server core now.
        dict(name="loadsim",
             cmd=[PY, "tools/loadsim.py", "--qps", "100", "--duration_s",
                  "30", "--p99_bound_ms", "400"],
             timeout=900, cpu_ok=True),
        # Live PS resharding acceptance (r15): resize the PS tier 2→3→2
        # shards mid-run under closed-loop predict load with one worker
        # kill — zero reseeds, zero failed predicts, monotone step, both
        # epoch transitions bounded and dtxtop-visible.  JAX-on-CPU, so
        # cpu_ok; verdict gated against tools/loadsim_reshard_baseline.json
        # by perf_gate (metric loadsim_reshard_slo).
        dict(name="loadsim_reshard",
             cmd=[PY, "tools/loadsim.py", "--scenario", "reshard", "--qps",
                  "25", "--duration_s", "45", "--p99_bound_ms", "400"],
             timeout=900, cpu_ok=True),
        # Graceful-degradation acceptance (r18): a >=4x-capacity unpaced
        # burst against deliberately bounded serve replicas — admission
        # control must shed the excess (goodput floor holds), control ops
        # are never shed (zero lease expirations), and p99 returns to a
        # bounded multiple of baseline within the recovery window of
        # burst end (no metastable retry storm).  JAX-on-CPU, so cpu_ok;
        # verdict gated against tools/loadsim_overload_baseline.json by
        # perf_gate (metric loadsim_overload_slo).
        dict(name="loadsim_overload",
             cmd=[PY, "tools/loadsim.py", "--scenario", "overload",
                  "--qps", "100", "--duration_s", "30"],
             timeout=900, cpu_ok=True),
        # Rolling-deploy acceptance (r19): a 3-replica registry-pinned
        # serve pool flips stable→canary→promoted under closed-loop load
        # with a kill/join cycle landing mid-flip — zero failed predicts,
        # canary weight honored ±tolerance, served model_version monotone
        # and fully promoted, both versions dtxtop-visible.  JAX-on-CPU,
        # so cpu_ok; verdict gated against
        # tools/loadsim_canary_baseline.json by perf_gate (metric
        # loadsim_canary_slo).
        # p99 bound: the flip runs ~14 processes (training + 7 serve
        # tasks + the orchestrator) on whatever the dev box has — the
        # hard zero-failure/weight/monotonicity gates carry the
        # acceptance; the latency bound is a loose tail tripwire.
        dict(name="loadsim_canary",
             cmd=[PY, "tools/loadsim.py", "--scenario", "canary",
                  "--qps", "50", "--duration_s", "60",
                  "--p99_bound_ms", "2500"],
             timeout=900, cpu_ok=True),
        # Multi-tenant isolation acceptance (r20): two tenants' training
        # stacks share one PS tier + serve pool; the noisy tenant
        # 4x-overloads the pool mid-run and is shed ONLY via its
        # per-tenant quota while the SLO tenant never fails a predict
        # and keeps a bounded p99 — plus disjoint per-tenant namespaces
        # on dtxtop's rollup and zero lease expirations.  JAX-on-CPU, so
        # cpu_ok; verdict gated against
        # tools/loadsim_multitenant_baseline.json by perf_gate (metric
        # loadsim_multitenant_slo).
        dict(name="loadsim_multitenant",
             cmd=[PY, "tools/loadsim.py", "--scenario", "multitenant",
                  "--qps", "100", "--duration_s", "30"],
             timeout=900, cpu_ok=True),
    ]
    return plan


def run_step(step: dict, fused_env: str) -> dict:
    step = dict(step)
    step["env"] = {
        k: (fused_env if v == "{FUSED}" else v)
        for k, v in step.get("env", {}).items()
    }
    env = dict(os.environ)
    env.update(step["env"])
    t0 = time.time()
    timed_out = False
    # Own session per step so a timeout kills the WHOLE process group —
    # ps_tpu_smoke spawns a 4-process cluster, and a leaked hung chief
    # would hold the chip every later step needs.
    p = subprocess.Popen(
        step["cmd"], stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True, cwd=ROOT, env=env, start_new_session=True,
    )
    try:
        out, err = p.communicate(timeout=step["timeout"])
        rc = p.returncode
    except subprocess.TimeoutExpired:
        timed_out = True
        rc = -9
        import signal

        try:
            os.killpg(os.getpgid(p.pid), signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
        try:
            out, err = p.communicate(timeout=30)
        except subprocess.TimeoutExpired:
            out, err = "", ""
    dt = time.time() - t0
    rec = {
        "name": step["name"],
        "cmd": " ".join(step["cmd"][1:]) if step["cmd"][0] == PY else " ".join(step["cmd"]),
        "env": step.get("env", {}),
        "rc": rc,
        "timed_out": timed_out,
        "seconds": round(dt, 1),
        "json": last_json_line(out),
        "stdout_tail": out[-4000:],
        "stderr_tail": err[-2500:],
    }
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--out", default=os.path.join(ROOT, "chiprun_out", "campaign.json")
    )
    ap.add_argument("--only", default="", help="comma list of step names")
    ap.add_argument(
        "--resume", action="store_true",
        help="keep the out-file's succeeded steps and run only the rest — "
        "a failure mid-campaign must not cost the measurements already taken",
    )
    args = ap.parse_args()

    state = {"started": time.strftime("%Y-%m-%dT%H:%M:%S"), "status": "running", "steps": []}
    succeeded: set[str] = set()
    if args.resume and os.path.exists(args.out):
        try:
            with open(args.out) as f:
                prev = json.load(f)
            state["steps"] = [r for r in prev.get("steps", []) if r.get("rc") == 0]
            succeeded = {r["name"] for r in state["steps"]}
            state["resumed_from"] = prev.get("started")
            print(f"[campaign] resuming; keeping {sorted(succeeded)}", flush=True)
        except (json.JSONDecodeError, OSError) as e:
            print(f"[campaign] resume failed ({e}); starting fresh", flush=True)

    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)

    def flush():
        tmp = args.out + ".tmp"
        with open(tmp, "w") as f:
            json.dump(state, f, indent=1)
        os.replace(tmp, args.out)

    flush()
    failed_required: list[str] = []
    failed_optional: list[str] = []

    def record_step(step: dict, fused_env: str) -> dict:
        """Run one step and fold it into the shared accounting — the ONE
        run/record/failure/flush block both loops (cpu pre-steps and the
        chip agenda) use, so their campaign JSON can never diverge."""
        print(f"[campaign] step {step['name']} ...", flush=True)
        rec = run_step(step, fused_env)
        state["steps"].append(rec)
        if rec["rc"] == 0:
            succeeded.add(step["name"])
        else:
            (failed_optional if step.get("optional") else failed_required).append(
                step["name"]
            )
            state["failed_steps"] = failed_required
            state["failed_optional"] = failed_optional
        flush()
        print(f"[campaign]   rc={rec['rc']} {rec['seconds']}s", flush=True)
        return rec

    # CPU-runnable steps first — they need no chip.  One attempt only: a
    # failed cpu step is accounted here and SKIPPED by the main loop (a
    # deterministic failure would just repeat and double-record the step).
    attempted_cpu: set[str] = set()
    only = {s for s in args.only.split(",") if s}
    for step in steps_plan():
        if not step.get("cpu_ok") or step["name"] in succeeded:
            continue
        if only and step["name"] not in only:
            continue
        attempted_cpu.add(step["name"])
        record_step(step, "0")
    # Step 1 resolves the fused gate for everything after it.  On --resume
    # the gate is recomputed from the kept steps — record it immediately so
    # the out-file header never reports '?' for a gate the downstream steps
    # actually ran with (ADVICE r5).  Keyed on flash_parity specifically:
    # the cpu pre-steps also populate `succeeded`, and a fresh campaign
    # must not stamp "parity failed" for a gate never yet determined.
    fused_env = "1" if "flash_parity" in succeeded else "0"
    if "flash_parity" in succeeded:
        state["fused_gate"] = fused_env
        flush()
    # Failure accounting honors each step's `optional` flag: optional rows
    # (deep-regime/segmented extras) may fail without demoting the campaign
    # from "complete" — their failures are still recorded per step.
    for step in steps_plan():
        if only and step["name"] not in only:
            continue
        if step["name"] in succeeded or step["name"] in attempted_cpu:
            continue
        rec = record_step(step, fused_env)
        if step["name"] == "flash_parity":
            fused_env = "1" if rec["rc"] == 0 else "0"
            state["fused_gate"] = fused_env
            flush()
    state["status"] = (
        "complete" if not failed_required else "complete_with_failures"
    )
    flush()
    print(
        f"[campaign] {state['status']}"
        + (f" (required failures: {failed_required})" if failed_required else "")
        + (f" (optional failures: {failed_optional})" if failed_optional else ""),
        flush=True,
    )


if __name__ == "__main__":
    main()
