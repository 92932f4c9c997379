"""CPU smoke tests for the measurement-campaign tools (r5).

The campaign tools exist to run unattended in a scarce hardware window —
a bit-rotted tool that crashes at minute 0 of a 30-minute window is the r4
failure mode all over again.  These tests exercise each tool's core path
in interpret/CPU mode so import errors, signature drift, or plan typos
surface in CI, not on the chip.
"""

from __future__ import annotations

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOLS = os.path.join(ROOT, "tools")
for p in (ROOT, TOOLS):
    if p not in sys.path:
        sys.path.insert(0, p)


def test_campaign_plan_is_well_formed():
    import measure_campaign as mc

    plan = mc.steps_plan()
    names = [s["name"] for s in plan]
    assert len(names) == len(set(names)), "duplicate step names"
    # The r4 agenda's core steps must all be present.
    for required in (
        "flash_parity", "bench_t8192_fused", "bench_t8192_split",
        "flash_bench_t16384_f1", "bench_moe", "bench_resnet",
        "comms_measure", "ulysses_ab", "bench_decode_moe",
        "bench_decode_pipeline", "ps_tpu_smoke",
    ):
        assert required in names, f"campaign lost step {required}"
    for s in plan:
        assert s["timeout"] >= 600, (s["name"], "timeout too tight for a cold compile")
        # Every script the plan invokes must exist.
        target = s["cmd"][1]
        assert os.path.exists(os.path.join(ROOT, target)), (s["name"], target)
        for v in s.get("env", {}).values():
            assert v == "{FUSED}" or v.isdigit(), (s["name"], v)
        # `optional` is consumed by the failure accounting — only True (or
        # absent) is meaningful.
        assert s.get("optional", True) is True, (s["name"], s.get("optional"))
    # flash_parity must run FIRST: it resolves the fused gate for the rest.
    assert names[0] == "flash_parity"


def test_campaign_fused_placeholder_resolution(monkeypatch, tmp_path):
    """run_step substitutes '{FUSED}' with the parity outcome and passes it
    through the subprocess env (the mechanism that keeps a Mosaic parity
    failure from poisoning every downstream measurement)."""
    import json

    import measure_campaign as mc

    step = {
        "name": "probe_env",
        "cmd": [sys.executable, "-c",
                "import os, json; print(json.dumps({'v': os.environ.get('DTX_FUSED_BWD')}))"],
        "env": {"DTX_FUSED_BWD": "{FUSED}"},
        "timeout": 60,
    }
    rec = mc.run_step(step, "1")
    assert rec["rc"] == 0 and rec["json"] == {"v": "1"}
    rec = mc.run_step(step, "0")
    assert rec["json"] == {"v": "0"}


def test_flash_parity_case_runs_in_interpret_mode():
    """run_case at a tiny shape: parity + bitwise determinism hold in
    interpret mode (the TPU run reuses this exact code path)."""
    import flash_parity
    import jax.numpy as jnp

    rec = flash_parity.run_case(1, 2, 128, 16, jnp.float32, True, check_ref=True)
    assert rec["ok"], rec
    assert rec["bitwise_deterministic"]
    rec = flash_parity.run_case(1, 2, 128, 16, jnp.bfloat16, False, check_ref=False)
    assert rec["ok"], rec


def test_ulysses_ab_grad_time_tiny():
    import ulysses_ab

    t = ulysses_ab.grad_time(1, 2, 128, 16, steps=1)
    assert t > 0


def test_ps_smoke_final_parser():
    import ps_tpu_smoke

    out = "noise\nFINAL step=40 steps_per_sec=11.7 examples_per_sec_per_chip=748 mode=sync_replicas_cluster\n"
    f = ps_tpu_smoke._final(out)
    assert f["step"] == 40 and f["mode"] == "sync_replicas_cluster"
    with pytest.raises(AssertionError):
        ps_tpu_smoke._final("no final here")


def test_campaign_report_renders(tmp_path, capsys):
    import json

    import campaign_report

    state = {
        "started": "2026-07-31T06:00:00", "status": "complete", "fused_gate": "1",
        "steps": [
            {"name": "flash_parity", "cmd": "tools/flash_parity.py", "env": {},
             "rc": 0, "timed_out": False, "seconds": 120.0,
             "json": {"parity_ok": True, "platform": "tpu", "cases": [
                 {"shape": [1, 8, 8192, 128], "dtype": "bfloat16", "causal": True,
                  "ok": True, "bitwise_deterministic": True, "dq_vs_split_rel": 0.01}]},
             "stdout_tail": "", "stderr_tail": ""},
            {"name": "bench_t8192_fused", "cmd": "bench.py ...", "env": {"DTX_FUSED_BWD": "1"},
             "rc": 0, "timed_out": False, "seconds": 300.0,
             "json": {"metric": "transformer_tokens_per_sec_per_chip", "value": 70000.0,
                      "unit": "tokens/sec/chip", "vs_baseline": 1.11,
                      "detail": {"mfu": 0.42}},
             "stdout_tail": "", "stderr_tail": ""},
            {"name": "flash_bench_t8192_f1", "cmd": "tools/flash_bench.py ...", "env": {},
             "rc": -9, "timed_out": True, "seconds": 1200.0, "json": None,
             "stdout_tail": "| row |", "stderr_tail": ""},
            # Failed bench with a STALE json line: must render as FAILED,
            # not as a clean measurement (ADVICE r5).
            {"name": "bench_moe", "cmd": "bench.py ...", "env": {},
             "rc": 1, "timed_out": False, "seconds": 90.0,
             "json": {"metric": "moe_tokens", "value": 123.0, "unit": "tok/s",
                      "vs_baseline": 0.5, "detail": {}},
             "stdout_tail": "", "stderr_tail": ""},
        ],
    }
    p = tmp_path / "c.json"
    p.write_text(json.dumps(state))
    import sys as _sys

    old = _sys.argv
    _sys.argv = ["campaign_report.py", str(p)]
    try:
        campaign_report.main()
    finally:
        _sys.argv = old
    out = capsys.readouterr().out
    assert "parity_ok=True" in out
    assert "70000.0 tokens/sec/chip" in out and "42.0% MFU" in out
    assert "`bench_t8192_fused` [ok]" in out
    assert "FAILED rc=-9 (timeout)" in out
    # A failed bench step renders its status tag even with stale JSON.
    assert "`bench_moe` [FAILED rc=1]" in out
