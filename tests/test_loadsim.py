"""loadsim (r14 tentpole): verdict logic units + the chaos smoke e2e.

The unit tests pin the SLO verdict computation (step-progress analysis,
chaos plan composition) deterministically; the
smoke e2e drives the REAL ``tools/loadsim.py`` — a multi-process
train-and-serve cluster off the product CLI with a full kill/join/leave
cycle under closed-loop predict load — and asserts the gates the
acceptance rig stands on: zero failed serve requests, monotone advancing
global step through the chaos, and the joined worker's lease visible to
a mid-run ``dtxtop --json`` that exits 0.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from tools import loadsim  # noqa: E402


def test_build_plan_scripts_one_full_cycle():
    from distributed_tensorflow_examples_tpu.utils import faults

    plan = loadsim.build_plan(10.0, 40.0, join_worker_id=2)
    specs = faults.parse_plan(plan)  # must parse loudly-valid
    kinds = sorted(s.kind for s in specs)
    assert kinds.count("die") == 3  # ps + serve + worker kills
    assert "leave" in kinds and "join" in kinds
    dies = {s.role: s.after_s for s in specs if s.kind == "die"}
    assert set(dies) == {"ps0", "serve0", "worker1"}
    # The orchestrator consumes the join; the leave outlives the kill.
    (join,) = faults.join_specs(plan)
    assert join.role == "worker2"
    (leave,) = [s for s in specs if s.kind == "leave"]
    assert leave.after_s > dies["worker1"] > join.after_s
    # Offsets bake in the boot window.
    assert min(s.after_s for s in specs if s.after_s) >= 10.0


def test_default_scenario_runs_4x_clients():
    """r17: the default closed-loop client count is 4x the r14 rig (16
    generator connections; SLO gates unchanged) — the serve plane rides
    the unified server core, so connection count is cheap.  Pinned here
    so a refactor cannot silently shrink the standing acceptance load."""
    import inspect

    assert inspect.signature(
        loadsim.LoadGenerator.__init__
    ).parameters["threads"].default == 16
    ns = _parse_loadsim_args([])
    assert ns.gen_threads == 16 and ns.qps == 100.0


def _parse_loadsim_args(argv):
    """The loadsim arg surface, parsed without booting a cluster: main()
    dispatches AFTER parse_args, so intercept at the scenario branch."""
    import unittest.mock as mock

    captured = {}

    def grab(args):
        captured["ns"] = args
        raise SystemExit(0)

    with mock.patch.object(loadsim, "run_reshard", side_effect=grab):
        with pytest.raises(SystemExit):
            loadsim.main(argv + ["--scenario", "reshard"])
    return captured["ns"]


def test_analyze_steps_verdicts():
    markers = {"kill_worker": 10.0, "leave_worker": 20.0}
    good = [(t, 100 + 10 * t) for t in range(0, 30, 2)]
    v = loadsim.analyze_steps([(float(t), int(s)) for t, s in good], markers)
    assert v["step_monotone"] and v["step_advanced"]
    assert v["step_advanced_post_chaos"]
    # A regression (step going BACKWARD — a lost publish) fails monotone.
    bad = [(0.0, 100), (5.0, 200), (10.0, 150), (15.0, 300)]
    v = loadsim.analyze_steps(bad, markers)
    assert not v["step_monotone"] and v["step_advanced"]
    # Stalling after the last chaos marker fails the post-chaos gate even
    # though the overall window advanced.
    stalled = [(0.0, 100), (10.0, 500), (21.0, 500), (29.0, 500)]
    v = loadsim.analyze_steps(stalled, markers)
    assert v["step_advanced"] and not v["step_advanced_post_chaos"]
    # Missing scrapes (-1) are holes, not evidence.
    v = loadsim.analyze_steps([(0.0, -1), (1.0, 5), (2.0, 9)], {})
    assert v["step_first"] == 5 and v["step_monotone"]


@pytest.mark.slow
def test_loadsim_chaos_smoke_e2e(tmp_path):
    """THE acceptance smoke: a short real-cluster run with the full
    kill/join/leave cycle must pass its SLO gate end to end."""
    out = tmp_path / "verdict.json"
    env = dict(os.environ)
    env.pop("DTX_FAULT_PLAN", None)
    env.pop("DTX_FAULT_ROLE", None)
    env.setdefault("JAX_PLATFORMS", "cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "loadsim.py"),
         "--qps=15", "--duration_s=30", "--p99_bound_ms=1500",
         f"--out={out}", f"--logdir={tmp_path}"],
        capture_output=True, text=True, timeout=420, cwd=ROOT, env=env,
    )
    tail = "\n".join(r.stdout.strip().splitlines()[-3:])
    assert r.returncode == 0, f"loadsim rc={r.returncode}\n{tail}\n{r.stderr[-2000:]}"
    v = json.loads(open(out).read())
    assert v["slo_pass"], v["gates"]
    assert v["predict_failed"] == 0 and v["predict_ok"] > 100
    assert v["step_monotone"] and v["step_advanced_post_chaos"]
    assert v["gates"]["dtxtop_midrun_exit0"] and v["gates"]["join_lease_seen"]


def test_canary_scenario_surface_and_phases():
    """r19: the canary scenario's arg surface and timeline — the weight
    deliberately differs from the plain round-robin share (1/(R+1)) so an
    ignored weight FAILS the honored-fraction gate instead of passing by
    coincidence, and the phases order publish -> canary -> kill ->
    promote -> retire."""
    ns = _parse_loadsim_args([])
    assert ns.canary_weight == 0.4 and ns.canary_tol == 0.12
    # 3 stable + 1 canary round-robins to 0.25 — outside weight ± tol.
    rr_share = 1.0 / (max(3, ns.serve_replicas) + 1)
    assert abs(rr_share - ns.canary_weight) > ns.canary_tol
    p = loadsim.CANARY_PHASES
    assert (
        p["publish_v2"] < p["canary_up"] < p["kill_serve"]
        < p["promote_start"] < p["retire_old"] < 1.0
    )
