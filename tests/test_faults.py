"""Fault-injection matrix for the resilient PS path (r6 tentpole).

The reference's fault story was crash-restart-everything: a lost PS task
stalled every worker until the whole job died and restarted from a
checkpoint (SURVEY.md section 5.3).  These tests drive the scripted fault
plans of ``utils/faults.py`` (``DTX_FAULT_PLAN``) against the MNIST-shaped
async-PS workload over the REAL socket transport and assert partial
recovery: clients reconnect (exponential backoff), replay dedup-tagged ops
(zero duplicate gradient applications, by counter), a killed PS task is
healed by ``supervise()`` restart + chief reseed, and training converges to
the fault-free loss.

Tier-1 (non-slow) coverage: connection drop, slow PS, and a real PS
kill+restart on a compact 2-process topology (PS subprocess under the
product supervisor path; chief+workers as threads of this process).  The
full multi-process matrix (worker SIGKILL etc.) is slow-marked here and in
tests/test_ps_remote.py.
"""

from __future__ import annotations

import os
import subprocess
import sys
import threading
import time

import numpy as np
import jax
import optax
import pytest

from distributed_tensorflow_examples_tpu import models
from distributed_tensorflow_examples_tpu.parallel import async_ps, ps_service
from distributed_tensorflow_examples_tpu.utils import faults

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CFG = models.mlp.Config(hidden=(16,), compute_dtype="float32")


@pytest.fixture(autouse=True)
def _clean_fault_env(monkeypatch):
    """Role/plan isolation: earlier tests exercising the product launchers
    (e.g. the ps_experiment validation tests) may have set the process
    fault role; these tests rely on the per-client role defaults."""
    monkeypatch.delenv("DTX_FAULT_PLAN", raising=False)
    monkeypatch.delenv("DTX_FAULT_ROLE", raising=False)
    monkeypatch.setattr(faults, "_role", None)


def _blob_batches(seed, batch=32):
    rng = np.random.default_rng(seed)
    protos = np.random.default_rng(0).normal(size=(10, 784)).astype(np.float32)
    while True:
        y = rng.integers(0, 10, size=batch).astype(np.int32)
        x = protos[y] + 0.1 * rng.normal(size=(batch, 784)).astype(np.float32)
        yield {"image": x, "label": y}


def _eval_loss(params) -> float:
    batch = next(_blob_batches(99, batch=256))
    loss, _ = models.mlp.loss_fn(CFG)(params, {}, batch, jax.random.key(0))
    return float(loss)


def _run_socket_training(
    *, steps=40, mode="async", plan="", ps_addr=None, ps_addrs=None,
    n_workers=2, shards=1, replicas=1, reconnect_deadline_s=60.0,
    join_timeout=180.0, wire_dtype="f32", stop_servers=None, on_chief=None,
):
    """One async-PS training run over the socket transport, chief + worker
    threads in THIS process (the thread/2-process fault path): cheap enough
    for tier-1, yet every op crosses the real TCP framing, so connection
    drops/delays/PS restarts exercise the actual recovery code.  Async runs
    carry the r7 fast path by default (prefetch double-buffering + the
    versioned param-pull cache); ``wire_dtype`` additionally switches the
    negotiated payload encoding.  ``shards`` > 1 hosts that many in-process
    shard servers (r9 scatter/gather); ``ps_addrs`` connects to external
    shard servers instead.  ``replicas=2`` (r12) gives every shard a
    primary/backup pair (in-process, or external when ``ps_addrs`` lists
    shards*2 replica-major entries).  ``on_chief(chief)`` runs on a side
    thread once training started — the mid-run kill hook."""
    os.environ["DTX_FAULT_PLAN"] = plan
    try:
        cfg = async_ps.AsyncPSConfig(
            num_workers=n_workers,
            mode=mode,
            train_steps=steps,
            replicas_to_aggregate=1 if mode == "sync_replicas" else None,
            ps_op_timeout_s=10.0,
            ps_reconnect_deadline_s=reconnect_deadline_s,
            ps_wire_dtype=wire_dtype,
        )
        chief = async_ps.RemotePSChief(
            cfg,
            models.mlp.loss_fn(CFG),
            optax.sgd(0.02),
            models.mlp.init(CFG, jax.random.key(0)),
            rng=jax.random.key(0),
            ps_addr=ps_addr,
            ps_addrs=ps_addrs,
            ports=[0] * (shards * replicas) if shards * replicas > 1 else None,
            ps_replicas=replicas,
        )
        if ps_addrs is not None:
            addrs = ps_addrs
        else:
            # Replica-major flat list, exactly the --ps_hosts convention.
            addrs = [
                rl[r]
                for r in range(replicas)
                for rl in chief._group.replica_addrs
            ]
        workers = [
            threading.Thread(
                target=async_ps.remote_worker_loop,
                args=("127.0.0.1", chief.port, w),
                kwargs=dict(
                    cfg=cfg,
                    loss_fn=models.mlp.loss_fn(CFG),
                    init_fn=lambda rng: models.mlp.init(CFG, rng),
                    batches=_blob_batches(w + 1),
                    rng=jax.random.key(0),
                    addrs=addrs,
                    ps_replicas=replicas,
                ),
                daemon=True,
            )
            for w in range(n_workers)
        ]
        done = threading.Event()
        out: dict = {}

        def chief_body():
            try:
                out["params"] = chief.run_chief()
            except BaseException as e:  # noqa: BLE001 — re-raised below
                out["exc"] = e
            finally:
                done.set()

        ct = threading.Thread(target=chief_body, daemon=True)
        ct.start()
        if on_chief is not None:
            threading.Thread(
                target=on_chief, args=(chief,), daemon=True
            ).start()
        for w in workers:
            w.start()
        if not done.wait(join_timeout):
            chief._client.cancel_all()
            raise AssertionError("chief did not finish within the deadline")
        for w in workers:
            w.join(timeout=30)
        if "exc" in out:
            raise out["exc"]
        return chief
    finally:
        os.environ.pop("DTX_FAULT_PLAN", None)
        # stop_servers=False keeps THIS process's shard servers alive after
        # training — the serving e2e's PS keeps publishing params to
        # replicas that outlive the training run.
        if stop_servers if stop_servers is not None else (ps_addr is None):
            ps_service.stop_server()


def test_fault_plan_parse_roles_and_strip():
    plan = (
        "drop_conn:role=worker0,op=7;delay:role=worker*,op=3,ms=5.5,count=2;"
        "die:role=ps0,after_reqs=80"
    )
    specs = faults.parse_plan(plan)
    assert [s.kind for s in specs] == ["drop_conn", "delay", "die"]
    assert specs[1].matches_role("worker1") and not specs[1].matches_role("chief0")
    # format/parse round trip, and die-stripping (the supervisor heal path).
    assert faults.parse_plan(faults.format_plan(specs))[1].ms == 5.5
    healed = faults.plan_without(plan, "die", "ps0")
    assert "die" not in healed and "drop_conn" in healed
    # Bad plans fail the launch loudly.
    for bad in ("explode:at=3", "drop_conn:role=w", "die:role=x", "delay:op=1,zz=2"):
        with pytest.raises(ValueError):
            faults.parse_plan(bad)
    # Probabilistic faults are deterministic per (seed, role, kind).
    a = faults._DetRng(7, "worker0", "delay")
    b = faults._DetRng(7, "worker0", "delay")
    assert [a.uniform() for _ in range(5)] == [b.uniform() for _ in range(5)]


def test_native_tagged_dedup_counters():
    """The replay-idempotence contract at the native layer: a re-issued
    (worker, seq) apply/push is counted in ``deduped`` and NOT re-applied —
    the mechanism behind the e2e zero-duplicate assertion."""
    from distributed_tensorflow_examples_tpu import native

    acc = native.GradientAccumulator(2)
    assert acc.apply_tagged(0, worker=1, seq=1, grad=np.ones(2))
    assert not acc.apply_tagged(0, worker=1, seq=1, grad=np.ones(2))  # replay
    assert acc.apply_tagged(0, worker=2, seq=1, grad=3 * np.ones(2))  # other worker
    assert acc.deduped == 1
    out = acc.take(2)
    np.testing.assert_allclose(out, [2.0, 2.0])  # duplicate NOT averaged in
    # A replayed stale drop answers duplicate too (dropped counter exact).
    acc.set_global_step(5)
    assert not acc.apply_tagged(4, worker=1, seq=2, grad=np.ones(2))
    assert not acc.apply_tagged(4, worker=1, seq=2, grad=np.ones(2))
    assert acc.dropped == 1 and acc.deduped == 2
    # Timed take surfaces a deadline instead of hanging forever.
    assert acc.take(1, timeout_s=0.1) is native.TIMED_OUT

    gq = native.GradientQueue(2, capacity=4)
    assert gq.push_tagged(0, worker=1, seq=1, grad=np.ones(2)) is True
    assert gq.push_tagged(0, worker=1, seq=1, grad=np.ones(2)) is True  # dup ok
    assert gq.deduped == 1
    step, _ = gq.pop()
    assert step == 0
    assert gq.pop(timeout_s=0.1) is native.TIMED_OUT  # dup was NOT enqueued
    # Bounded full-queue wait: a full queue times out instead of blocking.
    small = native.GradientQueue(1, capacity=1)
    assert small.push_tagged(0, worker=1, seq=1, grad=np.ones(1)) is True
    assert (
        small.push_tagged(0, worker=1, seq=2, grad=np.ones(1), timeout_s=0.1)
        is native.TIMED_OUT
    )


def test_connection_drop_recovers_and_converges(caplog):
    """Connection drops injected on both workers AND the chief mid-run: the
    clients reconnect + replay and the MNIST-blob async-PS run reaches the
    step target and the fault-free final loss, with zero duplicate
    gradient applications (dedup counter) and the recovery events on the
    ``dtx.faults`` logger."""
    caplog.set_level("INFO", logger="dtx.faults")
    baseline = _run_socket_training(steps=40, plan="")
    loss_ok = _eval_loss(baseline.params)

    plan = (
        "drop_conn:role=worker0,op=9;drop_conn:role=worker1,op=13,count=2;"
        "drop_conn:role=chief0,op=20"
    )
    chief = _run_socket_training(steps=40, plan=plan)
    assert chief.global_step == 40
    # Replay never double-applied a gradient: every drop here severs BEFORE
    # the op is sent, so the dedup tables must show zero suppressions AND
    # the applied-step count is exact (a duplicate would overshoot it).
    assert chief.total_deduped == 0
    loss_faulty = _eval_loss(chief.params)
    assert loss_faulty < max(2 * loss_ok, loss_ok + 0.35), (loss_faulty, loss_ok)
    events = [
        r.getMessage() for r in caplog.records if "dtx.faults" in r.getMessage()
    ]
    assert any("inject_drop_conn" in m for m in events), events
    assert any("event=reconnected" in m for m in events), events


def test_slow_ps_delay_converges():
    """Slow-PS fault: every worker op delayed — training is slower but
    semantics are unchanged and the run still reaches the target."""
    chief = _run_socket_training(
        steps=25, plan="delay:role=worker*,op=1,count=200,ms=15"
    )
    assert chief.global_step == 25
    assert _eval_loss(chief.params) < 2.0


def test_prefetch_connection_faults_do_not_corrupt_training(caplog):
    """r7 satellite: faults targeted at the PREFETCH connections only
    (role ``worker<i>_pf`` — connection drops AND delays) must never
    corrupt the consuming step: the prefetch client heals internally
    (reconnect + replay of the idempotent versioned pull, cache
    invalidated via the on_reconnect hook), errors would surface on
    ``.get()`` rather than feed the gradient a torn snapshot, and the run
    reaches its step target at the fault-free loss."""
    caplog.set_level("INFO", logger="dtx.faults")
    plan = (
        "drop_conn:role=worker0_pf,op=3;drop_conn:role=worker1_pf,op=5,count=2;"
        "delay:role=worker*_pf,op=8,count=30,ms=10"
    )
    chief = _run_socket_training(steps=40, plan=plan)
    assert chief.global_step == 40
    assert chief.total_deduped == 0  # pulls are idempotent: no dedup traffic
    assert _eval_loss(chief.params) < 2.0
    events = [
        r.getMessage() for r in caplog.records if "dtx.faults" in r.getMessage()
    ]
    # The faults really hit the prefetch connections, and those clients
    # really ran the recovery path.
    assert any("role=worker0_pf" in m and "inject_drop_conn" in m for m in events), events
    assert any("_pf" in m and "event=reconnected" in m for m in events), events


def test_fault_matrix_with_bf16_wire_and_prefetch(caplog):
    """Acceptance: the fault matrix holds with the FULL fast path on —
    bf16 wire encoding (negotiated per connection, re-negotiated on every
    reconnect) plus prefetch double-buffering.  Drops on workers and chief
    mid-run still heal with zero duplicate applications and the run
    converges."""
    caplog.set_level("INFO", logger="dtx.faults")
    plan = (
        "drop_conn:role=worker0,op=9;drop_conn:role=worker1_pf,op=4;"
        "drop_conn:role=chief0,op=20"
    )
    chief = _run_socket_training(steps=40, plan=plan, wire_dtype="bf16")
    assert chief.global_step == 40
    assert chief.total_deduped == 0
    # bf16 quantizes params/grads on the wire (~3 decimal digits), so the
    # loss bound is the same coarse "training worked" gate the other fault
    # runs use, not a parity check.
    assert _eval_loss(chief.params) < 2.0
    events = [
        r.getMessage() for r in caplog.records if "dtx.faults" in r.getMessage()
    ]
    assert any("event=reconnected" in m for m in events), events


_PS_TASK_SCRIPT = """\
import os, sys
sys.path.insert(0, {root!r})
os.environ.setdefault("JAX_PLATFORMS", "cpu")
from types import SimpleNamespace

from distributed_tensorflow_examples_tpu.train import ps_experiment

FLAGS = SimpleNamespace(
    job_name="ps", task_index={task_index}, ps_hosts={ps_hosts!r},
    worker_hosts="a:1,b:1", ps_tasks=1, ps_listen_all=False, ps_restarts=2,
    ps_replicas={ps_replicas}, ps_layout_version=0,
    batch_size=8, train_steps=60, log_dir="", checkpoint_every_steps=50,
    replicas_to_aggregate=0, max_staleness=0, deterministic=False, seed=0,
    grad_accum=1,
)
ps_experiment.run_ps_cluster_task(
    init_fn=None, loss_fn=None, optimizer=None, batches_for_worker=None,
    FLAGS=FLAGS, mode="async", eval_fn=None,
)
"""


def _free_ports(n: int) -> list[int]:
    import socket as _socket

    socks = [_socket.socket() for _ in range(n)]
    for s in socks:
        s.bind(("127.0.0.1", 0))
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    return ports


def test_ps_kill_mid_run_heals_via_supervised_restart(tmp_path, caplog):
    """The tentpole acceptance scenario: a dedicated PS task is KILLED
    mid-run by the fault plan (``die:after_reqs`` — deterministic in the
    request stream), its supervisor restarts it (stripping the fired spec),
    the chief detects the new incarnation, re-creates objects and reseeds
    (republish + counters), workers reconnect, and the async MNIST-blob run
    reaches its step target and the fault-free loss — partial recovery, not
    whole-job restart."""
    caplog.set_level("INFO", logger="dtx.faults")
    (port,) = _free_ports(1)
    script = tmp_path / "ps_task.py"
    script.write_text(
        _PS_TASK_SCRIPT.format(
            root=ROOT, task_index=0, ps_hosts=f"127.0.0.1:{port}",
            ps_replicas=1,
        )
    )
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # Kill the PS once it has served 120 requests — mid-run: the 40-step
    # 2-worker run needs a few hundred, while startup (idle shutdown-queue
    # polls + probe pings + object creation) stays well under the trigger
    # even on a slow box.  The supervised-child env inherits the plan; the
    # supervisor strips it after the injected death.
    env["DTX_FAULT_PLAN"] = "die:role=ps0,after_reqs=120"
    logf = open(tmp_path / "ps_task.log", "w")
    ps_proc = subprocess.Popen(
        [sys.executable, str(script)],
        stdout=logf, stderr=subprocess.STDOUT, env=env, cwd=ROOT,
    )
    try:
        # Wait for the PS service to answer (first incarnation up).
        deadline = time.time() + 120
        up = False
        while time.time() < deadline:
            try:
                c = ps_service.PSClient("127.0.0.1", port, timeout_s=2.0)
                c.ping()
                c.close()
                up = True
                break
            except OSError:
                time.sleep(0.2)
        assert up, "PS task never came up"

        chief = _run_socket_training(
            steps=40, ps_addr=("127.0.0.1", port), reconnect_deadline_s=90.0,
            join_timeout=240.0,
        )
        assert chief.global_step == 40
        # The applied count is exact (every pop->apply is counted once) and
        # the dedup/dropped counters were readable end-of-run (-1 = the
        # transport died before they could be collected).  The suppression
        # mechanics themselves — a replayed delivery answers "duplicate"
        # and is never applied — are pinned by
        # test_native_tagged_dedup_counters and
        # test_ps_remote.test_client_reconnects_replays_and_dedups.
        assert chief.total_deduped != -1 and chief.total_dropped != -1
        assert _eval_loss(chief.params) < 2.0
        # The chief must have crossed a NEW incarnation and reseeded.
        events = [
            r.getMessage() for r in caplog.records if "dtx.faults" in r.getMessage()
        ]
        assert any("incarnation_changed=True" in m for m in events), events
        assert any("event=chief_reseed" in m for m in events), events

        ps_proc.wait(timeout=60)
    finally:
        if ps_proc.poll() is None:
            ps_proc.kill()
            ps_proc.wait()
        logf.close()
    ps_log = (tmp_path / "ps_task.log").read_text()
    # The injected death fired, the supervisor healed the plan, and the
    # SECOND incarnation served to completion (clean shutdown handshake).
    assert "event=inject_die" in ps_log, ps_log[-2000:]
    assert "event=supervisor_healed_plan" in ps_log, ps_log[-2000:]
    assert "PS_DONE" in ps_log, ps_log[-2000:]
    assert ps_proc.returncode == 0, ps_log[-2000:]


def test_single_shard_drop_conn_heals(caplog):
    """r9 fault matrix: connection drops targeted at ONE SHARD's client
    connections only (role suffix ``_s<i>`` — the direct and prefetch
    clients of shard 1) in a 2-shard run.  That shard's clients reconnect
    and replay; the other shard's connections never drop; the run reaches
    its step target at the fault-free loss with zero duplicate
    applications."""
    caplog.set_level("INFO", logger="dtx.faults")
    plan = (
        "drop_conn:role=worker0_s1,op=6;drop_conn:role=worker1_s1,op=9;"
        "drop_conn:role=worker0_pf_s1,op=4"
    )
    chief = _run_socket_training(steps=40, plan=plan, shards=2)
    assert chief.global_step == 40
    assert chief.total_deduped == 0
    assert _eval_loss(chief.params) < 2.0
    events = [
        r.getMessage() for r in caplog.records if "dtx.faults" in r.getMessage()
    ]
    # The faults really hit shard 1's clients, and those clients really
    # reconnected; shard 0's plain worker roles never dropped.
    assert any("role=worker0_s1" in m and "inject_drop_conn" in m for m in events), events
    assert any("_s1" in m and "event=reconnected" in m for m in events), events
    assert not any(
        "inject_drop_conn" in m and "role=worker0 " in m for m in events
    ), events


def test_one_shard_of_two_killed_heals_via_supervised_restart(tmp_path, caplog):
    """r9 acceptance (the sharded tentpole scenario): a 2-shard, 2-worker
    async MNIST-blob run with BOTH shard servers as dedicated supervised
    PS tasks; shard 1's task is KILLED mid-run by its fault plan, its
    supervisor restarts it, the chief reseeds ONLY that shard (republish
    slice + counters — shard 0 is never reseeded, so the workers' shard-0
    versioned caches stay valid), and training heals to the step target
    and the fault-free loss."""
    caplog.set_level("INFO", logger="dtx.faults")
    ports = _free_ports(2)
    ps_hosts = ",".join(f"127.0.0.1:{p}" for p in ports)
    env_base = dict(os.environ)
    env_base["JAX_PLATFORMS"] = "cpu"
    procs, logs = [], []
    try:
        for tid in (0, 1):
            script = tmp_path / f"ps_task_{tid}.py"
            script.write_text(
                _PS_TASK_SCRIPT.format(
                    root=ROOT, task_index=tid, ps_hosts=ps_hosts,
                    ps_replicas=1,
                )
            )
            env = dict(env_base)
            # Only shard 1 dies (role ps1), once it has served 60 requests
            # — mid-run: each shard sees roughly half the single-server
            # request stream of the unsharded kill test (tokens stay on
            # shard 0), while startup polling stays well under the
            # trigger.
            env["DTX_FAULT_PLAN"] = "die:role=ps1,after_reqs=60"
            logf = open(tmp_path / f"ps_task_{tid}.log", "w")
            logs.append(logf)
            procs.append(
                subprocess.Popen(
                    [sys.executable, str(script)],
                    stdout=logf, stderr=subprocess.STDOUT, env=env, cwd=ROOT,
                )
            )
        # Wait for both shard servers to answer.
        for port in ports:
            deadline = time.time() + 120
            up = False
            while time.time() < deadline:
                try:
                    c = ps_service.PSClient("127.0.0.1", port, timeout_s=2.0)
                    c.ping()
                    c.close()
                    up = True
                    break
                except OSError:
                    time.sleep(0.2)
            assert up, f"shard task at port {port} never came up"

        chief = _run_socket_training(
            steps=40,
            ps_addrs=[("127.0.0.1", p) for p in ports],
            reconnect_deadline_s=90.0,
            join_timeout=240.0,
        )
        assert chief.global_step == 40
        assert chief.total_deduped != -1 and chief.total_dropped != -1
        assert _eval_loss(chief.params) < 2.0
        events = [
            r.getMessage() for r in caplog.records if "dtx.faults" in r.getMessage()
        ]
        # The chief crossed shard 1's new incarnation and reseeded THAT
        # shard individually; shard 0 was never reseeded.
        assert any(
            "event=chief_reseed" in m and "shard=1" in m for m in events
        ), events
        assert not any(
            "event=chief_reseed" in m and "shard=0" in m for m in events
        ), events

        for p in procs:
            p.wait(timeout=60)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
        for f in logs:
            f.close()
    log1 = (tmp_path / "ps_task_1.log").read_text()
    log0 = (tmp_path / "ps_task_0.log").read_text()
    # Shard 1: injected death fired, supervisor healed, second incarnation
    # served to completion.  Shard 0: no death, served straight through.
    assert "event=inject_die" in log1, log1[-2000:]
    assert "event=supervisor_healed_plan" in log1, log1[-2000:]
    assert "PS_DONE" in log1, log1[-2000:]
    assert "event=inject_die" not in log0, log0[-2000:]
    assert "PS_DONE" in log0, log0[-2000:]
    assert procs[0].returncode == 0 and procs[1].returncode == 0


# ---------------------------------------------------------------------------
# PS shard replication (r12): failover matrix
# ---------------------------------------------------------------------------


def test_backup_leg_faults_inject_under_b_role(caplog):
    """r12 fault matrix: the failover leg is its OWN client role — a plan
    targeting ``<role>_b`` fires only on ops issued while connected to the
    backup replica, and those ops still heal by reconnect+replay."""
    caplog.set_level("INFO", logger="dtx.faults")
    pa = ps_service.start_server(0)
    pb = ps_service.start_server(0, peer=("127.0.0.1", pa), sync_wait_s=10.0)
    ps_service.set_server_peer(pa, ("127.0.0.1", pb))
    os.environ["DTX_FAULT_PLAN"] = "drop_conn:role=w0_b,op=1"
    try:
        c = ps_service.PSClient(
            "127.0.0.1", pa, op_timeout_s=5.0, reconnect_deadline_s=20.0,
            role="w0", addrs=[("127.0.0.1", pa), ("127.0.0.1", pb)],
        )
        st = ps_service.RemoteParamStore(c, "params", 4, cache_pulls=False)
        st.set(1, np.arange(4, dtype=np.float32))
        ps_service.stop_server(pa)  # force the failover to the backup leg
        assert st.get()[0] == 1  # heals over to the backup mid-call
        # First COUNTED backup-leg op: the injected drop fires under w0_b
        # and heals by reconnect+replay on the same leg.
        step, flat = st.get()
        assert step == 1
        np.testing.assert_array_equal(flat, np.arange(4, dtype=np.float32))
        c.close()
    finally:
        os.environ.pop("DTX_FAULT_PLAN", None)
        ps_service.stop_server()
    events = [
        r.getMessage() for r in caplog.records if "dtx.faults" in r.getMessage()
    ]
    assert any(
        "inject_drop_conn" in m and "role=w0_b" in m for m in events
    ), events
    # Recovery events carry the client's base role + the replica index
    # (the leg suffix is the INJECTION identity, not the logging one).
    assert any(
        "event=reconnected" in m and "replica=1" in m for m in events
    ), events
    # The primary leg never fired (its role carries no _b suffix).
    assert not any(
        "inject_drop_conn" in m and "role=w0 " in m for m in events
    ), events


def test_partition_between_replicas_fails_loudly_not_split_brain(caplog):
    """r12 fault matrix: a ``partition`` spec between the two replicas of
    a shard (both stay ALIVE) makes the next state-mutating op fail with
    the loud divergence error — never a silent split-brain — while reads
    keep serving.  Arms exactly the way ``host_ps_task`` does."""
    caplog.set_level("INFO", logger="dtx.faults")
    pa = ps_service.start_server(0)
    pb = ps_service.start_server(0, peer=("127.0.0.1", pa), sync_wait_s=10.0)
    ps_service.set_server_peer(pa, ("127.0.0.1", pb))
    os.environ["DTX_FAULT_PLAN"] = "partition:role=ps0,peer=ps1"
    try:
        # A spec whose peer glob does NOT match this pair must not arm.
        faults.arm_process_faults(
            role="ps0",
            partition_fn=lambda spec: (
                spec.matches_peer("ps9")
                and ps_service.set_server_partitioned(pa, True)
            ),
        )
        c = ps_service.PSClient("127.0.0.1", pa, op_timeout_s=5.0)
        st = ps_service.RemoteParamStore(c, "params", 4, cache_pulls=False)
        st.set(1, np.zeros(4, np.float32))  # link healthy: accepted
        # The real arming: peer glob matches, the pair partitions.
        faults.arm_process_faults(
            role="ps0",
            partition_fn=lambda spec: (
                spec.matches_peer("ps1")
                and ps_service.set_server_partitioned(pa, True)
            ),
        )
        with pytest.raises(ps_service.PSError, match="replication diverged"):
            st.set(2, np.ones(4, np.float32))
        # Reads still serve, and the divergence is latched/observable.
        assert st.get()[0] == 1
        assert ps_service.server_diverged(pa) == 1
        c.close()
    finally:
        os.environ.pop("DTX_FAULT_PLAN", None)
        ps_service.stop_server()
    events = [
        r.getMessage() for r in caplog.records if "dtx.faults" in r.getMessage()
    ]
    assert any("event=inject_partition" in m for m in events), events


def test_replicated_ps_kill_heals_via_backup_with_zero_reseeds(tmp_path, caplog):
    """r12 acceptance (the replication tentpole scenario): a 2-shard
    REPLICATED topology — 4 dedicated supervised PS tasks, shard i served
    by primary ps<i> and backup ps<2+i> — runs the async MNIST-blob
    training; shard 0's PRIMARY is KILLED mid-run by its fault plan
    (``die:after_reqs``).  The clients fail over to the backup inside
    their own recovery loops (state token proves the state survived), so
    training heals with ZERO chief reseeds (the counter stays 0 and no
    chief_reseed event fires — the pre-r12 behavior this PR replaces),
    at-most-once push semantics hold across the failover (dedup counters
    readable, applied-step count exact), and the restarted primary
    catches up from the survivor via REPL_SYNC and serves to a clean
    shutdown.

    r13 growth: the whole story is ALSO read from OUTSIDE the processes,
    live, via the wire-level STATS scrape (tools/dtxtop.py): before the
    kill every task answers its counter table in one scrape — the
    backups' start-time REPL_SYNC catch-ups visible as
    ``repl_syncs_served`` on the primaries — and after the kill the
    surviving replicas still answer, with shard 0's backup counting its
    dead peer (``fwd_peer_down`` grows as the failed-over clients' writes
    can no longer be forwarded) — the failover evidence, with zero
    process internals touched."""
    from tools import dtxtop

    caplog.set_level("INFO", logger="dtx.faults")
    ports = _free_ports(4)
    ps_hosts = ",".join(f"127.0.0.1:{p}" for p in ports)
    env_base = dict(os.environ)
    env_base["JAX_PLATFORMS"] = "cpu"
    procs, logs = [], []
    scrape: dict = {}
    run_over = threading.Event()

    def scrape_throughout(chief):
        # Samples continuously for the whole run (the 40-step blob run is
        # seconds long; the kill fires a couple of steps in): keep the
        # best FULL snapshot (all 4 roles up — pre-kill) and the best
        # POST-KILL snapshot (ps0 down, every survivor answering).
        try:
            while not run_over.is_set():
                snap = dtxtop.snapshot(
                    [("127.0.0.1", p) for p in ports],
                    ps_shards=2, ps_replicas=2, timeout_s=3.0,
                )
                by_role = {r["role"]: r for r in snap["roles"]}
                if snap["summary"]["roles_ok"] == 4 and "full" not in scrape:
                    scrape["full"] = snap
                if (
                    not by_role["ps0"]["ok"]
                    and all(by_role[f"ps{i}"]["ok"] for i in (1, 2, 3))
                ):
                    scrape["post_kill"] = snap
                time.sleep(0.2)
        except BaseException as e:  # noqa: BLE001 — asserted below
            scrape["exc"] = e

    try:
        for tid in range(4):
            script = tmp_path / f"ps_task_{tid}.py"
            script.write_text(
                _PS_TASK_SCRIPT.format(
                    root=ROOT, task_index=tid, ps_hosts=ps_hosts,
                    ps_replicas=2,
                )
            )
            env = dict(env_base)
            # Only shard 0's PRIMARY dies, once it has served 60 requests
            # — mid-run (tokens/coordination keep its counter moving),
            # while startup polling stays well under the trigger.
            env["DTX_FAULT_PLAN"] = "die:role=ps0,after_reqs=60"
            logf = open(tmp_path / f"ps_task_{tid}.log", "w")
            logs.append(logf)
            procs.append(
                subprocess.Popen(
                    [sys.executable, str(script)],
                    stdout=logf, stderr=subprocess.STDOUT, env=env, cwd=ROOT,
                )
            )
        for port in ports:
            deadline = time.time() + 120
            up = False
            while time.time() < deadline:
                try:
                    c = ps_service.PSClient("127.0.0.1", port, timeout_s=2.0)
                    c.ping()
                    c.close()
                    up = True
                    break
                except OSError:
                    time.sleep(0.2)
            assert up, f"replica task at port {port} never came up"

        chief = _run_socket_training(
            steps=40,
            ps_addrs=[("127.0.0.1", p) for p in ports],
            replicas=2,
            reconnect_deadline_s=90.0,
            join_timeout=240.0,
            on_chief=scrape_throughout,
        )
        run_over.set()
        # The acceptance gates: exact step target, ZERO chief reseeds
        # (assert the counter), dedup counters readable end-of-run, and
        # the fault-free loss.
        assert chief.global_step == 40
        # r13: the external STATS scrape saw the whole story without
        # touching any process internals.
        assert "exc" not in scrape, scrape.get("exc")
        assert "full" in scrape, "no pre-kill full-cluster scrape landed"
        full = {r["role"]: r["stats"] for r in scrape["full"]["roles"]}
        assert all(full[f"ps{i}"]["replicated"] == 1 for i in range(4))
        # The backups' start-time REPL_SYNC catch-ups, counted on the
        # primaries that served them.  Asserted on ps1 ONLY: ps1 never
        # dies, so its counter survives no matter when the 4-role
        # snapshot landed — ps0's counter resets if the kill slipped in
        # before the first full scrape (the snapshot would then be of the
        # restarted incarnation, whose own catch-up sync counts on ps2).
        assert full["ps1"]["repl_syncs_served"] >= 1, full
        for i in range(4):
            assert "gq_deduped" in full[f"ps{i}"], full
        assert "post_kill" in scrape, "no post-kill survivor scrape landed"
        pk = {
            r["role"]: r["stats"]
            for r in scrape["post_kill"]["roles"] if r["ok"]
        }
        # Failover, externally visible: the clients moved to shard 0's
        # backup, whose forwards now count a dead peer, and the backups
        # applied forwarded dedup mirrors while the primaries lived.
        assert pk["ps2"]["fwd_peer_down"] >= 1, pk
        assert (
            pk["ps2"]["mirror_applies"] + pk["ps3"]["mirror_applies"]
        ) > 0, pk
        assert chief.reseeds == 0, "a replicated primary kill must not reseed"
        assert chief.total_deduped != -1 and chief.total_dropped != -1
        assert _eval_loss(chief.params) < 2.0
        events = [
            r.getMessage() for r in caplog.records if "dtx.faults" in r.getMessage()
        ]
        assert not any("event=chief_reseed" in m for m in events), events
        # Some client really failed over to a backup replica with its
        # state proven intact (the zero-stall path actually ran).
        assert any(
            "event=replica_state_intact" in m and "replica=1" in m
            for m in events
        ), events

        # The restarted primary either got the chief's shutdown push
        # (restarted mid-run) or exits via the orphaned-replica detector
        # (restarted after the run already finished) — both are clean.
        for p in procs:
            p.wait(timeout=120)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
        for f in logs:
            f.close()
    log0 = (tmp_path / "ps_task_0.log").read_text()
    # ps0: injected death fired, supervisor healed the plan, the restarted
    # incarnation (synced from the backup) served to a clean shutdown.
    assert "event=inject_die" in log0, log0[-2000:]
    assert "event=supervisor_healed_plan" in log0, log0[-2000:]
    assert "PS_DONE" in log0, log0[-2000:]
    # Every other replica served straight through, no deaths.
    for tid in (1, 2, 3):
        lg = (tmp_path / f"ps_task_{tid}.log").read_text()
        assert "event=inject_die" not in lg, lg[-2000:]
        assert "PS_DONE" in lg, lg[-2000:]
    assert all(p.returncode == 0 for p in procs), [p.returncode for p in procs]


def test_both_replicas_killed_chief_reseed_still_heals(caplog):
    """r12 fault matrix: losing BOTH replicas of a shard mid-run falls
    back to the pre-r12 last resort — both restart empty (a fresh state
    lineage), the chief detects total state loss and reseeds, and
    training still reaches its target."""
    caplog.set_level("INFO", logger="dtx.faults")
    killed = threading.Event()

    def kill_both(chief):
        while chief.global_step < 3:
            time.sleep(0.02)
        ports = [p for _, p in chief._group.replica_addrs[0]]
        ps_service.stop_server(ports[0])
        ps_service.stop_server(ports[1])
        time.sleep(0.5)
        # The "supervisor" restarts both EMPTY on the same ports — no
        # survivor to sync from, so a fresh token lineage on both.
        ps_service.start_server(ports[0])
        ps_service.start_server(
            ports[1], peer=("127.0.0.1", ports[0]), sync_wait_s=10.0
        )
        ps_service.set_server_peer(ports[0], ("127.0.0.1", ports[1]))
        killed.set()

    chief = _run_socket_training(
        steps=60, replicas=2, reconnect_deadline_s=60.0,
        join_timeout=200.0, on_chief=kill_both,
    )
    assert killed.is_set(), "the kill hook never fired"
    assert chief.global_step == 60
    assert chief.reseeds >= 1, "total state loss must run the reseed path"
    assert _eval_loss(chief.params) < 2.0
    events = [
        r.getMessage() for r in caplog.records if "dtx.faults" in r.getMessage()
    ]
    assert any("event=chief_reseed" in m for m in events), events


def _dsvc_splits(n=8, rows=16):
    """Splits whose rows carry their split index (recoverable through the
    image decode: marker = round((x + 0.5) * 255))."""
    return [
        {
            "image": np.full((rows, 4), i, np.uint8),
            "label": np.zeros(rows, np.int64),
        }
        for i in range(n)
    ]


def _dsvc_marker(batch) -> int:
    # Invert the image decode's normalization (x = v/255 - 0.5).
    return int(round((float(batch["image"].flat[0]) + 0.5) * 255))


def test_data_service_client_faults_heal(caplog):
    """r8 fault matrix, input leg: connection drops AND delays targeted at
    the data-service client roles (``<role>_ds``) — the clients reconnect
    into the SAME server incarnation, whose replay-safe GET_SPLIT re-answers
    the held split, so the epoch still covers every split exactly once with
    no duplicate deliveries."""
    caplog.set_level("INFO", logger="dtx.faults")
    from distributed_tensorflow_examples_tpu.data import data_service as dsvc

    os.environ["DTX_FAULT_PLAN"] = (
        "drop_conn:role=dw0_ds,op=6;drop_conn:role=dw1_ds,op=9,count=2;"
        "delay:role=dw*_ds,op=4,count=6,ms=10"
    )
    srv = dsvc.DataServiceServer(_dsvc_splits(6, rows=8), batch_size=4, seed=0)
    seen = {0: set(), 1: set()}
    errors: list = []

    def worker(w):
        try:
            src = dsvc.RemoteDatasetSource(
                f"dsvc://127.0.0.1:{srv.port}", worker_id=w, role=f"dw{w}_ds",
                op_timeout_s=10.0, reconnect_deadline_s=30.0,
            )
            for b in src.batches(repeat=False):
                seen[w].add(int(b["image"][0, 0]))
            src.close()
        except BaseException as e:  # noqa: BLE001
            errors.append((w, e))

    try:
        ts = [threading.Thread(target=worker, args=(w,)) for w in (0, 1)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=90)
        assert not any(t.is_alive() for t in ts), "workers hung"
        assert not errors, errors
        assert seen[0] | seen[1] == set(range(6))
        assert not (seen[0] & seen[1]), (seen, "duplicate delivery")
        events = [
            r.getMessage() for r in caplog.records if "dtx.faults" in r.getMessage()
        ]
        assert any("inject_drop_conn" in m and "role=dw0_ds" in m for m in events), events
        assert any("inject_delay" in m and "_ds" in m for m in events), events
        assert any("event=reconnected" in m and "_ds" in m for m in events), events
    finally:
        os.environ.pop("DTX_FAULT_PLAN", None)
        srv.stop()


_DSVC_TASK_SCRIPT = """\
import os, sys
sys.path.insert(0, {root!r})
os.environ.setdefault("JAX_PLATFORMS", "cpu")
from types import SimpleNamespace

from distributed_tensorflow_examples_tpu.train import ps_experiment

FLAGS = SimpleNamespace(
    job_name="data_service", task_index=0, ps_hosts="",
    data_service_hosts="127.0.0.1:{port}", worker_hosts="a:1,b:1",
    ps_tasks=1, ps_listen_all=False, ps_restarts=2, data_dir={data_dir!r},
    batch_size=8, train_steps=60, log_dir="", checkpoint_every_steps=50,
    replicas_to_aggregate=0, max_staleness=0, deterministic=False, seed=0,
    grad_accum=1,
)
ps_experiment.run_ps_cluster_task(
    init_fn=None, loss_fn=None, optimizer=None, batches_for_worker=None,
    FLAGS=FLAGS, mode="async", eval_fn=None,
)
"""


def test_data_service_kill_mid_epoch_heals_via_supervised_restart(tmp_path, caplog):
    """r8 acceptance: the data-service TASK is killed mid-epoch by the
    fault plan (``die:after_reqs`` against role ``data_service0``), its
    supervisor restarts it (stripping the fired spec), the clients
    reconnect into the new incarnation and RE-CLAIM their in-flight splits,
    and between the two workers every split is still visited at least
    once."""
    caplog.set_level("INFO", logger="dtx.faults")
    import socket as _socket

    from distributed_tensorflow_examples_tpu.data import (
        data_service as dsvc,
        filestream,
    )

    # 9 shards of 16 marker-valued NHWC rows (the task's decode_fn is the
    # image decoder); the last shard is held out as the eval chunk, leaving
    # 8 train splits of 4 local batches each.
    n_train = 8
    marker = np.repeat(np.arange(9, dtype=np.uint8), 16)
    filestream.write_array_shards(
        str(tmp_path / "shards"),
        {
            "image": np.broadcast_to(
                marker[:, None, None, None], (144, 2, 2, 3)
            ).copy(),
            "label": np.zeros(144, np.int64),
        },
        rows_per_shard=16,
    )
    s = _socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    script = tmp_path / "dsvc_task.py"
    script.write_text(
        _DSVC_TASK_SCRIPT.format(
            root=ROOT, port=port, data_dir=str(tmp_path / "shards")
        )
    )
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # Kill the data server once it has served 25 requests — mid-epoch: the
    # 2-worker single-epoch run issues ~50 (32 batches + split/handshake
    # traffic), while task startup alone stays well under the trigger.
    env["DTX_FAULT_PLAN"] = "die:role=data_service0,after_reqs=25"
    logf = open(tmp_path / "dsvc_task.log", "w")
    proc = subprocess.Popen(
        [sys.executable, str(script)],
        stdout=logf, stderr=subprocess.STDOUT, env=env, cwd=ROOT,
    )
    seen = {0: set(), 1: set()}
    errors: list = []

    def worker(w):
        try:
            src = dsvc.RemoteDatasetSource(
                f"dsvc://127.0.0.1:{port}", worker_id=w, role=f"dw{w}_ds",
                op_timeout_s=10.0, reconnect_deadline_s=120.0,
            )
            for b in src.batches(repeat=False):
                seen[w].add(_dsvc_marker(b))
                time.sleep(0.03)  # spread the epoch across the kill point
            src.close()
        except BaseException as e:  # noqa: BLE001
            errors.append((w, e))

    try:
        # Wait for the first incarnation to answer.
        deadline = time.time() + 120
        up = False
        while time.time() < deadline:
            try:
                probe = dsvc.DataServiceClient(
                    "127.0.0.1", port, role="probe_ds", reconnect_deadline_s=0.0
                )
                probe.close()
                up = True
                break
            except OSError:
                time.sleep(0.2)
        assert up, "data service task never came up"

        ts = [threading.Thread(target=worker, args=(w,)) for w in (0, 1)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=180)
        assert not any(t.is_alive() for t in ts), "workers hung"
        assert not errors, errors
        assert seen[0] | seen[1] == set(range(n_train)), (
            seen, "a split was never visited across the data-server restart",
        )
        # The clients crossed a NEW incarnation (restart detected).
        events = [
            r.getMessage() for r in caplog.records if "dtx.faults" in r.getMessage()
        ]
        assert any("event=dsvc_reincarnation" in m for m in events), events

        # Clean shutdown of the healed second incarnation.
        ctl = dsvc.DataServiceClient("127.0.0.1", port, role="ctl_ds")
        ctl.shutdown_server()
        ctl.close()
        proc.wait(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
        logf.close()
    task_log = (tmp_path / "dsvc_task.log").read_text()
    assert "event=inject_die" in task_log, task_log[-2000:]
    assert "event=supervisor_healed_plan" in task_log, task_log[-2000:]
    assert "DSVC_DONE" in task_log, task_log[-2000:]
    assert proc.returncode == 0, task_log[-2000:]


def test_serve_client_faults_heal(caplog):
    """r10 fault matrix, serving leg: connection drops AND delays targeted
    at the serving-wire client roles (``<role>_sv``) — predict is pure, so
    the client reconnects and REPLAYS it safely; answers stay correct and
    stamped with the served model_step throughout."""
    caplog.set_level("INFO", logger="dtx.faults")
    from distributed_tensorflow_examples_tpu import serve
    from distributed_tensorflow_examples_tpu.parallel import ps_shard

    port = ps_service.start_server(0)
    addrs = [("127.0.0.1", port)]
    group = ps_shard.ShardedPSClients(addrs, role="pub", op_timeout_s=10.0)
    pstore = ps_shard.ShardedParamStore(
        group, "params", ps_shard.ShardLayout(12, 1)
    )
    flat = np.arange(12, dtype=np.float32)
    pstore.set(3, flat)

    def init_fn(rng):
        import jax.numpy as jnp

        return {"w": jnp.zeros((4, 3), jnp.float32)}

    srv = serve.ModelReplicaServer(
        init_fn, lambda p, b: b["x"] @ p["w"], addrs, max_batch=4,
        max_wait_ms=2.0, refresh_ms=10.0, role="srv_f",
    )
    os.environ["DTX_FAULT_PLAN"] = (
        "drop_conn:role=cl0_sv,op=3;drop_conn:role=cl1_sv,op=5,count=2;"
        "delay:role=cl*_sv,op=2,count=4,ms=10"
    )
    try:
        assert srv.wait_for_model(30.0)
        x = np.eye(4, dtype=np.float32)
        want = x @ flat.reshape(4, 3)
        errors: list = []

        def client_body(i):
            try:
                c = serve.ServeClient(
                    "127.0.0.1", srv.port, role=f"cl{i}_sv",
                    op_timeout_s=10.0, reconnect_deadline_s=30.0,
                )
                for _ in range(8):
                    step, out = c.predict({"x": x})
                    assert step == 3
                    np.testing.assert_allclose(out["output"], want, rtol=1e-6)
                c.close()
            except BaseException as e:  # noqa: BLE001
                errors.append((i, e))

        ts = [threading.Thread(target=client_body, args=(i,)) for i in (0, 1)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in ts), "serve clients hung"
        assert not errors, errors
        events = [
            r.getMessage() for r in caplog.records if "dtx.faults" in r.getMessage()
        ]
        assert any(
            "inject_drop_conn" in m and "role=cl0_sv" in m for m in events
        ), events
        assert any("inject_delay" in m and "_sv" in m for m in events), events
        assert any(
            "event=reconnected" in m and "_sv" in m for m in events
        ), events
    finally:
        os.environ.pop("DTX_FAULT_PLAN", None)
        srv.stop()
        group.close()
        ps_service.stop_server()


_SERVE_TASK_SCRIPT = """\
import os, sys
sys.path.insert(0, {root!r})
os.environ.setdefault("JAX_PLATFORMS", "cpu")
from types import SimpleNamespace

from distributed_tensorflow_examples_tpu import models
from distributed_tensorflow_examples_tpu.train import ps_experiment

CFG = models.mlp.Config(hidden=(16,), compute_dtype="float32")

FLAGS = SimpleNamespace(
    job_name="serve", task_index={task_index}, ps_hosts={ps_hosts!r},
    serve_hosts={serve_hosts!r}, worker_hosts="a:1,b:1", ps_tasks=1,
    ps_shards=-1, ps_listen_all=False, ps_restarts=2,
    serve_max_batch=16, serve_max_wait_ms=3.0, serve_queue_depth=256,
    serve_refresh_ms=25.0,
    batch_size=8, train_steps=60, log_dir="", checkpoint_every_steps=50,
    replicas_to_aggregate=0, max_staleness=0, deterministic=False, seed=0,
    grad_accum=1,
)
ps_experiment.run_ps_cluster_task(
    init_fn=lambda rng: models.mlp.init(CFG, rng),
    loss_fn=models.mlp.loss_fn(CFG),
    optimizer=None, batches_for_worker=None, FLAGS=FLAGS, mode="async",
    eval_fn=None,
    predict_fn=lambda params, batch: models.mlp.apply(
        CFG, params, batch["image"]
    ),
)
"""


def test_serve_replica_kill_mid_load_heals_via_supervised_restart(tmp_path, caplog):
    """r10 acceptance (the serving tentpole scenario): a 2-replica serve
    cluster behind a 2-shard PS serves correct predictions while a REAL
    training chief (+ 2 workers) publishes new params — every replica's
    served model_step advances WITHOUT a restart (same incarnation across
    the advance) — and replica 0 is KILLED mid-load by its fault plan
    (``die:after_reqs``), its supervisor restarts it (stripping the fired
    spec), the fresh incarnation re-pulls the CURRENT params straight from
    the PS (zero coordination) and rejoins the pool's rotation, with ZERO
    failed client requests across the whole run (the pool's deadline +
    ejection absorbs the gap)."""
    caplog.set_level("INFO", logger="dtx.faults")
    from distributed_tensorflow_examples_tpu import serve

    ps_ports = _free_ports(2)
    serve_ports = _free_ports(2)
    # The 2-shard PS lives in THIS process, outliving the training run so
    # the restarted replica has a live store to re-pull from.
    for i, p in enumerate(ps_ports):
        ps_service.start_server(p, shard_id=i, shard_count=2)
    ps_hosts = ",".join(f"127.0.0.1:{p}" for p in ps_ports)
    serve_hosts = ",".join(f"127.0.0.1:{p}" for p in serve_ports)
    env_base = dict(os.environ)
    env_base["JAX_PLATFORMS"] = "cpu"
    env_base.pop("DTX_FAULT_PLAN", None)
    procs, logs = [], []
    stop_load = threading.Event()
    load_errors: list = []
    load_ok = [0]
    # (incarnation, model_step) samples per replica, appended in time order
    # by the monitor — the no-restart/advance and restart evidence.
    samples: dict[int, list[tuple[int, int]]] = {0: [], 1: []}
    try:
        for tid in (0, 1):
            script = tmp_path / f"serve_task_{tid}.py"
            script.write_text(
                _SERVE_TASK_SCRIPT.format(
                    root=ROOT, task_index=tid, ps_hosts=ps_hosts,
                    serve_hosts=serve_hosts,
                )
            )
            env = dict(env_base)
            if tid == 0:
                # Replica 0 dies once it has served 250 requests — mid-load
                # (the pool's round-robin reaches it within seconds), well
                # past startup/stats chatter.
                env["DTX_FAULT_PLAN"] = "die:role=serve0,after_reqs=250"
            logf = open(tmp_path / f"serve_task_{tid}.log", "w")
            logs.append(logf)
            procs.append(
                subprocess.Popen(
                    [sys.executable, str(script)],
                    stdout=logf, stderr=subprocess.STDOUT, env=env, cwd=ROOT,
                )
            )

        pool = serve.ServePool(
            [("127.0.0.1", p) for p in serve_ports], role="load_sv",
            op_timeout_s=10.0, eject_s=1.0, deadline_s=120.0,
        )
        x = next(_blob_batches(5, batch=4))["image"]

        def load_body():
            # Continuous client load: EVERY logical predict must succeed —
            # overload/unavailable/transport gaps are absorbed by the
            # pool's rotation + retry, the kill by its ejection window.
            while not stop_load.is_set():
                try:
                    step, out = pool.predict({"image": x})
                    assert step >= 0 and out["output"].shape == (4, 10)
                    load_ok[0] += 1
                except BaseException as e:  # noqa: BLE001
                    load_errors.append(e)
                    return
                time.sleep(0.005)

        def monitor_body():
            clients: dict[int, object] = {}
            while not stop_load.is_set():
                for i, p in enumerate(serve_ports):
                    try:
                        c = clients.get(i)
                        if c is None:
                            c = serve.ServeClient(
                                "127.0.0.1", p, role="mon_sv",
                                op_timeout_s=5.0, reconnect_deadline_s=0.0,
                            )
                            clients[i] = c
                        st = c.stats()
                        samples[i].append(
                            (int(st["incarnation"]), int(st["model_step"]))
                        )
                    except Exception:
                        clients.pop(i, None)  # replica down/restarting
                time.sleep(0.1)
            for c in clients.values():
                try:
                    c.close()
                except Exception:
                    pass

        # Both replicas answer stats before load starts (NO_MODEL is fine
        # at this point — the chief has not published yet).
        deadline = time.time() + 120
        for p in serve_ports:
            while True:
                try:
                    c = serve.ServeClient(
                        "127.0.0.1", p, role="probe_sv",
                        op_timeout_s=5.0, reconnect_deadline_s=0.0,
                    )
                    c.stats()
                    c.close()
                    break
                except (OSError, serve.ServeError):
                    assert time.time() < deadline, (
                        f"serve replica at port {p} never came up"
                    )
                    time.sleep(0.2)

        loaders = [threading.Thread(target=load_body) for _ in range(2)]
        mon = threading.Thread(target=monitor_body)
        for t in loaders:
            t.start()
        mon.start()

        # The REAL training run: chief + 2 workers in this process against
        # the same 2-shard PS the replicas track; every applied update is
        # published to the store the replicas poll.
        chief = _run_socket_training(
            steps=40,
            ps_addrs=[("127.0.0.1", p) for p in ps_ports],
            reconnect_deadline_s=90.0, join_timeout=240.0,
            stop_servers=False,
        )
        assert chief.global_step == 40

        # Keep the load running until replica 0's RESTART is visible (a
        # second incarnation answering stats) and both replicas track the
        # final published step — then the heal is complete end to end.
        deadline = time.time() + 150
        while time.time() < deadline:
            incs0 = {inc for inc, _ in samples[0]}
            caught_up = all(
                any(step == 40 for _, step in samples[i]) for i in (0, 1)
            )
            if len(incs0) >= 2 and caught_up and not load_errors:
                break
            if load_errors:
                break
            time.sleep(0.2)

        # Final correctness: the pool's answer at the final step matches a
        # local apply of the chief's final params bit-for-bit shape-wise.
        step, out = pool.predict({"image": x})
        assert step == 40, step
        want = np.asarray(models.mlp.apply(CFG, chief.params, x))
        np.testing.assert_allclose(out["output"], want, rtol=1e-4, atol=1e-5)

        stop_load.set()
        for t in loaders:
            t.join(timeout=30)
        mon.join(timeout=30)

        # ZERO failed client requests across the kill+restart.
        assert not load_errors, load_errors
        assert load_ok[0] > 50, load_ok
        # Every replica's served step ADVANCED within one incarnation (hot
        # tracking, not restart): some incarnation shows >= 2 distinct
        # steps.
        for i in (0, 1):
            by_inc: dict[int, set[int]] = {}
            for inc, step in samples[i]:
                by_inc.setdefault(inc, set()).add(step)
            assert any(
                len(steps - {-1}) >= 2 for steps in by_inc.values()
            ), (i, by_inc)
        # Replica 0 really restarted (two incarnations seen) and the healed
        # incarnation re-pulled the current params.
        incs0 = [inc for inc, _ in samples[0]]
        assert len(set(incs0)) >= 2, set(incs0)
        last_inc0 = incs0[-1]
        assert any(
            inc == last_inc0 and step == 40 for inc, step in samples[0]
        ), samples[0][-10:]

        # Clean shutdown of both replicas (the healed second incarnation of
        # replica 0 included).
        pool.close()
        for p in serve_ports:
            ctl = serve.ServeClient(
                "127.0.0.1", p, role="ctl_sv", op_timeout_s=10.0,
            )
            ctl.shutdown_server()
            ctl.close()
        for pr in procs:
            pr.wait(timeout=60)
    finally:
        stop_load.set()
        for pr in procs:
            if pr.poll() is None:
                pr.kill()
                pr.wait()
        for f in logs:
            f.close()
        ps_service.stop_server()
    log0 = (tmp_path / "serve_task_0.log").read_text()
    log1 = (tmp_path / "serve_task_1.log").read_text()
    # Replica 0: injected death fired, supervisor healed the plan, second
    # incarnation served to clean shutdown.  Replica 1: no death at all.
    assert "event=inject_die" in log0, log0[-2000:]
    assert "event=supervisor_healed_plan" in log0, log0[-2000:]
    assert "SERVE_DONE" in log0, log0[-2000:]
    assert "event=inject_die" not in log1, log1[-2000:]
    assert "SERVE_DONE" in log1, log1[-2000:]
    assert procs[0].returncode == 0 and procs[1].returncode == 0


@pytest.mark.slow
def test_worker_die_fault_in_multiprocess_cluster():
    """Fault-plan-driven worker death in a REAL 3-process cluster (the
    harness-level analog of test_ps_remote's SIGKILL test): task 2's
    process exits via ``die:after_s`` mid-run; the chief keeps aggregating
    from the survivor and reaches the step target."""
    import tempfile

    from distributed_tensorflow_examples_tpu.utils.multiprocess import (
        MultiProcessRunner,
    )

    d = tempfile.mkdtemp(prefix="dtx_fault_mp_")
    script = """
import os, sys, time
import numpy as np
import jax, jax.numpy as jnp
import optax

from distributed_tensorflow_examples_tpu.parallel import async_ps
from distributed_tensorflow_examples_tpu.utils import faults

idx = int(sys.argv[1])
d = os.environ["DTX_PS_DIR"]
dim = 8
W_TRUE = np.arange(dim, dtype=np.float32)


def init_fn(rng):
    return {"w": jnp.zeros((dim,), jnp.float32)}


def loss_fn(params, model_state, batch, rng):
    pred = batch["x"] @ params["w"]
    l = jnp.mean((pred - batch["y"]) ** 2)
    return l, (model_state, {"loss": l})


def batches(seed):
    r = np.random.default_rng(seed)
    while True:
        time.sleep(0.02)
        x = r.normal(size=(32, dim)).astype(np.float32)
        yield {"x": x, "y": x @ W_TRUE}


cfg = async_ps.AsyncPSConfig(
    num_workers=2, mode="sync_replicas", train_steps=120,
    replicas_to_aggregate=1,
)
faults.arm_process_faults()
if idx == 0:
    chief = async_ps.RemotePSChief(
        cfg, loss_fn, optax.sgd(0.05), init_fn(jax.random.key(0))
    )
    with open(os.path.join(d, "port.tmp"), "w") as f:
        f.write(str(chief.port))
    os.rename(os.path.join(d, "port.tmp"), os.path.join(d, "port"))
    params = chief.run_chief()
    err = float(np.abs(np.asarray(params["w"]) - W_TRUE).max())
    print(f"CHIEF_DONE step={chief.global_step} err={err:.4f}", flush=True)
else:
    p = os.path.join(d, "port")
    for _ in range(600):
        if os.path.exists(p):
            break
        time.sleep(0.1)
    port = int(open(p).read())
    n = async_ps.remote_worker_loop(
        "127.0.0.1", port, idx, cfg=cfg, loss_fn=loss_fn, init_fn=init_fn,
        batches=batches(idx),
    )
    print(f"WORKER_DONE n={n}", flush=True)
"""
    r = MultiProcessRunner(
        3, script,
        env={"DTX_PS_DIR": d},
        fault_plan="die:role=task2,after_s=1.5",
        timeout=300.0,
        prelude=False,
    )
    r.start()
    codes = r.join()
    outs = [r.output(i) for i in range(3)]
    assert codes[0] == 0, outs[0][-2000:]
    assert codes[2] == faults.FAULT_EXIT_CODE, (codes, outs[2][-800:])
    assert "event=inject_die" in outs[2], outs[2][-800:]
    assert "CHIEF_DONE step=120" in outs[0], outs[0][-2000:]
    err = float(outs[0].split("err=")[1].split()[0])
    assert err < 0.5, outs[0][-2000:]
    r.cleanup()


# ----------------------------------------------------------------------------
# Membership events (r14): lease heartbeat transport + join/leave kinds
# ----------------------------------------------------------------------------


def test_membership_heartbeat_lm_drop_conn_heals(caplog, monkeypatch):
    """The ``_lm`` (lease/membership) client leg under injected faults:
    a ``drop_conn:role=member0_lm,op=2`` severs the heartbeat's socket
    mid-renewal; the owned PSClient reconnects and the lease stays live —
    membership survives the same transport chaos as every other wire."""
    from distributed_tensorflow_examples_tpu.parallel import membership

    monkeypatch.setenv(
        "DTX_FAULT_PLAN", "drop_conn:role=member0_lm,op=2,count=2"
    )
    port = ps_service.start_server(0)
    caplog.set_level("INFO", logger="dtx.faults")
    hb = membership.LeaseHeartbeat(
        [("127.0.0.1", port)], "member0", kind="worker", ttl_s=0.6,
        role="member0", reconnect_deadline_s=10.0,
    )
    try:
        deadline = time.monotonic() + 10.0
        while hb.renewals < 4 and time.monotonic() < deadline:
            time.sleep(0.05)
        assert hb.renewals >= 4, "heartbeat wedged after the injected drop"
        c = ps_service.PSClient("127.0.0.1", port, timeout_s=5.0)
        live = membership.live_members(c, "worker")
        c.close()
        assert [m["member"] for m in live] == ["member0"]
    finally:
        hb.close()
        ps_service.stop_server()
    assert any(
        "event=inject_drop_conn" in r.message and "member0_lm" in r.message
        for r in caplog.records
    ), "the _lm drop never fired"
    assert any("event=reconnected" in r.message for r in caplog.records)


def test_leave_fault_departs_cleanly_with_exit_zero(tmp_path):
    """The ``leave`` membership kind: the matching process runs its
    registered leave hooks (lease release) and exits 0 — a clean
    departure a supervisor must NOT restart, distinct from ``die``'s
    exit-43 crash.  Plan: ``leave:role=member1,after_s=0.3``."""
    marker = tmp_path / "left"
    script = f"""
import sys, time
sys.path.insert(0, {ROOT!r})
from distributed_tensorflow_examples_tpu.utils import faults
faults.set_role("member1")
faults.register_leave_hook(
    lambda: open({str(marker)!r}, "w").write("hooks-ran")
)
faults.arm_process_faults()
time.sleep(30)  # the leave fires long before this
print("NOT-REACHED")
"""
    env = dict(os.environ)
    env["DTX_FAULT_PLAN"] = "leave:role=member1,after_s=0.3"
    r = subprocess.run(
        [sys.executable, "-c", script], env=env, capture_output=True,
        text=True, timeout=120,
    )
    assert r.returncode == 0, (r.returncode, r.stderr[-500:])
    assert "NOT-REACHED" not in r.stdout
    assert marker.read_text() == "hooks-ran"
    assert "event=inject_leave" in r.stderr


def test_join_specs_are_orchestrator_events(caplog):
    """The ``join`` membership kind parses (``join:role=worker2,
    after_s=5``), surfaces through ``faults.join_specs`` for the
    orchestrator (loadsim spawns the member), and in-process arming
    SKIPS it loudly — a plan wired to the wrong process is never
    silently inert."""
    plan = "join:role=worker2,after_s=5;die:role=ps0,after_s=9"
    specs = faults.join_specs(plan)
    assert [s.role for s in specs] == ["worker2"]
    assert faults.join_specs(plan, "worker2")
    assert not faults.join_specs(plan, "chief0")
    # join without after_s fails the launch loudly.
    with pytest.raises(ValueError):
        faults.parse_plan("join:role=worker2")
    with pytest.raises(ValueError):
        faults.parse_plan("leave:role=worker0")
    caplog.set_level("INFO", logger="dtx.faults")
    faults.set_role("worker2")
    try:
        os.environ["DTX_FAULT_PLAN"] = plan
        threads = faults.arm_process_faults()
        assert threads == []  # join skipped; ps0's die doesn't match
    finally:
        os.environ.pop("DTX_FAULT_PLAN", None)
    assert any(
        "event=fault_unarmed" in r.message
        and "join_is_orchestrated" in r.message
        for r in caplog.records
    )
