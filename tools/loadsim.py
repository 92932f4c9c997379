"""loadsim — closed-loop chaos load simulator + SLO gate (r14 tentpole).

Boots a REAL multi-process train-and-serve cluster off the product CLI
(``examples/mnist_mlp.py`` — supervised PS task(s), chief, async workers,
supervised serve replicas), drives BOTH planes simultaneously — training
runs free while a closed-loop generator holds the serve pool at a target
qps — and runs a continuous membership-chaos timeline from one
``DTX_FAULT_PLAN``:

- kills (``die``) of the PS task, a worker, and a serve replica — each
  healed by the machinery under test (supervised restart + client
  reconnect for services; lease EXPIRY for the unsupervised worker);
- a ``join``: a brand-new worker (and optionally serve replica) process
  spawned MID-RUN, which acquires a membership lease, pulls current
  params and contributes with no restart of anything else — the
  orchestrator half of the membership event kinds (``faults.join_specs``);
- a ``leave``: a worker departs gracefully (releases its lease, exits 0).

Throughout, the cluster is scraped over the same wires any operator
tooling uses (``tools/dtxtop.snapshot`` — serve replicas are discovered
from the LEASE REGISTRY, not static flags, so the elastic pool is
followed as it changes), and once mid-run the real ``python -m
tools.dtxtop --json`` CLI is shelled out and must exit 0 showing the
joined worker's lease.

The run ends in a machine-readable SLO VERDICT (last stdout line, and
``--out``):

- ``predict_failed == 0`` — zero failed serve requests across the whole
  kill/join/leave cycle (the ServePool rotation absorbs every fault);
- ``p99_ms <= p99_bound_ms`` at the achieved qps;
- the training global step (the served ``model_step``) is MONOTONE
  across every scrape and STRICTLY advances across the chaos window;
- the joined worker's lease was observed by the mid-run dtxtop scrape.

Exit code 0 iff every gate holds — the standing acceptance rig ROADMAP
items 1–4 gate on, runnable on any CPU dev box.

Usage::

    python tools/loadsim.py --qps=100 --duration_s=30 --p99_bound_ms=250

r17: the default scenario drives 4x the original closed-loop client count
(16 generator connections at qps 100) with the SLO gates unchanged — the
serve plane now rides the unified server core (parallel/server_core.py).

r18 (``--scenario=overload``): the graceful-degradation acceptance — a
baseline phase, then an UNPACED 4x burst slams the serve pool past its
(deliberately bounded) capacity, then recovery.  Gates: goodput floor
during the burst, zero lease expirations (control ops are never shed),
p99 back under a bounded multiple of baseline within ``--recovery_bound_s``
of burst end (the no-metastability proof), training step monotone and
advancing throughout.  See ``run_overload``.

r20 (``--scenario=multitenant``): the noisy-neighbor isolation
acceptance — two tenants' training runs share ONE PS tier and ONE serve
pool; tenant ``runa`` goes 4x-noisy mid-run while tenant ``runb``'s paced
SLO traffic must stay spotless.  Gates: the per-tenant quotas shed ONLY
``runa``, ``runb`` never fails a predict and its p99 stays bounded, both
tenants' PS namespaces and leased members stay disjointly visible to
dtxtop's per-tenant rollup.  See ``run_multitenant``.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# A host-side rig: this process and every child it starts run JAX on the
# CPU, whatever the machine's own JAX_PLATFORMS says (the chip machine sets
# "tpu,cpu", and a chip belongs to one process at a time).
os.environ["JAX_PLATFORMS"] = "cpu"

#: Verdict schema version (tests pin it).
VERDICT_SCHEMA_VERSION = 1

#: Chaos timeline, as fractions of the load window: when each membership
#: event fires relative to load start.  Kills come first (heal under
#: load), the join lands while the killed worker's lease is expiring, the
#: leave runs last — so the run ends on the JOINED member carrying
#: training alone, the strongest elasticity evidence.
PHASES = {
    "kill_ps": 0.20,
    "kill_serve": 0.35,
    "join_worker": 0.45,
    "kill_worker": 0.60,
    "leave_worker": 0.75,
}

#: Reshard-scenario timeline (r15, ``--scenario=reshard``): resize the PS
#: tier N→N+1→N shards mid-run under closed-loop predict load, with one
#: worker kill landing between the transitions — the ROADMAP item 3
#: acceptance: zero reseeds, zero failed predicts, monotone strictly
#: advancing step, both epoch transitions visible to dtxtop.
RESHARD_PHASES = {
    "reshard_up": 0.20,
    "kill_worker": 0.45,
    "reshard_down": 0.55,
}

#: Overload-scenario timeline (r18, ``--scenario=overload``), as fractions
#: of the load window: a baseline phase establishes the healthy p99, then
#: an UNPACED burst generator (``--burst_threads``, default 4x the paced
#: client count) slams the serve pool past capacity, then the burst stops
#: and the recovery clock runs.  The no-metastability proof: goodput
#: holds a floor DURING the burst (admission sheds excess instead of
#: collapsing), no live member's lease expires (control ops are never
#: shed), and p99 returns to a bounded multiple of baseline WITHIN
#: ``--recovery_bound_s`` after the burst ends (retry budgets + jittered
#: backoff keep the recovering clients from re-overloading the cluster —
#: the storm dies WITH the burst, it does not outlive it).
OVERLOAD_PHASES = {
    "burst_start": 0.35,
    "burst_end": 0.65,
}

#: Multitenant-scenario timeline (r20, ``--scenario=multitenant``), as
#: fractions of the load window: two tenants' training runs (``runa``,
#: ``runb``) share ONE PS tier and ONE serve pool; tenant ``runb``'s paced
#: SLO traffic establishes a baseline, then tenant ``runa`` goes 4x-noisy
#: (unpaced closed-loop clients) for the middle of the window, then stops.
#: The isolation proof: the serve cores' per-tenant quotas shed ONLY
#: ``runa`` (``shed_quota`` trips on its rows and stays zero on
#: ``runb``'s), ``runb``'s paced traffic never fails a predict and its
#: noisy-window p99 stays under a bounded multiple of its own baseline,
#: and both tenants' PS namespaces stay disjointly visible to dtxtop.
MULTITENANT_PHASES = {
    "noise_start": 0.35,
    "noise_end": 0.65,
}

#: Canary-scenario timeline (r19, ``--scenario=canary``), as fractions of
#: the load window: v1 registry replicas serve from t0; mid-run the
#: orchestrator publishes v2 (the training run's CURRENT params — the
#: registry decouples deploys from the live run), spawns ONE canary
#: replica pinned v2 and routes ``--canary_weight`` of the paced traffic
#: at it; a stable replica is KILLED during the flip (healed by its
#: supervisor, re-pinning v1 — a restart cannot change what a replica
#: serves); then the rolling promote spawns v2 replacements (surge) and
#: retires every v1 task.  Gates: zero failed predicts through the whole
#: flip, canary weight honored ±tolerance, the served model_version
#: monotone across scrapes and all-v2 at the end, both versions visible
#: to dtxtop's per-version rollup mid-flip.
CANARY_PHASES = {
    "publish_v2": 0.18,
    "canary_up": 0.22,
    "kill_serve": 0.40,
    "promote_start": 0.55,
    "retire_old": 0.72,
}


def free_ports(n: int) -> list[int]:
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


def build_plan(ready_s: float, duration_s: float, join_worker_id: int) -> str:
    """The cluster-wide DTX_FAULT_PLAN for one kill/join/leave cycle.
    ``after_s`` triggers arm at each PROCESS's start, so the offsets
    include the boot window (``ready_s``) for tasks launched at t0; the
    ``join`` spec is the orchestrator's own schedule (loadsim spawns the
    worker — ``faults.join_specs`` — nothing in-process arms it)."""
    t = {k: ready_s + f * duration_s for k, f in PHASES.items()}
    return ";".join([
        f"die:role=ps0,after_s={t['kill_ps']:.1f}",
        f"die:role=serve0,after_s={t['kill_serve']:.1f}",
        f"join:role=worker{join_worker_id},after_s={t['join_worker']:.1f}",
        f"die:role=worker1,after_s={t['kill_worker']:.1f}",
        f"leave:role=worker0,after_s={t['leave_worker']:.1f}",
        # Background client-level chaos: transient drops and delays on the
        # training workers' PS legs, healed by reconnect+replay under load.
        "drop_conn:role=worker0,op=25,count=2",
        "delay:role=worker1,op=30,ms=40,count=3",
    ])


class LoadGenerator:
    """Closed-loop predict load at a target qps over a ServePool, with
    replica discovery following the LEASE registry (the elastic pool).

    ``qps=None`` runs UNPACED (r18 overload scenario): every thread
    re-issues the moment its previous predict resolves — the burst
    generator that drives the cluster past capacity.  ``snap_window``
    drains the stats accumulated since the last snap, so the overload
    scenario can measure per-phase p99/goodput from ONE generator
    without restarting its connections.

    ``pool_per_thread=True`` gives every generator thread its OWN static
    ``ServePool`` (burst generators).  ``ServeClient`` serializes ops
    per connection, so N threads sharing one pool hold at most
    one request in flight PER REPLICA no matter how large N is — a
    burst that must exceed the replicas' admission bounds needs N
    independent connections, the real N-clients overload shape."""

    def __init__(
        self, ps_addrs, serve_addrs, *, qps: float | None, threads: int = 16,
        deadline_s: float = 60.0, role: str = "loadsim_sv",
        op_timeout_s: float | None = 10.0, rows: int = 4,
        pool_per_thread: bool = False, tenant: str = "default",
    ):
        from distributed_tensorflow_examples_tpu import serve

        self.qps = None if qps is None else float(qps)
        self.rows = int(rows)
        self._serve_addrs = list(serve_addrs)
        self._deadline_s = deadline_s
        self._op_timeout_s = op_timeout_s
        self._pool_per_thread = bool(pool_per_thread)
        self.tenant = tenant
        self.role = role
        self.ok = 0
        self.failed = 0
        self.errors: list[str] = []
        self.latencies_ms: list[float] = []
        self._win_ok = 0
        self._win_failed = 0
        self._win_lat: list[float] = []
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self.pool = serve.ServePool(
            list(serve_addrs), role=role, deadline_s=deadline_s,
            op_timeout_s=op_timeout_s, tenant=tenant,
        )
        # No PS addresses = static pool only (the burst-child processes:
        # a 10s burst needs no elastic discovery).
        self.discovery = (
            serve.LeaseServeDiscovery(list(ps_addrs), self.pool, poll_s=1.0)
            if ps_addrs
            else None
        )
        self._threads = [
            threading.Thread(
                target=self._loop, args=(i, max(1, threads)), daemon=True,
                name=f"loadsim-gen{i}",
            )
            for i in range(max(1, threads))
        ]

    def _loop(self, tid: int, n_threads: int) -> None:
        import numpy as np

        pool = self.pool
        if self._pool_per_thread:
            from distributed_tensorflow_examples_tpu import serve

            pool = serve.ServePool(
                list(self._serve_addrs), role=f"{self.role}{tid}",
                deadline_s=self._deadline_s,
                op_timeout_s=self._op_timeout_s, tenant=self.tenant,
            )
        x = np.zeros((self.rows, 784), np.float32)
        period = None if self.qps is None else n_threads / self.qps
        next_t = (
            time.monotonic() + tid * period / n_threads
            if period is not None
            else 0.0
        )
        while not self._stop.is_set():
            if period is not None:
                now = time.monotonic()
                if now < next_t:
                    time.sleep(min(next_t - now, 0.05))
                    continue
                next_t += period
            t0 = time.perf_counter()
            try:
                pool.predict({"image": x})
            except Exception as e:  # noqa: BLE001 — every failure is counted
                with self._lock:
                    self.failed += 1
                    self._win_failed += 1
                    if len(self.errors) < 20:
                        self.errors.append(f"{type(e).__name__}: {e}")
                continue
            dt_ms = (time.perf_counter() - t0) * 1e3
            with self._lock:
                self.ok += 1
                self._win_ok += 1
                self.latencies_ms.append(dt_ms)
                self._win_lat.append(dt_ms)
        if pool is not self.pool:
            pool.close()

    def snap_window(self) -> dict:
        """Drain and return the stats accumulated since the last snap
        (phase-local goodput/latency for the overload scenario; the
        cumulative counters for :meth:`stop` are untouched)."""
        with self._lock:
            lat = sorted(self._win_lat)
            ok, failed = self._win_ok, self._win_failed
            self._win_lat, self._win_ok, self._win_failed = [], 0, 0
        pct = lambda p: (  # noqa: E731
            round(lat[min(len(lat) - 1, int(p * len(lat)))], 3) if lat else 0.0
        )
        return {
            "ok": ok, "failed": failed,
            "p50_ms": pct(0.50), "p99_ms": pct(0.99),
        }

    def start(self) -> None:
        for t in self._threads:
            t.start()

    def stop(self) -> dict:
        self._stop.set()
        for t in self._threads:
            t.join(timeout=10.0)
        if self.discovery is not None:
            self.discovery.close()
        self.pool.close()
        with self._lock:
            lat = sorted(self.latencies_ms)
        pct = lambda p: (  # noqa: E731
            round(lat[min(len(lat) - 1, int(p * len(lat)))], 3) if lat else 0.0
        )
        return {
            "predict_ok": self.ok,
            "predict_failed": self.failed,
            "errors": self.errors,
            "p50_ms": pct(0.50),
            "p90_ms": pct(0.90),
            "p99_ms": pct(0.99),
        }


def launch_task(example, common, job, index, logdir, env, log_name=None):
    log_path = os.path.join(logdir, f"{log_name or f'{job}{index}'}.log")
    f = open(log_path, "ab")
    proc = subprocess.Popen(
        [sys.executable, example, *common, f"--job_name={job}",
         f"--task_index={index}"],
        stdout=f, stderr=subprocess.STDOUT, env=env,
    )
    proc._dtx_log = log_path  # type: ignore[attr-defined]
    proc._dtx_logf = f  # type: ignore[attr-defined]
    return proc


def wait_ps_ready(addrs, deadline_s: float) -> bool:
    from distributed_tensorflow_examples_tpu.parallel import ps_service

    t_end = time.monotonic() + deadline_s
    pending = list(addrs)
    while pending and time.monotonic() < t_end:
        h, p = pending[0]
        try:
            c = ps_service.PSClient(h, p, timeout_s=2.0)
            c.ping()
            c.close()
            pending.pop(0)
        except Exception:  # noqa: BLE001
            time.sleep(0.3)
    return not pending


def wait_serve_ready(addrs, deadline_s: float) -> bool:
    from distributed_tensorflow_examples_tpu import serve

    t_end = time.monotonic() + deadline_s
    pending = list(addrs)
    while pending and time.monotonic() < t_end:
        h, p = pending[0]
        try:
            c = serve.ServeClient(
                h, p, op_timeout_s=2.0, reconnect_deadline_s=0.0,
            )
            st = c.stats()
            c.close()
            if int(st.get("model_step", -1)) >= 0:
                pending.pop(0)
                continue
        except Exception:  # noqa: BLE001
            pass
        time.sleep(0.3)
    return not pending


def analyze_steps(step_series: list[tuple[float, int]], markers: dict) -> dict:
    """Step-progress verdict from the scrape series: monotone everywhere,
    and strictly advancing across the chaos window (first→last) and past
    the LAST chaos marker (the joined worker carrying training alone)."""
    steps = [s for _, s in step_series if s >= 0]
    monotone = all(b >= a for a, b in zip(steps, steps[1:]))
    advanced = len(steps) >= 2 and steps[-1] > steps[0]
    last_marker = max(markers.values()) if markers else 0.0
    after_last = [s for t, s in step_series if t >= last_marker and s >= 0]
    advanced_post_chaos = len(after_last) >= 2 and after_last[-1] > after_last[0]
    return {
        "step_first": steps[0] if steps else -1,
        "step_last": steps[-1] if steps else -1,
        "step_monotone": bool(monotone),
        "step_advanced": bool(advanced),
        "step_advanced_post_chaos": bool(advanced_post_chaos),
    }


def run_reshard(args) -> int:
    """The live-resharding acceptance scenario (``--scenario=reshard``):
    boot a real multi-process cluster at N PS shards (layout epoch 1),
    hold closed-loop predict load, then mid-run spawn N+1 ``--ps_reshard_to``
    joiner tasks (epoch 2), kill a worker while the new layout serves,
    and reshard back down to N shards (epoch 3).  SLO verdict
    (``reshard_slo``): zero failed predicts, zero chief reseeds, p99
    under bound, monotone strictly-advancing step, both transitions
    committed within ``--reshard_bound_s`` each, every retired PS task
    drained and exited 0, and all three epochs visible to dtxtop."""
    from distributed_tensorflow_examples_tpu.utils import faults
    from tools import dtxtop

    faults.set_role("loadsim")
    logdir = args.logdir or tempfile.mkdtemp(prefix="dtx-loadsim-rs-")
    os.makedirs(logdir, exist_ok=True)
    n1 = max(1, args.ps_shards)
    n2 = n1 + 1
    topo_shards = {1: n1, 2: n2, 3: n1}
    ports = free_ports(n1 + n2 + n1 + args.serve_replicas)
    topo_ports = {
        1: ports[:n1],
        2: ports[n1 : n1 + n2],
        3: ports[n1 + n2 : n1 + n2 + n1],
    }
    serve_ports = ports[n1 + n2 + n1 :]
    topo_addrs = {
        v: [("127.0.0.1", p) for p in topo_ports[v]] for v in (1, 2, 3)
    }
    serve_addrs = [("127.0.0.1", p) for p in serve_ports]

    def hosts(v):
        return ",".join(f"127.0.0.1:{p}" for p in topo_ports[v])

    def common_for(old_epoch: int):
        return [
            "--sync_replicas=false",
            "--batch_size=64",
            "--train_steps=1000000",  # outlives the window; loadsim tears down
            "--hidden_units=32",
            f"--ps_hosts={hosts(old_epoch)}",
            f"--ps_shards={topo_shards[old_epoch]}",
            "--ps_replicas=1",
            f"--ps_layout_version={old_epoch}",
            f"--worker_hosts={','.join(f'127.0.0.1:{7000 + i}' for i in range(args.workers))}",
            f"--serve_hosts={','.join(f'127.0.0.1:{p}' for p in serve_ports)}",
            "--ps_restarts=3",
            f"--lease_ttl_s={args.lease_ttl_s}",
            "--log_every_steps=50",
        ]

    t_kill = args.boot_offset_s + RESHARD_PHASES["kill_worker"] * args.duration_s
    plan = "" if args.no_chaos else f"die:role=worker1,after_s={t_kill:.1f}"
    env = dict(os.environ)
    env.pop("DTX_FAULT_ROLE", None)
    env["DTX_FAULT_PLAN"] = plan
    procs: dict[str, subprocess.Popen] = {}

    def spawn(name: str, job: str, index: int, extra=(), old_epoch: int = 1):
        procs[name] = launch_task(
            args.example, common_for(old_epoch) + list(extra), job, index,
            logdir, env, log_name=name,
        )

    verdict: dict = {
        "schema_version": VERDICT_SCHEMA_VERSION,
        "metric": "loadsim_reshard_slo",
        "qps_target": args.qps,
        "duration_s": args.duration_s,
        "p99_bound_ms": args.p99_bound_ms,
        "reshard_bound_s": args.reshard_bound_s,
        "logdir": logdir,
        "chaos": not args.no_chaos,
        "shards": [n1, n2, n1],
    }
    gen = None
    step_series: list[tuple[float, int]] = []
    epochs_seen: set[int] = set()
    committed_at: dict[int, float] = {}
    spawned_at: dict[int, float] = {}
    scrape_fail = 0
    cli_probe: dict = {}
    try:
        for i in range(n1):
            spawn(f"ps_v1_{i}", "ps", i)
        if not wait_ps_ready(topo_addrs[1], args.ready_wait_s):
            raise RuntimeError(f"PS tasks never came up (logs: {logdir})")
        spawn("chief0", "chief", 0)
        for i in range(args.workers):
            spawn(f"worker{i}", "worker", i)
        for i in range(args.serve_replicas):
            spawn(f"serve{i}", "serve", i)
        if not wait_serve_ready(serve_addrs, args.ready_wait_s):
            raise RuntimeError(
                f"serve replicas never pulled a model (logs: {logdir})"
            )

        gen = LoadGenerator(
            topo_addrs[1], serve_addrs, qps=args.qps,
            threads=args.gen_threads, deadline_s=max(30.0, args.duration_s),
        )
        gen.start()
        t0 = time.monotonic()
        t_end = t0 + args.duration_s
        markers = {
            name: t0 + frac * args.duration_s
            for name, frac in RESHARD_PHASES.items()
        }
        while time.monotonic() < t_end or (
            len(committed_at) < 2 and time.monotonic() < t_end + 45.0
        ):
            now = time.monotonic()
            if 2 not in spawned_at and now >= markers["reshard_up"]:
                spawned_at[2] = now
                for j in range(n2):
                    spawn(
                        f"ps_v2_{j}", "ps", j,
                        extra=[f"--ps_reshard_to=2:{hosts(2)}"], old_epoch=1,
                    )
                faults.log_event("loadsim_reshard_spawned", version=2)
            if 3 not in spawned_at and now >= markers["reshard_down"] and \
                    2 in committed_at:
                spawned_at[3] = now
                for j in range(n1):
                    spawn(
                        f"ps_v3_{j}", "ps", j,
                        extra=[f"--ps_reshard_to=3:{hosts(3)}"], old_epoch=2,
                    )
                faults.log_event("loadsim_reshard_spawned", version=3)
            # Scrape the newest LIVE topology: a retired tier drains and
            # exits quickly once every client swapped, so the scrape must
            # not stay pinned to a dead coordinator (an operator keeps
            # their --ps_hosts fresh the same way; dtxtop's record-chasing
            # covers the drain window, not a long-gone tier).
            snap = None
            for v in sorted({1, *spawned_at}, reverse=True):
                try:
                    s = dtxtop.snapshot(
                        topo_addrs[v], ps_shards=topo_shards[v],
                        ps_replicas=1, timeout_s=3.0,
                    )
                except Exception:  # noqa: BLE001 — try the next tier
                    continue
                if s["summary"]["roles_ok"] > 0:
                    snap = s
                    break
            if snap is None:
                scrape_fail += 1
            else:
                steps = snap["summary"]["serve"]["model_steps"]
                step_series.append(
                    (time.monotonic(), max(steps) if steps else -1)
                )
                epochs_seen.update(snap["summary"]["ps"].get("epochs", []))
                committed = snap["summary"]["ps"]["reshard"].get(
                    "committed", 0
                )
                for v in (2, 3):
                    if committed >= v and v not in committed_at:
                        committed_at[v] = time.monotonic()
                verdict["members_last"] = snap["summary"]["members"]
            # THE acceptance probe: after the second commit, the real
            # dtxtop CLI must exit 0 against the CURRENT topology and
            # show the final epoch — both transitions chased and visible.
            if 3 in committed_at and not cli_probe:
                cli = subprocess.run(
                    [sys.executable, "-m", "tools.dtxtop", "--json",
                     f"--ps_hosts={hosts(3)}",
                     f"--ps_shards={topo_shards[3]}", "--ps_replicas=1"],
                    capture_output=True, text=True, cwd=ROOT, env=env,
                    timeout=120,
                )
                cli_probe["exit"] = cli.returncode
                try:
                    s = json.loads(cli.stdout.strip().splitlines()[-1])
                    cli_probe["committed"] = (
                        s["summary"]["ps"]["reshard"]["committed"]
                    )
                    cli_probe["epochs"] = s["summary"]["ps"]["epochs"]
                except Exception:  # noqa: BLE001
                    cli_probe["committed"] = -1
            time.sleep(1.0)
        verdict["window_s"] = round(time.monotonic() - t0, 1)
    finally:
        load = gen.stop() if gen is not None else {
            "predict_ok": 0, "predict_failed": -1, "errors": ["never ran"],
            "p50_ms": 0.0, "p90_ms": 0.0, "p99_ms": 0.0,
        }
        # Give retired tiers a moment to finish their drain-exit before
        # the verdict reads their exit codes.
        deadline = time.monotonic() + 10.0
        retired = [
            n for n in procs
            if n.startswith(("ps_v1_", "ps_v2_")) and len(committed_at) >= 2
        ]
        while time.monotonic() < deadline and any(
            procs[n].poll() is None for n in retired
        ):
            time.sleep(0.5)
        verdict["old_ps_exit"] = {n: procs[n].poll() for n in retired}
        for p in procs.values():
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        deadline = time.monotonic() + 15.0
        for p in procs.values():
            try:
                p.wait(timeout=max(0.1, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                p.kill()
            getattr(p, "_dtx_logf").close()

    window = verdict.get("window_s") or args.duration_s
    verdict.update(load)
    verdict["qps_achieved"] = round(load["predict_ok"] / window, 2)
    verdict["scrape_failures"] = scrape_fail
    verdict["epochs_seen"] = sorted(epochs_seen)
    verdict["transition_s"] = {
        str(v): round(committed_at[v] - spawned_at[v], 1)
        for v in committed_at
        if v in spawned_at
    }
    verdict["dtxtop_probe"] = cli_probe
    markers_t = {f"reshard_v{v}": t for v, t in committed_at.items()}
    verdict.update(analyze_steps(step_series, markers_t))

    verdict["chief_reseeds_seen"] = _fired_in(
        procs.get("chief0"), "event=chief_reseed"
    )
    verdict["reshard_commits_seen"] = _fired_in(
        procs.get("chief0"), "event=reshard_committed"
    )
    verdict["kill_fired"] = _fired_in(
        procs.get("worker1"), "event=inject_die"
    )
    gates = {
        "zero_failed_predicts": load["predict_failed"] == 0,
        "p99_under_bound": 0.0 < load["p99_ms"] <= args.p99_bound_ms,
        "qps_at_target": verdict["qps_achieved"] >= 0.6 * args.qps,
        "step_monotone": verdict["step_monotone"],
        "step_advanced": verdict["step_advanced"],
        "step_advanced_post_chaos": verdict["step_advanced_post_chaos"],
        "zero_reseeds": not verdict["chief_reseeds_seen"],
        "both_transitions_committed": len(committed_at) == 2,
        "transitions_bounded": bool(verdict["transition_s"]) and all(
            t <= args.reshard_bound_s for t in verdict["transition_s"].values()
        ),
        "epochs_all_seen": {1, 2, 3} <= epochs_seen,
        "dtxtop_probe_exit0": cli_probe.get("exit") == 0,
        "dtxtop_probe_final_epoch": cli_probe.get("committed") == 3,
        "old_ps_drained_exit0": bool(verdict["old_ps_exit"]) and all(
            rc == 0 for rc in verdict["old_ps_exit"].values()
        ),
    }
    if not args.no_chaos:
        gates["kill_fired"] = verdict["kill_fired"]
    verdict["gates"] = gates
    verdict["slo_pass"] = all(gates.values())
    verdict["loadsim_p99_ms"] = load["p99_ms"]
    if args.out:
        with open(args.out, "w") as f:
            json.dump(verdict, f, indent=2)
    print(json.dumps(verdict))
    return 0 if verdict["slo_pass"] else 1


def run_overload(args) -> int:
    """The graceful-degradation acceptance scenario (``--scenario=overload``,
    r18): boot a real multi-process train-and-serve cluster with BOUNDED
    serve capacity (small batcher queue + a queue-deadline policy), hold
    paced closed-loop predict load, then slam the pool with an unpaced
    burst of ``--burst_threads`` extra clients (>= 4x the paced count) for
    the middle of the window, stop the burst, and measure recovery.

    SLO verdict (``overload_slo``):

    - ``goodput_floor`` — ok-predicts/sec across ALL generators during
      the burst stays above ``--goodput_floor_frac`` x the paced target
      (shedding is graceful: excess is refused, admitted work completes);
    - ``zero_lease_expirations`` — no live member's lease expires during
      the whole run (control ops are never shed, so heartbeats renew
      straight through saturation);
    - ``p99_recovered`` within ``--recovery_bound_s`` of burst end, to
      ``--recovery_factor`` x the baseline p99 (no metastable retry storm
      outliving the burst);
    - training step monotone and strictly advancing across the run;
    - the paced (SLO) traffic never fails a logical predict.
    """
    from distributed_tensorflow_examples_tpu.utils import faults
    from tools import dtxtop

    faults.set_role("loadsim")
    logdir = args.logdir or tempfile.mkdtemp(prefix="dtx-loadsim-ov-")
    os.makedirs(logdir, exist_ok=True)
    n_ps = args.ps_shards * args.ps_replicas
    ports = free_ports(n_ps + args.serve_replicas)
    ps_ports, serve_ports = ports[:n_ps], ports[n_ps:]
    ps_addrs = [("127.0.0.1", p) for p in ps_ports]
    serve_addrs = [("127.0.0.1", p) for p in serve_ports]
    common = [
        "--sync_replicas=false",
        "--batch_size=64",
        "--train_steps=1000000",  # outlives the window; loadsim tears down
        # Bounded serve capacity: the burst must actually EXCEED it on any
        # dev box, or the scenario proves nothing.  A WIDE hidden layer
        # makes each apply genuinely cost milliseconds (the batch thread
        # is one thread, so apply time bounds replica throughput), small
        # max_batch keeps coalescing from buying it back, and the small
        # queue + queue-deadline policy exercise the r18 shed paths under
        # genuine saturation (the `overload_tripped` gate pins that it
        # really happened).
        f"--hidden_units={args.hidden_units}",
        f"--ps_hosts={','.join(f'127.0.0.1:{p}' for p in ps_ports)}",
        f"--ps_shards={args.ps_shards}",
        f"--ps_replicas={args.ps_replicas}",
        f"--worker_hosts={','.join(f'127.0.0.1:{7000 + i}' for i in range(args.workers))}",
        f"--serve_hosts={','.join(f'127.0.0.1:{p}' for p in serve_ports)}",
        "--ps_restarts=3",
        f"--lease_ttl_s={args.lease_ttl_s}",
        "--log_every_steps=50",
        f"--serve_queue_depth={args.serve_queue_depth}",
        "--serve_max_batch=2",
        "--serve_max_wait_ms=20",
        f"--serve_queue_deadline_ms={args.serve_queue_deadline_ms}",
    ]
    env = dict(os.environ)
    env.pop("DTX_FAULT_ROLE", None)
    env["DTX_FAULT_PLAN"] = ""  # overload IS the fault; no injected chaos
    procs: dict[str, subprocess.Popen] = {}

    def spawn(job: str, index: int) -> None:
        procs[f"{job}{index}"] = launch_task(
            args.example, common, job, index, logdir, env
        )

    verdict: dict = {
        "schema_version": VERDICT_SCHEMA_VERSION,
        "metric": "loadsim_overload_slo",
        "qps_target": args.qps,
        "gen_threads": args.gen_threads,
        "burst_threads": args.burst_threads,
        "duration_s": args.duration_s,
        "goodput_floor_frac": args.goodput_floor_frac,
        "recovery_bound_s": args.recovery_bound_s,
        "recovery_factor": args.recovery_factor,
        "logdir": logdir,
    }
    gen = None
    burst_children: list[subprocess.Popen] = []
    step_series: list[tuple[float, int]] = []
    scrape_fail = 0
    members_before: set = set()
    members_after: set = set()
    last_summary: dict = {}

    def scrape(dst_members: set | None = None) -> None:
        nonlocal scrape_fail, last_summary
        try:
            snap = dtxtop.snapshot(
                ps_addrs, ps_shards=args.ps_shards,
                ps_replicas=args.ps_replicas, timeout_s=3.0,
            )
            steps = snap["summary"]["serve"]["model_steps"]
            step_series.append(
                (time.monotonic(), max(steps) if steps else -1)
            )
            last_summary = snap["summary"]
            if dst_members is not None:
                mem = snap["summary"]["members"]
                dst_members.update(mem["workers"], mem["serve"])
        except Exception:  # noqa: BLE001 — a saturated scrape may miss
            scrape_fail += 1

    try:
        for i in range(n_ps):
            spawn("ps", i)
        if not wait_ps_ready(ps_addrs, args.ready_wait_s):
            raise RuntimeError(f"PS tasks never came up (logs: {logdir})")
        spawn("chief", 0)
        for i in range(args.workers):
            spawn("worker", i)
        for i in range(args.serve_replicas):
            spawn("serve", i)
        if not wait_serve_ready(serve_addrs, args.ready_wait_s):
            raise RuntimeError(
                f"serve replicas never pulled a model (logs: {logdir})"
            )

        gen = LoadGenerator(
            ps_addrs, serve_addrs, qps=args.qps, threads=args.gen_threads,
            deadline_s=max(30.0, args.duration_s),
        )
        gen.start()
        t0 = time.monotonic()
        t_burst_on = t0 + OVERLOAD_PHASES["burst_start"] * args.duration_s
        t_burst_off = t0 + OVERLOAD_PHASES["burst_end"] * args.duration_s

        # Phase 1 — baseline: the healthy p99 the recovery gate compares
        # against, plus the live-member set whose leases must survive.
        while time.monotonic() < t_burst_on:
            scrape(members_before)
            time.sleep(1.0)
        baseline = gen.snap_window()
        verdict["baseline_p99_ms"] = baseline["p99_ms"]
        verdict["baseline_ok"] = baseline["ok"]
        verdict["baseline_failed"] = baseline["failed"]

        # Phase 2 — burst: unpaced closed-loop clients in SEPARATE
        # processes (the orchestrator's own GIL must not cap the offered
        # load — and N distinct client processes is the real overload
        # shape).  Each child's pool runs a SHORT logical deadline: under
        # saturation a burst predict fails fast (through the retry
        # budget) instead of queueing forever — burst failures are
        # EXPECTED and not gated; the goodput floor is.
        burst_s = t_burst_off - time.monotonic()
        per_proc = max(1, args.burst_threads // args.burst_procs)
        burst_children += [
            subprocess.Popen(
                [sys.executable, os.path.abspath(__file__),
                 "--scenario=burst_child",
                 "--burst_serve_hosts="
                 + ",".join(f"127.0.0.1:{p}" for p in serve_ports),
                 f"--gen_threads={per_proc}",
                 f"--burst_rows={args.burst_rows}",
                 f"--duration_s={burst_s:.1f}"],
                stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
                text=True, env=env, cwd=ROOT,
            )
            for _ in range(args.burst_procs)
        ]
        faults.log_event(
            "loadsim_burst_on", procs=args.burst_procs, threads=per_proc,
        )
        while any(c.poll() is None for c in burst_children):
            scrape()
            time.sleep(1.0)
            if time.monotonic() > t_burst_off + 60.0:
                for c in burst_children:
                    c.kill()
                break
        paced_burst = gen.snap_window()
        t_recover0 = time.monotonic()
        faults.log_event("loadsim_burst_off")
        burst_ok = burst_failed = 0
        for c in burst_children:
            try:
                out, _ = c.communicate(timeout=10.0)
                st = json.loads(out.strip().splitlines()[-1])
                burst_ok += st["predict_ok"]
                burst_failed += st["predict_failed"]
            except Exception:  # noqa: BLE001 — a killed child reports 0
                burst_failed += 1
        burst_window = t_recover0 - t_burst_on
        goodput = (paced_burst["ok"] + burst_ok) / max(0.1, burst_window)
        verdict["burst_window_s"] = round(burst_window, 1)
        verdict["burst_procs"] = args.burst_procs
        verdict["burst_goodput_qps"] = round(goodput, 2)
        verdict["burst_paced"] = paced_burst
        verdict["burst_ok"] = burst_ok
        verdict["burst_failed"] = burst_failed

        # Phase 3 — recovery: windowed p99 of the PACED traffic until it
        # returns under the bounded multiple of baseline (or the bound
        # expires).  The clock starts the moment the burst stops.
        target_ms = max(
            args.recovery_factor * baseline["p99_ms"], args.recovery_floor_ms
        )
        verdict["recovery_target_ms"] = round(target_ms, 3)
        recovery_s = None
        windows = []
        while time.monotonic() < t_recover0 + args.recovery_bound_s:
            t_win = time.monotonic()
            while time.monotonic() < t_win + 2.0:
                scrape()
                time.sleep(1.0)
            w = gen.snap_window()
            windows.append(w)
            # Recovered = a window that is fully HEALTHY again: traffic
            # flowing, zero typed failures (the retry budgets refilled),
            # p99 back under the bounded multiple of baseline.
            if w["ok"] > 0 and w["failed"] == 0 and w["p99_ms"] <= target_ms:
                recovery_s = time.monotonic() - t_recover0
                break
        verdict["recovery_windows"] = windows
        verdict["recovery_s"] = (
            round(recovery_s, 1) if recovery_s is not None else -1.0
        )
        # A short settled tail so the step/lease gates see the recovered
        # cluster, and the member set to compare against the baseline's.
        t_tail = time.monotonic() + 3.0
        while time.monotonic() < t_tail:
            scrape(members_after)
            time.sleep(1.0)
        verdict["window_s"] = round(time.monotonic() - t0, 1)
    finally:
        for c in burst_children:
            if c.poll() is None:  # an exception mid-burst: don't orphan
                c.kill()
        load = gen.stop() if gen is not None else {
            "predict_ok": 0, "predict_failed": -1, "errors": ["never ran"],
            "p50_ms": 0.0, "p90_ms": 0.0, "p99_ms": 0.0,
        }
        for name, p in procs.items():
            if p.poll() is None:
                p.send_signal(
                    signal.SIGTERM
                    if name.startswith(("ps", "serve"))
                    else signal.SIGKILL
                )
        deadline = time.monotonic() + 15.0
        for p in procs.values():
            try:
                p.wait(timeout=max(0.1, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                p.kill()
            getattr(p, "_dtx_logf").close()

    verdict.update(load)
    verdict["scrape_failures"] = scrape_fail
    verdict.update(analyze_steps(step_series, {"burst": 0.0}))
    # The overload telemetry the run produced (dtxtop's last scrape):
    # sheds prove admission control engaged; leases_expired must be 0.
    verdict["shed_total"] = (
        last_summary.get("serve", {}).get("shed_total", 0)
        + last_summary.get("ps", {}).get("shed_total", 0)
        + last_summary.get("dsvc", {}).get("shed_total", 0)
    )
    verdict["batcher_overloads"] = last_summary.get("serve", {}).get(
        "overloads", 0
    )
    verdict["leases_expired"] = last_summary.get("ps", {}).get(
        "leases_expired", -1
    )
    verdict["retry"] = last_summary.get("retry", {})
    verdict["members_before"] = sorted(members_before)
    verdict["members_after"] = sorted(members_after)
    goodput_floor = args.goodput_floor_frac * args.qps
    verdict["goodput_floor_qps"] = round(goodput_floor, 2)
    gates = {
        # The HEALTHY phases are spotless: zero typed failures before the
        # burst.  (During the burst, paced predicts MAY surface the typed
        # budget-exhausted/deadline errors — that is the discipline
        # working, and the goodput + recovery gates bound its cost.)
        "zero_failed_baseline": verdict["baseline_failed"] == 0,
        "baseline_served": verdict["baseline_ok"] > 0,
        # Graceful degradation DURING the burst: admitted work completes
        # at or above the floor while the excess sheds.
        "goodput_floor": verdict["burst_goodput_qps"] >= goodput_floor,
        # Control-plane priority: saturation never starved a heartbeat
        # into a false member expiry — and every pre-burst member is
        # still leased after recovery.
        "zero_lease_expirations": verdict["leases_expired"] == 0,
        "members_retained": members_before <= members_after,
        # The no-metastability proof: p99 back under the bounded multiple
        # of baseline within the recovery window of burst end.
        "p99_recovered_in_bound": verdict["recovery_s"] >= 0.0,
        "step_monotone": verdict["step_monotone"],
        "step_advanced": verdict["step_advanced"],
        # The burst genuinely tripped admission control somewhere (core
        # shed or batcher refusal): a burst the cluster absorbed without
        # shedding proves nothing about degradation.
        "overload_tripped": (
            verdict["shed_total"] + verdict["batcher_overloads"] > 0
        ),
    }
    verdict["gates"] = gates
    verdict["slo_pass"] = all(gates.values())
    verdict["loadsim_p99_ms"] = load["p99_ms"]
    if args.out:
        with open(args.out, "w") as f:
            json.dump(verdict, f, indent=2)
    print(json.dumps(verdict))
    return 0 if verdict["slo_pass"] else 1


def run_multitenant(args) -> int:
    """The multi-tenancy acceptance scenario (``--scenario=multitenant``,
    r20): boot ONE shared PS tier + ONE serve pool, run TWO independent
    training stacks over it (``--tenant=runa`` and ``--tenant=runb`` —
    each its own chief + workers publishing namespaced params/leases),
    hold paced tenant-``runb`` SLO load on the serve pool, then slam it
    with a 4x unpaced tenant-``runa`` noise fleet for the middle of the
    window.  The serve replicas run ``--tenant_quotas`` (default:
    ``runa`` weight 1 with tight in-flight/dispatch caps, ``runb``
    weight 3, uncapped).

    SLO verdict (``multitenant_slo``):

    - ``b_zero_failed`` — tenant ``runb``'s paced traffic never fails a
      logical predict, through the whole noise window;
    - ``b_p99_bounded`` — ``runb``'s p99 DURING the noise stays under
      ``--mt_p99_factor`` x its own baseline (abs floor
      ``--mt_p99_floor_ms``): the quota + weighted-fair dispatch keep the
      noisy neighbor from inflating the SLO tenant's tail;
    - ``a_quota_tripped`` / ``b_not_shed`` — the per-tenant quota shed
      ONLY ``runa`` (its ``shed_quota`` > 0; ``runb``'s ``shed_total``
      == 0 on the dtxtop per-tenant rollup);
    - ``namespace_isolated`` — both tenants' rows in the rollup carry
      their own PS objects and leased members (disjoint ``t.<tenant>.*``
      namespaces on the SHARED tier);
    - ``zero_lease_expirations``, monotone strictly-advancing step.
    """
    from distributed_tensorflow_examples_tpu.utils import faults
    from tools import dtxtop

    faults.set_role("loadsim")
    logdir = args.logdir or tempfile.mkdtemp(prefix="dtx-loadsim-mt-")
    os.makedirs(logdir, exist_ok=True)
    n_ps = args.ps_shards * args.ps_replicas
    ports = free_ports(n_ps + args.serve_replicas)
    ps_ports, serve_ports = ports[:n_ps], ports[n_ps:]
    ps_addrs = [("127.0.0.1", p) for p in ps_ports]
    serve_addrs = [("127.0.0.1", p) for p in serve_ports]
    base = [
        "--sync_replicas=false",
        "--batch_size=64",
        "--train_steps=1000000",  # outlives the window; loadsim tears down
        "--hidden_units=64",
        f"--ps_hosts={','.join(f'127.0.0.1:{p}' for p in ps_ports)}",
        f"--ps_shards={args.ps_shards}",
        f"--ps_replicas={args.ps_replicas}",
        f"--worker_hosts={','.join(f'127.0.0.1:{7000 + i}' for i in range(args.workers))}",
        f"--serve_hosts={','.join(f'127.0.0.1:{p}' for p in serve_ports)}",
        "--ps_restarts=3",
        f"--lease_ttl_s={args.lease_ttl_s}",
        "--log_every_steps=50",
    ]
    env = dict(os.environ)
    env.pop("DTX_FAULT_ROLE", None)
    env["DTX_FAULT_PLAN"] = ""  # the noisy neighbor IS the fault
    procs: dict[str, subprocess.Popen] = {}

    def spawn(name: str, job: str, index: int, extra=()) -> None:
        procs[name] = launch_task(
            args.example, base + list(extra), job, index, logdir, env,
            log_name=name,
        )

    verdict: dict = {
        "schema_version": VERDICT_SCHEMA_VERSION,
        "metric": "loadsim_multitenant_slo",
        "qps_target": args.qps,
        "gen_threads": args.gen_threads,
        "duration_s": args.duration_s,
        "tenant_quotas": args.mt_quotas,
        "noise_threads": args.mt_noise_threads,
        "noise_procs": args.mt_noise_procs,
        "mt_p99_factor": args.mt_p99_factor,
        "logdir": logdir,
    }
    gen = None
    noise_children: list[subprocess.Popen] = []
    step_series: list[tuple[float, int]] = []
    scrape_fail = 0
    last_summary: dict = {}

    def scrape() -> None:
        nonlocal scrape_fail, last_summary
        try:
            snap = dtxtop.snapshot(
                ps_addrs, ps_shards=args.ps_shards,
                ps_replicas=args.ps_replicas, timeout_s=3.0,
            )
            steps = snap["summary"]["serve"]["model_steps"]
            step_series.append(
                (time.monotonic(), max(steps) if steps else -1)
            )
            last_summary = snap["summary"]
        except Exception:  # noqa: BLE001 — a saturated scrape may miss
            scrape_fail += 1

    try:
        # ONE shared PS tier (untenanted: shared infrastructure), then a
        # full training stack PER TENANT over it, then the shared serve
        # pool — replicas are tenant runb's (they hot-track runb's
        # namespaced params) and carry the per-tenant admission quotas.
        for i in range(n_ps):
            spawn(f"ps{i}", "ps", i)
        if not wait_ps_ready(ps_addrs, args.ready_wait_s):
            raise RuntimeError(f"PS tasks never came up (logs: {logdir})")
        for t in ("runa", "runb"):
            spawn(f"{t}_chief0", "chief", 0, extra=[f"--tenant={t}"])
            for i in range(args.workers):
                spawn(f"{t}_worker{i}", "worker", i, extra=[f"--tenant={t}"])
        for i in range(args.serve_replicas):
            spawn(
                f"serve{i}", "serve", i,
                extra=["--tenant=runb",
                       f"--tenant_quotas={args.mt_quotas}"],
            )
        if not wait_serve_ready(serve_addrs, args.ready_wait_s):
            raise RuntimeError(
                f"serve replicas never pulled a model (logs: {logdir})"
            )

        # The SLO tenant's paced generator: every predict rides tenant
        # runb's namespace tag, so the serve cores attribute (and
        # weighted-fair schedule) it as runb.
        gen = LoadGenerator(
            ps_addrs, serve_addrs, qps=args.qps, threads=args.gen_threads,
            deadline_s=max(30.0, args.duration_s), tenant="runb",
        )
        gen.start()
        t0 = time.monotonic()
        t_noise_on = t0 + MULTITENANT_PHASES["noise_start"] * args.duration_s
        t_noise_off = t0 + MULTITENANT_PHASES["noise_end"] * args.duration_s
        t_end = t0 + args.duration_s

        # Phase 1 — baseline: runb's healthy p99, the bound the noisy
        # window is judged against.
        while time.monotonic() < t_noise_on:
            scrape()
            time.sleep(1.0)
        baseline = gen.snap_window()
        verdict["baseline"] = baseline

        # Phase 2 — noise: unpaced tenant-runa clients in SEPARATE
        # processes (the real N-clients noisy-neighbor shape; the
        # orchestrator's GIL must not cap the offered load).  Short
        # logical deadlines: a shed runa predict fails fast through its
        # retry budget — runa failures are EXPECTED (that is the quota
        # working) and not gated.
        noise_s = t_noise_off - time.monotonic()
        per_proc = max(1, args.mt_noise_threads // args.mt_noise_procs)
        noise_children += [
            subprocess.Popen(
                [sys.executable, os.path.abspath(__file__),
                 "--scenario=burst_child",
                 "--burst_serve_hosts="
                 + ",".join(f"127.0.0.1:{p}" for p in serve_ports),
                 f"--gen_threads={per_proc}",
                 f"--burst_rows={args.burst_rows}",
                 "--burst_tenant=runa",
                 f"--duration_s={noise_s:.1f}"],
                stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
                text=True, env=env, cwd=ROOT,
            )
            for _ in range(args.mt_noise_procs)
        ]
        faults.log_event(
            "loadsim_mt_noise_on", procs=args.mt_noise_procs,
            threads=per_proc,
        )
        while any(c.poll() is None for c in noise_children):
            scrape()
            time.sleep(1.0)
            if time.monotonic() > t_noise_off + 60.0:
                for c in noise_children:
                    c.kill()
                break
        noisy = gen.snap_window()
        verdict["noisy"] = noisy
        faults.log_event("loadsim_mt_noise_off")
        noise_ok = noise_failed = 0
        for c in noise_children:
            try:
                out, _ = c.communicate(timeout=10.0)
                st = json.loads(out.strip().splitlines()[-1])
                noise_ok += st["predict_ok"]
                noise_failed += st["predict_failed"]
            except Exception:  # noqa: BLE001 — a killed child reports 0
                noise_failed += 1
        verdict["noise_ok"] = noise_ok
        verdict["noise_failed"] = noise_failed

        # Phase 3 — tail: the noise is gone; runb keeps flowing and the
        # final scrapes carry the per-tenant rollup the gates read.
        while time.monotonic() < t_end:
            scrape()
            time.sleep(1.0)
        verdict["tail"] = gen.snap_window()
        verdict["window_s"] = round(time.monotonic() - t0, 1)
    finally:
        for c in noise_children:
            if c.poll() is None:  # an exception mid-noise: don't orphan
                c.kill()
        load = gen.stop() if gen is not None else {
            "predict_ok": 0, "predict_failed": -1, "errors": ["never ran"],
            "p50_ms": 0.0, "p90_ms": 0.0, "p99_ms": 0.0,
        }
        for name, p in procs.items():
            if p.poll() is None:
                p.send_signal(
                    signal.SIGTERM
                    if name.startswith(("ps", "serve"))
                    else signal.SIGKILL
                )
        deadline = time.monotonic() + 15.0
        for p in procs.values():
            try:
                p.wait(timeout=max(0.1, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                p.kill()
            getattr(p, "_dtx_logf").close()

    verdict.update(load)
    verdict["scrape_failures"] = scrape_fail
    verdict.update(analyze_steps(step_series, {"noise": 0.0}))
    tenants = last_summary.get("tenants", {})
    verdict["tenants"] = tenants
    verdict["leases_expired"] = last_summary.get("ps", {}).get(
        "leases_expired", -1
    )
    runa = tenants.get("runa", {})
    runb = tenants.get("runb", {})
    baseline = verdict.get("baseline", {"ok": 0, "failed": -1, "p99_ms": 0.0})
    noisy = verdict.get("noisy", {"ok": 0, "failed": -1, "p99_ms": 1e9})
    p99_target = max(
        args.mt_p99_factor * baseline["p99_ms"], args.mt_p99_floor_ms
    )
    verdict["noisy_p99_target_ms"] = round(p99_target, 3)
    gates = {
        # The SLO tenant is spotless END TO END: its quota weight + the
        # noisy tenant's caps mean the noise never costs runb a predict.
        "b_zero_failed": load["predict_failed"] == 0,
        "b_baseline_served": baseline["ok"] > 0 and baseline["failed"] == 0,
        # Bounded interference: runb's p99 under the noise stays within
        # the factor of its own baseline (abs floor for very fast boxes).
        "b_p99_bounded": noisy["ok"] > 0 and noisy["p99_ms"] <= p99_target,
        # The noise fleet genuinely offered load (a no-show noise phase
        # proves nothing about isolation).
        "noise_offered": noise_ok + noise_failed > 0,
        # The per-tenant quota tripped on the noisy tenant ONLY: runa's
        # rollup row shows quota sheds, runb's shows NO sheds of any
        # kind — admission pressure never crossed the tenant boundary.
        "a_quota_tripped": runa.get("shed_quota", 0) > 0,
        "b_not_shed": runb.get("shed_total", -1) == 0,
        # Namespace isolation on the SHARED tier: each tenant's own
        # params objects and leased members, visible per-tenant.
        "namespace_isolated": (
            runa.get("ps_objects", 0) >= 1 and runb.get("ps_objects", 0) >= 1
            and runa.get("members", 0) >= 1 and runb.get("members", 0) >= 1
        ),
        # Control-plane priority held for BOTH tenants: no live member's
        # lease expired under the noise.
        "zero_lease_expirations": verdict["leases_expired"] == 0,
        "step_monotone": verdict["step_monotone"],
        "step_advanced": verdict["step_advanced"],
    }
    verdict["gates"] = gates
    verdict["slo_pass"] = all(gates.values())
    verdict["loadsim_p99_ms"] = load["p99_ms"]
    if args.out:
        with open(args.out, "w") as f:
            json.dump(verdict, f, indent=2)
    print(json.dumps(verdict))
    return 0 if verdict["slo_pass"] else 1


def run_canary(args) -> int:
    """The rolling-deploy acceptance scenario (``--scenario=canary``, r19):
    boot a real multi-process train-and-serve cluster whose serve replicas
    PIN registry versions (``--registry_dir``/``--serve_model_version``),
    hold closed-loop predict load, and drive a full stable→canary→promoted
    version flip WITH a kill/join cycle landing mid-flip:

    - t0: the training run's params publish to the registry as v1; three
      replicas pin it;
    - mid-run: the CURRENT params publish as v2, one canary replica pins
      it (the join), and ``--canary_weight`` of the paced traffic routes
      at it (``ServePool.set_canary`` over lease-discovered replicas whose
      versions ride the msrv HELLO word / response stamps);
    - a stable replica is killed during the flip (supervised restart
      re-pins v1 — version identity survives the heal);
    - promote: v2 replacements spawn (surge), then every v1 task retires.

    SLO verdict (``canary_slo``): zero failed predicts across the whole
    flip, the canary traffic fraction within ``--canary_tol`` of the
    weight, the served model_version monotone across scrapes and all-v2 at
    the end, training step advancing, the kill really fired, and dtxtop's
    per-version rollup showing BOTH versions mid-flip."""
    import jax  # noqa: F401 — the orchestrator reads PS params itself

    from distributed_tensorflow_examples_tpu import models
    from distributed_tensorflow_examples_tpu.parallel import ps_shard
    from distributed_tensorflow_examples_tpu.serve.registry import (
        ModelRegistry,
    )
    from distributed_tensorflow_examples_tpu.utils import faults
    from tools import dtxtop

    faults.set_role("loadsim")
    logdir = args.logdir or tempfile.mkdtemp(prefix="dtx-loadsim-cn-")
    os.makedirs(logdir, exist_ok=True)
    # Fresh registry per run (versions are immutable — a reused logdir
    # must not collide with a previous run's v1/v2).
    registry_dir = tempfile.mkdtemp(prefix="registry-", dir=logdir)
    n_replicas = max(3, args.serve_replicas)  # the acceptance flips a 3-pool
    n_ps = args.ps_shards * args.ps_replicas
    # Serve ports: [0..R) stable v1, [R] the canary, [R+1..2R] the v2
    # replacements — one --serve_hosts list, task_index selects.
    ports = free_ports(n_ps + 2 * n_replicas + 1)
    ps_ports = ports[:n_ps]
    serve_ports = ports[n_ps:]
    stable_ports = serve_ports[:n_replicas]
    canary_port = serve_ports[n_replicas]
    replacement_ports = serve_ports[n_replicas + 1 : 2 * n_replicas + 1]
    ps_addrs = [("127.0.0.1", p) for p in ps_ports]
    t_kill = args.boot_offset_s + CANARY_PHASES["kill_serve"] * args.duration_s
    plan = "" if args.no_chaos else f"die:role=serve1,after_s={t_kill:.1f}"
    env = dict(os.environ)
    env.pop("DTX_FAULT_ROLE", None)
    env["DTX_FAULT_PLAN"] = plan
    procs: dict[str, subprocess.Popen] = {}

    def common(version: int) -> list[str]:
        return [
            "--sync_replicas=false",
            "--batch_size=64",
            "--train_steps=1000000",  # outlives the window; loadsim tears down
            "--hidden_units=32",
            f"--ps_hosts={','.join(f'127.0.0.1:{p}' for p in ps_ports)}",
            f"--ps_shards={args.ps_shards}",
            f"--ps_replicas={args.ps_replicas}",
            f"--worker_hosts={','.join(f'127.0.0.1:{7000 + i}' for i in range(args.workers))}",
            f"--serve_hosts={','.join(f'127.0.0.1:{p}' for p in serve_ports)}",
            "--ps_restarts=3",
            f"--lease_ttl_s={args.lease_ttl_s}",
            "--log_every_steps=50",
            f"--registry_dir={registry_dir}",
            f"--serve_model_version={version}",
        ]

    def spawn(name: str, job: str, index: int, version: int = 0) -> None:
        procs[name] = launch_task(
            args.example, common(version), job, index, logdir, env,
            log_name=name,
        )

    verdict: dict = {
        "schema_version": VERDICT_SCHEMA_VERSION,
        "metric": "loadsim_canary_slo",
        "qps_target": args.qps,
        "duration_s": args.duration_s,
        "p99_bound_ms": args.p99_bound_ms,
        "canary_weight": args.canary_weight,
        "canary_tol": args.canary_tol,
        "replicas": n_replicas,
        "logdir": logdir,
        "chaos": not args.no_chaos,
    }
    gen = None
    step_series: list[tuple[float, int]] = []
    version_series: list[tuple[float, int]] = []
    both_versions_seen = False
    scrape_fail = 0
    final_versions: list[int] = []

    # The orchestrator's own PS-side: pull the live run's params to
    # publish registry versions from (the same flat vector the chief
    # publishes — ps_shard is the one layout definition).
    cfg = models.mlp.Config(hidden=(32,))
    total, _ = ps_shard.flat_param_spec(
        models.mlp.init(cfg, __import__("jax").random.key(0))
    )
    registry = ModelRegistry(registry_dir)
    group = None

    def publish_current(version: int) -> int:
        step, flat = pstore.get()
        if step < 0:
            raise RuntimeError("chief has not published params yet")
        return registry.publish(
            "default", flat, step=int(step), version=version,
            source="loadsim canary",
        )

    try:
        for i in range(n_ps):
            spawn(f"ps{i}", "ps", i)
        if not wait_ps_ready(ps_addrs, args.ready_wait_s):
            raise RuntimeError(f"PS tasks never came up (logs: {logdir})")
        spawn("chief0", "chief", 0)
        for i in range(args.workers):
            spawn(f"worker{i}", "worker", i)
        group = ps_shard.ShardedPSClients(
            ps_addrs[: args.ps_shards], role="loadsim_pub",
            op_timeout_s=10.0, replicas=1,
        )
        pstore = ps_shard.ShardedParamStore(
            group, "params", group.layout_for(total)
        )
        t_pub = time.monotonic() + args.ready_wait_s
        while True:
            try:
                if pstore.get()[0] >= 0:
                    break
            except Exception:  # noqa: BLE001 — chief still booting
                pass
            if time.monotonic() > t_pub:
                raise RuntimeError("chief never published params to the PS")
            time.sleep(0.5)
        publish_current(1)
        for i in range(n_replicas):
            spawn(f"serve{i}", "serve", i, version=1)
        stable_addrs = [("127.0.0.1", p) for p in stable_ports]
        if not wait_serve_ready(stable_addrs, args.ready_wait_s):
            raise RuntimeError(
                f"serve replicas never pinned v1 (logs: {logdir})"
            )

        gen = LoadGenerator(
            ps_addrs, stable_addrs, qps=args.qps, threads=args.gen_threads,
            deadline_s=max(30.0, args.duration_s),
        )
        gen.start()
        t0 = time.monotonic()
        t_end = t0 + args.duration_s
        markers = {
            name: t0 + frac * args.duration_s
            for name, frac in CANARY_PHASES.items()
        }
        published_v2 = canary_spawned = promoted = retired = False
        canary_window_base: dict | None = None
        canary_routed_t: float | None = None
        # The window extends (bounded) until the flip COMPLETES: on a
        # slow box the boot/evidence waits may push the retire past the
        # nominal duration, and a verdict for half a flip proves nothing.
        while time.monotonic() < t_end or (
            not retired and time.monotonic() < t_end + 90.0
        ):
            now = time.monotonic()
            if not published_v2 and now >= markers["publish_v2"]:
                published_v2 = True
                publish_current(2)  # the flip artifact: CURRENT params
                faults.log_event("loadsim_canary_published", version=2)
            if not canary_spawned and now >= markers["canary_up"]:
                canary_spawned = True
                spawn("serve_canary", "serve", n_replicas, version=2)
                if wait_serve_ready(
                    [("127.0.0.1", canary_port)], args.ready_wait_s
                ):
                    # The weighted split is measured from the moment the
                    # POOL actually routes the canary (lease discovery +
                    # the HELLO version word), not from the spawn — the
                    # replica's boot must not eat the evidence window.
                    t_disc = time.monotonic() + 20.0
                    while time.monotonic() < t_disc and 2 not in (
                        gen.pool.known_versions().values()
                    ):
                        time.sleep(0.3)
                    gen.pool.set_canary(2, args.canary_weight)
                    canary_window_base = gen.pool.version_stats()
                    canary_routed_t = time.monotonic()
                    faults.log_event("loadsim_canary_routed")
            if not promoted and now >= markers["promote_start"] and (
                canary_routed_t is None
                or now >= canary_routed_t + args.canary_window_s
            ):
                promoted = True
                # Canary verdict window closes here: measure the honored
                # traffic split before the promote changes the lanes.
                if canary_window_base is not None:
                    vs = gen.pool.version_stats()
                    d_can = (
                        vs.get(2, {}).get("ok", 0)
                        - canary_window_base.get(2, {}).get("ok", 0)
                    )
                    d_tot = sum(
                        row.get("ok", 0) for row in vs.values()
                    ) - sum(
                        row.get("ok", 0)
                        for row in canary_window_base.values()
                    )
                    verdict["canary_ok"] = d_can
                    verdict["canary_window_ok"] = d_tot
                    verdict["canary_frac"] = (
                        round(d_can / d_tot, 4) if d_tot else -1.0
                    )
                gen.pool.clear_canary()
                for i in range(n_replicas):
                    spawn(
                        f"serve_v2_{i}", "serve", n_replicas + 1 + i,
                        version=2,
                    )
                faults.log_event("loadsim_promote_spawned", replicas=n_replicas)
            if promoted and not retired and now >= markers["retire_old"]:
                # SURGE ordering: the v1 tier retires only once every v2
                # replacement is model-loaded and routable — capacity
                # never dips below the pool size mid-flip.
                if wait_serve_ready(
                    [("127.0.0.1", p) for p in replacement_ports],
                    args.ready_wait_s,
                ):
                    retired = True
                    for i in range(n_replicas):
                        p = procs.get(f"serve{i}")
                        if p is not None and p.poll() is None:
                            p.send_signal(signal.SIGTERM)
                    faults.log_event(
                        "loadsim_old_retired", replicas=n_replicas
                    )
            try:
                snap = dtxtop.snapshot(
                    ps_addrs, ps_shards=args.ps_shards,
                    ps_replicas=args.ps_replicas, timeout_s=3.0,
                )
                su = snap["summary"]["serve"]
                steps = su["model_steps"]
                step_series.append(
                    (time.monotonic(), max(steps) if steps else -1)
                )
                versions = [v for v in su.get("model_versions", []) if v > 0]
                version_series.append(
                    (time.monotonic(), max(versions) if versions else -1)
                )
                bv = su.get("by_version", {})
                if {"1", "2"} <= set(bv):
                    both_versions_seen = True
                final_versions = versions
                verdict["members_last"] = snap["summary"]["members"]
            except Exception:  # noqa: BLE001 — mid-flip scrapes may miss
                scrape_fail += 1
            time.sleep(1.0)
        verdict["window_s"] = round(time.monotonic() - t0, 1)
    finally:
        load = gen.stop() if gen is not None else {
            "predict_ok": 0, "predict_failed": -1, "errors": ["never ran"],
            "p50_ms": 0.0, "p90_ms": 0.0, "p99_ms": 0.0,
        }
        if group is not None:
            group.close()
        for name, p in procs.items():
            if p.poll() is None:
                p.send_signal(
                    signal.SIGTERM
                    if name.startswith(("ps", "serve"))
                    else signal.SIGKILL
                )
        deadline = time.monotonic() + 15.0
        for p in procs.values():
            try:
                p.wait(timeout=max(0.1, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                p.kill()
            getattr(p, "_dtx_logf").close()

    window = verdict.get("window_s") or args.duration_s
    verdict.update(load)
    verdict["qps_achieved"] = round(load["predict_ok"] / window, 2)
    verdict["scrape_failures"] = scrape_fail
    verdict.update(analyze_steps(step_series, {"flip": 0.0}))
    versions = [v for _, v in version_series if v >= 0]
    verdict["version_first"] = versions[0] if versions else -1
    verdict["version_last"] = versions[-1] if versions else -1
    verdict["version_monotone"] = all(
        b >= a for a, b in zip(versions, versions[1:])
    )
    verdict["final_versions"] = final_versions
    verdict["both_versions_observed"] = both_versions_seen
    verdict["kill_fired"] = _fired_in(
        procs.get("serve1"), "event=inject_die"
    )
    frac = verdict.get("canary_frac", -1.0)
    gates = {
        "zero_failed_predicts": load["predict_failed"] == 0,
        "p99_under_bound": 0.0 < load["p99_ms"] <= args.p99_bound_ms,
        "qps_at_target": verdict["qps_achieved"] >= 0.6 * args.qps,
        # The flip itself: canary traffic split honored, versions only
        # ever move forward, and the pool ends fully promoted.
        "canary_weight_honored": (
            frac >= 0.0 and abs(frac - args.canary_weight) <= args.canary_tol
        ),
        "version_monotone": verdict["version_monotone"],
        "flip_completed": bool(final_versions) and all(
            v == 2 for v in final_versions
        ),
        "both_versions_observed": both_versions_seen,
        "step_monotone": verdict["step_monotone"],
        "step_advanced": verdict["step_advanced"],
    }
    if not args.no_chaos:
        gates["kill_fired"] = verdict["kill_fired"]
    verdict["gates"] = gates
    verdict["slo_pass"] = all(gates.values())
    verdict["loadsim_p99_ms"] = load["p99_ms"]
    if args.out:
        with open(args.out, "w") as f:
            json.dump(verdict, f, indent=2)
    print(json.dumps(verdict))
    return 0 if verdict["slo_pass"] else 1


def run_burst_child(args) -> int:
    """Internal (``--scenario=burst_child``): one burst-client process of
    the overload scenario — ``--gen_threads`` unpaced closed-loop clients
    against ``--burst_serve_hosts`` for ``--duration_s``, final stats as
    the last stdout line."""
    from distributed_tensorflow_examples_tpu.utils import faults

    faults.set_role("loadsim_burst")
    serve_addrs = [
        (h, int(p))
        for h, _, p in (
            a.rpartition(":") for a in args.burst_serve_hosts.split(",") if a
        )
    ]
    gen = LoadGenerator(
        [], serve_addrs, qps=None, threads=args.gen_threads,
        deadline_s=3.0, role="loadsim_burst_sv", op_timeout_s=3.0,
        rows=args.burst_rows, pool_per_thread=True,
        tenant=args.burst_tenant,
    )
    gen.start()
    time.sleep(args.duration_s)
    print(json.dumps(gen.stop()))
    return 0


def _fired_in(p, needle: str) -> bool:
    path = getattr(p, "_dtx_log", "") if p is not None else ""
    try:
        with open(path, "rb") as f:
            return needle.encode() in f.read()
    except OSError:
        return False


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--qps", type=float, default=100.0)
    ap.add_argument("--duration_s", type=float, default=30.0)
    ap.add_argument(
        "--gen_threads", type=int, default=16,
        help="closed-loop generator clients (r17: 4x the original 4 — "
        "the default scenario now drives the serve pool with 16 "
        "concurrent connections; SLO gates unchanged)",
    )
    ap.add_argument("--p99_bound_ms", type=float, default=250.0)
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--serve_replicas", type=int, default=2)
    ap.add_argument("--ps_shards", type=int, default=1)
    ap.add_argument("--ps_replicas", type=int, default=1)
    ap.add_argument("--lease_ttl_s", type=float, default=3.0)
    ap.add_argument("--ready_wait_s", type=float, default=90.0)
    ap.add_argument(
        "--boot_offset_s", type=float, default=15.0,
        help="expected boot window baked into the chaos after_s offsets",
    )
    ap.add_argument(
        "--scenario",
        choices=(
            "chaos", "reshard", "overload", "canary", "multitenant",
            "burst_child",
        ),
        default="chaos",
        help="chaos = the r14 kill/join/leave cycle; reshard = the r15 "
        "live N->N+1->N PS resizing under load (one worker kill); "
        "overload = the r18 graceful-degradation burst (admission "
        "control, deadline propagation, retry budgets); canary = the r19 "
        "rolling registry-version flip (stable->canary->promoted with a "
        "kill/join cycle mid-flip, zero failed predicts, canary weight "
        "honored); multitenant = the r20 noisy-neighbor isolation run "
        "(two tenants' training stacks on one shared PS/serve plane, "
        "per-tenant quotas shed ONLY the noisy tenant); burst_child is "
        "internal (one spawned burst-client process of the "
        "overload/multitenant runs)",
    )
    ap.add_argument(
        "--canary_weight", type=float, default=0.4,
        help="canary scenario: fraction of paced traffic routed at the "
        "canary replica while both lanes are live (deliberately NOT the "
        "plain round-robin share, so an ignored weight fails the gate)",
    )
    ap.add_argument(
        "--canary_tol", type=float, default=0.12,
        help="canary scenario: allowed |achieved - weight| on the canary "
        "traffic fraction",
    )
    ap.add_argument(
        "--canary_window_s", type=float, default=10.0,
        help="canary scenario: minimum seconds of weighted-routing "
        "evidence before the promote may start (the flip must not "
        "outrun its own canary measurement on a slow box)",
    )
    ap.add_argument(
        "--reshard_bound_s", type=float, default=30.0,
        help="reshard scenario: max wall-time per epoch transition "
        "(joiner spawn -> commit observed)",
    )
    ap.add_argument(
        "--burst_threads", type=int, default=64,
        help="overload scenario: unpaced burst clients slammed at the "
        "serve pool mid-run (4x the paced 16 by default — each re-issues "
        "the instant its previous predict resolves, so offered load is "
        "whatever the cluster will bear plus a queue)",
    )
    ap.add_argument(
        "--burst_procs", type=int, default=4,
        help="overload scenario: burst-client PROCESSES the threads are "
        "spread over (one GIL must not cap the offered load)",
    )
    ap.add_argument(
        "--burst_serve_hosts", default="",
        help="internal (burst_child): static serve host list to hammer",
    )
    ap.add_argument(
        "--burst_tenant", default="default",
        help="internal (burst_child): tenant id the burst clients tag "
        "their predicts with (the multitenant scenario's noisy tenant)",
    )
    ap.add_argument(
        "--mt_quotas", default="runa=1:8:4,runb=3",
        help="multitenant scenario: the serve replicas' --tenant_quotas — "
        "by default the noisy tenant runa gets weight 1 with 8 in-flight "
        "/ 4 queued caps per replica, the SLO tenant runb weight 3 "
        "uncapped",
    )
    ap.add_argument(
        "--mt_noise_threads", type=int, default=64,
        help="multitenant scenario: unpaced tenant-runa noise clients "
        "(4x the paced 16 by default) slammed at the shared serve pool "
        "mid-run",
    )
    ap.add_argument(
        "--mt_noise_procs", type=int, default=4,
        help="multitenant scenario: noise-client PROCESSES the threads "
        "are spread over (one GIL must not cap the offered load)",
    )
    ap.add_argument(
        "--mt_p99_factor", type=float, default=3.0,
        help="multitenant scenario: runb's noisy-window p99 must stay "
        "under this multiple of its own baseline p99",
    )
    ap.add_argument(
        "--mt_p99_floor_ms", type=float, default=150.0,
        help="multitenant scenario: absolute floor on the noisy-window "
        "p99 target (a very fast baseline must not make isolation "
        "unprovable)",
    )
    ap.add_argument(
        "--burst_rows", type=int, default=64,
        help="overload scenario: rows per burst predict — heavy requests "
        "make each admitted burst batch cost real apply time, so the "
        "replica queue genuinely BUILDS instead of draining at wire "
        "speed (the paced SLO traffic stays at 4 rows)",
    )
    ap.add_argument(
        "--goodput_floor_frac", type=float, default=0.5,
        help="overload scenario: ok-predicts/sec during the burst must "
        "stay above this fraction of the paced qps target",
    )
    ap.add_argument(
        "--recovery_bound_s", type=float, default=20.0,
        help="overload scenario: p99 must return under the recovery "
        "target within this many seconds of burst end",
    )
    ap.add_argument(
        "--recovery_factor", type=float, default=1.5,
        help="overload scenario: the recovery target as a multiple of "
        "the baseline-phase p99",
    )
    ap.add_argument(
        "--recovery_floor_ms", type=float, default=50.0,
        help="overload scenario: absolute floor on the recovery target "
        "(a very fast baseline must not make recovery unprovable)",
    )
    ap.add_argument(
        "--serve_queue_depth", type=int, default=8,
        help="overload scenario: the replicas' bounded in-system predict "
        "queue (small enough that --burst_threads genuinely exceeds "
        "capacity on a dev box)",
    )
    ap.add_argument(
        "--serve_queue_deadline_ms", type=float, default=500.0,
        help="overload scenario: the replicas' queue-deadline policy "
        "(requests that waited past it are shed before a worker runs)",
    )
    ap.add_argument(
        "--hidden_units", type=int, default=4096,
        help="overload scenario: MLP width — wide enough that one apply "
        "costs real milliseconds, bounding replica throughput below the "
        "burst's offered load",
    )
    ap.add_argument("--no_chaos", action="store_true")
    ap.add_argument("--out", default="", help="write the verdict JSON here")
    ap.add_argument(
        "--logdir", default="", help="task log directory (default: tmp)"
    )
    ap.add_argument(
        "--example", default=os.path.join(ROOT, "examples", "mnist_mlp.py"),
    )
    args = ap.parse_args(argv)

    if args.scenario == "reshard":
        if args.ps_shards < 2:
            args.ps_shards = 2  # the acceptance resizes 2->3->2
        return run_reshard(args)
    if args.scenario == "overload":
        return run_overload(args)
    if args.scenario == "canary":
        return run_canary(args)
    if args.scenario == "multitenant":
        return run_multitenant(args)
    if args.scenario == "burst_child":
        return run_burst_child(args)

    from distributed_tensorflow_examples_tpu.parallel import membership
    from distributed_tensorflow_examples_tpu.utils import faults
    from tools import dtxtop

    faults.set_role("loadsim")
    logdir = args.logdir or tempfile.mkdtemp(prefix="dtx-loadsim-")
    os.makedirs(logdir, exist_ok=True)
    n_ps = args.ps_shards * args.ps_replicas
    join_wid = args.workers  # the joiner takes the next task index
    ports = free_ports(n_ps + args.serve_replicas)
    ps_ports, serve_ports = ports[:n_ps], ports[n_ps:]
    ps_addrs = [("127.0.0.1", p) for p in ps_ports]
    serve_addrs = [("127.0.0.1", p) for p in serve_ports]
    plan = (
        ""
        if args.no_chaos
        else build_plan(args.boot_offset_s, args.duration_s, join_wid)
    )
    common = [
        "--sync_replicas=false",
        "--batch_size=64",
        "--train_steps=1000000",  # outlives the window; loadsim tears down
        "--hidden_units=32",
        f"--ps_hosts={','.join(f'127.0.0.1:{p}' for p in ps_ports)}",
        f"--ps_shards={args.ps_shards}",
        f"--ps_replicas={args.ps_replicas}",
        # The joiner's slot rides at the end of the static list (data
        # sharding math needs a worker count; membership comes from leases).
        f"--worker_hosts={','.join(f'127.0.0.1:{7000 + i}' for i in range(args.workers + 1))}",
        f"--serve_hosts={','.join(f'127.0.0.1:{p}' for p in serve_ports)}",
        "--ps_restarts=3",
        f"--lease_ttl_s={args.lease_ttl_s}",
        "--log_every_steps=50",
    ]
    env = dict(os.environ)
    # Children derive their fault role from --job_name/--task_index; the
    # orchestrator's own exported role must NOT leak into them (it would
    # defeat every role glob in the plan).
    env.pop("DTX_FAULT_ROLE", None)
    env["DTX_FAULT_PLAN"] = plan
    procs: dict[str, subprocess.Popen] = {}
    spawn_t: dict[str, float] = {}

    def spawn(job: str, index: int) -> None:
        name = f"{job}{index}"
        spawn_t[name] = time.monotonic()
        procs[name] = launch_task(
            args.example, common, job, index, logdir, env
        )

    verdict: dict = {
        "schema_version": VERDICT_SCHEMA_VERSION,
        "metric": "loadsim_slo",
        "qps_target": args.qps,
        "gen_threads": args.gen_threads,
        "duration_s": args.duration_s,
        "p99_bound_ms": args.p99_bound_ms,
        "logdir": logdir,
        "chaos": not args.no_chaos,
    }
    gen = None
    step_series: list[tuple[float, int]] = []
    scrape_fail = 0
    markers: dict[str, float] = {}
    try:
        for i in range(n_ps):
            spawn("ps", i)
        if not wait_ps_ready(ps_addrs, args.ready_wait_s):
            raise RuntimeError(f"PS tasks never came up (logs: {logdir})")
        spawn("chief", 0)
        for i in range(args.workers):
            spawn("worker", i)
        for i in range(args.serve_replicas):
            spawn("serve", i)
        if not wait_serve_ready(serve_addrs, args.ready_wait_s):
            raise RuntimeError(
                f"serve replicas never pulled a model (logs: {logdir})"
            )

        gen = LoadGenerator(
            ps_addrs, serve_addrs, qps=args.qps,
            threads=args.gen_threads, deadline_s=max(30.0, args.duration_s),
        )
        gen.start()
        t0 = time.monotonic()
        t_end = t0 + args.duration_s
        if not args.no_chaos:
            # The chaos after_s timers are anchored to each PROCESS's own
            # start (arm time), not to load start — on a fast boot the
            # last event (the leave) can land past t0 + duration.  Extend
            # the observed window to cover every scheduled event plus a
            # grace, so the cycle always completes INSIDE the measured
            # run (the fired-event gates below then prove it did).
            last_event = max(
                spawn_t.get("worker0", t0)
                + args.boot_offset_s
                + PHASES["leave_worker"] * args.duration_s,
                spawn_t.get("worker1", t0)
                + args.boot_offset_s
                + PHASES["kill_worker"] * args.duration_s,
            )
            t_end = max(t_end, last_event + 4.0)
        join_at = {
            s.role: t0 + PHASES["join_worker"] * args.duration_s
            for s in faults.join_specs(plan)
        }
        for name, frac in PHASES.items():
            markers[name] = t0 + frac * args.duration_s
        midrun_done = False
        joined = False
        while time.monotonic() < t_end:
            # Orchestrated joins: spawn the new member processes mid-run.
            for role, when in list(join_at.items()):
                if time.monotonic() >= when:
                    wid = membership.member_index(role)
                    spawn("worker", wid)
                    joined = True
                    faults.log_event("loadsim_join_spawned", member=role)
                    del join_at[role]
            # Scrape over the same wires any operator tooling uses; serve
            # replicas come from the LEASE registry (elastic discovery).
            try:
                snap = dtxtop.snapshot(
                    ps_addrs, ps_shards=args.ps_shards,
                    ps_replicas=args.ps_replicas, timeout_s=3.0,
                )
                steps = snap["summary"]["serve"]["model_steps"]
                step_series.append(
                    (time.monotonic(), max(steps) if steps else -1)
                )
                verdict["members_last"] = snap["summary"]["members"]
            except Exception:  # noqa: BLE001 — mid-failover scrapes may miss
                scrape_fail += 1
            # THE acceptance probe: once the joiner is up, the real dtxtop
            # CLI must exit 0 and show its lease.
            if joined and not midrun_done and not args.no_chaos and (
                time.monotonic()
                >= markers["join_worker"] + max(3.0, 2 * args.lease_ttl_s)
            ):
                midrun_done = True
                cli = subprocess.run(
                    [sys.executable, "-m", "tools.dtxtop", "--json",
                     "--ps_hosts="
                     + ",".join(f"127.0.0.1:{p}" for p in ps_ports),
                     f"--ps_shards={args.ps_shards}",
                     f"--ps_replicas={args.ps_replicas}"],
                    capture_output=True, text=True, cwd=ROOT, env=env,
                    timeout=120,
                )
                verdict["dtxtop_exit"] = cli.returncode
                try:
                    cli_snap = json.loads(cli.stdout.strip().splitlines()[-1])
                    verdict["join_lease_seen"] = (
                        f"worker{join_wid}"
                        in cli_snap["summary"]["members"]["workers"]
                    )
                except Exception:  # noqa: BLE001
                    verdict["join_lease_seen"] = False
            time.sleep(1.0)
        verdict["window_s"] = round(time.monotonic() - t0, 1)
    finally:
        load = gen.stop() if gen is not None else {
            "predict_ok": 0, "predict_failed": -1, "errors": ["never ran"],
            "p50_ms": 0.0, "p90_ms": 0.0, "p99_ms": 0.0,
        }
        # Teardown: chief/workers first (SIGKILL — the run is over), then
        # the supervised services (SIGTERM forwards and ends supervision).
        for name, p in procs.items():
            if p.poll() is None:
                p.send_signal(
                    signal.SIGTERM
                    if name.startswith(("ps", "serve"))
                    else signal.SIGKILL
                )
        deadline = time.monotonic() + 15.0
        for p in procs.values():
            try:
                p.wait(timeout=max(0.1, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                p.kill()
            getattr(p, "_dtx_logf").close()

    window = verdict.get("window_s") or args.duration_s
    verdict.update(load)
    verdict["qps_achieved"] = round(load["predict_ok"] / window, 2)
    verdict["scrape_failures"] = scrape_fail
    verdict.update(analyze_steps(step_series, markers))
    if not args.no_chaos:
        # The chaos events must have FIRED inside the run (their timers
        # are per-process; a timing drift that quietly skipped one would
        # otherwise report a passing verdict for a cycle that never
        # happened).  The task logs are the evidence.
        def _fired(name: str, needle: str) -> bool:
            p = procs.get(name)
            path = getattr(p, "_dtx_log", "") if p is not None else ""
            try:
                with open(path, "rb") as f:
                    return needle.encode() in f.read()
            except OSError:
                return False

        verdict["kills_fired"] = {
            n: _fired(n, "event=inject_die")
            for n in ("ps0", "serve0", "worker1")
        }
        verdict["leave_fired"] = _fired("worker0", "event=inject_leave")
    gates = {
        "zero_failed_predicts": load["predict_failed"] == 0,
        "p99_under_bound": 0.0 < load["p99_ms"] <= args.p99_bound_ms,
        "qps_at_target": verdict["qps_achieved"] >= 0.6 * args.qps,
        "step_monotone": verdict["step_monotone"],
        "step_advanced": verdict["step_advanced"],
    }
    if not args.no_chaos:
        gates["step_advanced_post_chaos"] = verdict["step_advanced_post_chaos"]
        gates["dtxtop_midrun_exit0"] = verdict.get("dtxtop_exit") == 0
        gates["join_lease_seen"] = bool(verdict.get("join_lease_seen"))
        gates["kills_fired"] = all(verdict["kills_fired"].values())
        gates["leave_fired"] = verdict["leave_fired"]
    verdict["gates"] = gates
    verdict["slo_pass"] = all(gates.values())
    # The perf-gate metric field: the checked-in baselines key off it.
    verdict["loadsim_p99_ms"] = load["p99_ms"]
    if args.out:
        with open(args.out, "w") as f:
            json.dump(verdict, f, indent=2)
    print(json.dumps(verdict))
    return 0 if verdict["slo_pass"] else 1


if __name__ == "__main__":
    sys.exit(main())
