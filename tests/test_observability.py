"""Observability plane (r13 dtxobs): registry semantics under threads,
wire-level STATS round trips against all three services, flight-recorder
dumps on forced divergence, and the `dtxtop --json` snapshot schema.

The acceptance e2e (`test_dtxtop_scrapes_full_replicated_cluster`) boots
the full topology the tentpole names — 2-shard x 2-replica PS + data
service + 2-replica serve — drives load over every wire, and asserts ONE
dtxtop scrape returns every role's counters, the native server's
replication counters included.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

import numpy as np
import pytest

from distributed_tensorflow_examples_tpu.parallel import ps_service, ps_shard
from distributed_tensorflow_examples_tpu.utils import faults, telemetry
from distributed_tensorflow_examples_tpu.utils.metrics import LatencyRecorder
from tools import dtxtop
from tools.obs_snapshot_step import REQUIRED_KEYS, missing_counters


@pytest.fixture(autouse=True)
def _clean_fault_env(monkeypatch):
    monkeypatch.delenv("DTX_FAULT_PLAN", raising=False)
    monkeypatch.delenv("DTX_FAULT_ROLE", raising=False)
    monkeypatch.setattr(faults, "_role", None)


# ---------------------------------------------------------------------------
# Registry semantics
# ---------------------------------------------------------------------------


def test_registry_counters_exact_under_threads():
    """Counter increments from many threads are exact (int += is NOT
    atomic across bytecodes — the per-counter lock is what makes the
    exported numbers trustworthy), and histogram observes from the same
    contention never tear the snapshot."""
    reg = telemetry.MetricsRegistry()
    c = reg.counter("t/ops")
    h = reg.histogram("t/ms", capacity=128)
    n_threads, per = 8, 5000

    def body():
        for i in range(per):
            c.inc()
            h.observe(float(i % 100))

    threads = [threading.Thread(target=body) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == n_threads * per
    snap = reg.snapshot()
    assert snap["t/ops"] == n_threads * per
    assert snap["t/ms_count"] == n_threads * per
    assert 0.0 <= snap["t/ms_p50"] <= 99.0
    assert snap["t/ms_max"] <= 99.0


def test_registry_reset_keeps_cached_handles():
    """Hot paths cache instrument handles at module scope, so reset()
    must ZERO values, not drop instruments — a cached handle keeps
    counting into the table the next snapshot reads."""
    reg = telemetry.MetricsRegistry()
    c = reg.counter("t/cached")
    c.inc(5)
    reg.set_gauge("t/g", 7.0)
    reg.reset()
    assert reg.snapshot()["t/cached"] == 0
    c.inc()  # the pre-reset handle
    assert reg.snapshot()["t/cached"] == 1
    assert reg.counter("t/cached") is c
    assert reg.snapshot()["t/g"] == 0.0


def test_span_sums_exact_under_threads(monkeypatch):
    """``telemetry.span``: one handle entered from many threads adds
    exactly its elapsed nanoseconds to ``<name>/ns`` and 1 to ``<name>/n``
    per entry.  The clock is faked per thread (another base each, a fixed
    tick a read), so a start stamp kept on the shared handle instead of
    per thread, or a lost update, would show in the sum."""
    import types

    tick, n_threads, per = 7, 8, 2000
    local = threading.local()
    bases = iter(range(10**12, 10**12 * (n_threads + 2), 10**12))
    bases_lock = threading.Lock()

    def fake_ns() -> int:
        if not hasattr(local, "now"):
            with bases_lock:
                local.now = next(bases)
        local.now += tick
        return local.now

    monkeypatch.setattr(
        telemetry, "time",
        types.SimpleNamespace(perf_counter_ns=fake_ns, time=time.time),
    )
    sp = telemetry.span("t_span/exact")
    assert sp is not telemetry.span("t_span/exact")  # a handle per site...
    before = telemetry.snapshot()  # ...on the same two counters
    assert before["t_span/exact/n"] == 0 and before["t_span/exact/ns"] == 0

    def body():
        for _ in range(per):
            with sp:
                pass

    threads = [threading.Thread(target=body) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not any(t.is_alive() for t in threads)
    snap = telemetry.snapshot()
    assert snap["t_span/exact/n"] == n_threads * per
    assert snap["t_span/exact/ns"] == n_threads * per * tick
    # An exception passes through and the interval still counts.
    with pytest.raises(KeyError):
        with sp:
            raise KeyError("x")
    assert telemetry.snapshot()["t_span/exact/n"] == n_threads * per + 1


def test_telemetry_imports_and_resolves_spans_without_jax():
    """``tools/tsan_driver.py`` loads ``utils.telemetry`` in a process
    where JAX must never load: importing the module and resolving a span
    handle may not import it (only ENTERING a span reaches for the
    profiler's annotation)."""
    import subprocess
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = """
import importlib, os, sys, types
sys.modules["jax"] = None  # any 'import jax' now raises ImportError
pkg = "distributed_tensorflow_examples_tpu"
for name, path in ((pkg, pkg), (pkg + ".utils", os.path.join(pkg, "utils"))):
    mod = types.ModuleType(name)
    mod.__path__ = [os.path.join(sys.argv[1], path)]
    sys.modules[name] = mod
t = importlib.import_module(pkg + ".utils.telemetry")
t.span("x/y")
assert t.snapshot() == {"x/y/ns": 0, "x/y/n": 0}, t.snapshot()
assert not [m for m in sys.modules if m.startswith("jax.")]
print("ok")
"""
    r = subprocess.run(
        [sys.executable, "-c", code, root], capture_output=True, text=True,
        timeout=60,
    )
    assert r.returncode == 0 and r.stdout.strip() == "ok", r.stderr


def test_compile_counter_counts_new_shapes_only():
    """``telemetry.count_compiles``: a new shape is one compile, a repeat
    is none, registering twice does not count twice, and the flight
    recorder says when."""
    import jax

    telemetry.count_compiles()
    telemetry.count_compiles()

    @jax.jit
    def f(x):
        return x * 2 + 1

    def read():
        snap = telemetry.snapshot()
        return snap["jax/compiles"], snap["jax/compile_ns"]

    n0, ns0 = read()
    events0 = sum(1 for e in telemetry.RECORDER.events() if e["event"] == "compile")
    f(np.ones((3,), np.float32)).block_until_ready()
    n1, ns1 = read()
    assert n1 - n0 == 1 and ns1 > ns0
    f(np.ones((3,), np.float32)).block_until_ready()
    assert read() == (n1, ns1)
    f(np.ones((5,), np.float32)).block_until_ready()
    assert read()[0] - n1 == 1
    events = [e for e in telemetry.RECORDER.events() if e["event"] == "compile"]
    assert len(events) - events0 == 2 and events[-1]["seconds"] > 0


def test_histogram_bounded_window_percentiles():
    h = telemetry.Histogram("w", capacity=10)
    assert h.snapshot() == {
        "count": 0, "p50": 0.0, "p90": 0.0, "p99": 0.0, "max": 0.0,
    }
    for v in range(100):
        h.observe(float(v))
    s = h.snapshot()
    # count is lifetime; the window retains only the last `capacity`.
    assert s["count"] == 100
    assert s["max"] == 99.0 and s["p50"] >= 90.0


def _window_percentile(start: dict, end: dict, name: str, q: float):
    """A window's percentile from two registry snapshots, as the benchmark
    reads it (its reader is the one reduction of ``<name>/le/<edge>``)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks.harness import manifest

    return manifest.reader("counter_bucket_percentile")(
        {"counters": {"start": start, "end": end}}, hist=name, q=q,
        edges_per_octave=16)


@pytest.mark.parametrize("shape", ["lognormal", "two_steps", "one_value", "heavy_tail"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_histogram_window_is_the_difference_of_two_snapshots(shape, seed):
    """What a histogram observed between two snapshots, from the
    ``<name>/le/<edge>`` counts alone: the window's own observations and no
    earlier one - a stall from before the window is in no percentile of it,
    though the ring's ``max`` still shows it - with p50 / p95 / p100 within
    5 % of the exact percentiles of the same observations, for values that
    crowd into one bucket as for values spread over many."""
    rng = np.random.default_rng(seed)
    n = 4000  # more than the ring of 512 keeps
    window = {
        "lognormal": lambda: rng.lognormal(np.log(9.0), 0.6, n),
        # a step of 9.8 ms, a step + a chunk on a quarter of them
        "two_steps": lambda: np.where(rng.random(n) < 0.25, 30.8, 9.8)
        * rng.normal(1.0, 0.004, n),
        "one_value": lambda: np.full(n, 18.4),
        "heavy_tail": lambda: 5.0 * (1.0 + rng.pareto(1.5, n)),
    }[shape]().tolist()
    name = f"t_hist/window/{shape}/{seed}"
    h = telemetry.REGISTRY.histogram(name)
    before = [2500.0] * 3 + rng.lognormal(np.log(9.0), 0.6, 700).tolist()
    for v in before:
        h.observe(v)
    start = telemetry.snapshot()
    half = len(window) // 2
    for v in window[:half]:
        h.observe(v)
    h.observe_many(window[half:])
    end = telemetry.snapshot()
    assert end[f"{name}_count"] - start[f"{name}_count"] == n
    assert max(v for k, v in end.items() if k.startswith(f"{name}/le/")) == n + len(before)
    for q in (50, 95, 100):
        got = _window_percentile(start, end, name, q)
        assert got == pytest.approx(np.percentile(window, q), rel=0.05), q
    assert _window_percentile(start, end, name, 100) < 2500.0 * 0.9
    # The same keys it had, for a person and for dtxtop, over the ring.
    assert {f"{name}_{k}" for k in ("count", "p50", "p90", "p99", "max")} <= set(end)
    # From the first snapshot of all, the window is everything observed.
    everything = _window_percentile({f"{name}_count": 0}, end, name, 100)
    assert everything == pytest.approx(2500.0, rel=0.05)


def test_histogram_buckets_are_exact_under_threads_and_reset():
    """``observe`` and ``observe_many`` from many threads at once lose no
    observation of the ring's count or of the buckets; values at, under and
    over the grid's ends land in its first and last buckets; ``reset``
    empties the buckets with the ring."""
    n_threads, per = 8, 500
    h = telemetry.REGISTRY.histogram("t_hist/threads")
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        def body(i):
            for k in range(per):
                h.observe(1.0 + i)
                h.observe_many([0.0, 2.0**21, 1.0 + i])

        threads = [threading.Thread(target=body, args=(i,)) for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    total = n_threads * per * 4
    cum = h.cumulative()
    assert h.count == total and cum["inf"] == total
    assert cum["0.000976562"] == total // 4  # the zeros, at the lowest edge
    assert total - max(v for k, v in cum.items() if k != "inf") == total // 4
    # Each thread's value has a bucket of its own: 2 * per observations.
    inner = sorted(v for k, v in cum.items() if k not in ("inf", "0.000976562"))
    assert [b - a for a, b in zip([total // 4] + inner, inner)] == [2 * per] * n_threads
    h._reset()  # what ``REGISTRY.reset()`` calls on every instrument
    assert h.count == 0 and h.cumulative() == {}
    assert not [k for k in telemetry.snapshot() if k.startswith("t_hist/threads/le/")]


def test_latency_recorder_concurrent_hammer():
    """r13 satellite: percentile_scalars() must never read a half-updated
    ring while record() writes from other threads — the snapshot is taken
    under the recorder's lock, so every reduced percentile lies within
    the range of values ever recorded (a torn read would surface as a
    garbage duration from an unwritten slot)."""
    rec = LatencyRecorder(capacity=256)
    stop = threading.Event()
    LO, HI = 1e-3, 2e-3

    def writer(seed: int):
        rng = np.random.default_rng(seed)
        while not stop.is_set():
            rec.record(float(rng.uniform(LO, HI)))

    threads = [threading.Thread(target=writer, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    try:
        deadline = time.monotonic() + 0.5
        reads = 0
        while time.monotonic() < deadline:
            s = rec.percentile_scalars("h")
            if not s:
                continue
            reads += 1
            for p in (50, 90, 99):
                v = s[f"h/latency_p{p}_ms"]
                assert LO * 1e3 <= v <= HI * 1e3, (p, v)
            assert s["h/qps"] >= 0.0
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=10)
    assert reads > 10 and rec.total > 0


# ---------------------------------------------------------------------------
# Flight recorder
# ---------------------------------------------------------------------------


def test_flight_recorder_ring_and_dump(tmp_path):
    fr = telemetry.FlightRecorder(capacity=8)
    for i in range(12):
        fr.record("tick", i=i)
    assert len(fr) == 8  # bounded ring: oldest dropped
    assert [e["i"] for e in fr.events()] == list(range(4, 12))
    path = fr.dump(str(tmp_path / "flight.jsonl"), reason="unit")
    lines = [json.loads(l) for l in open(path)]
    assert lines[0]["event"] == "dump" and lines[0]["reason"] == "unit"
    assert lines[0]["retained"] == 8 and len(lines) == 9
    assert lines[1]["event"] == "tick" and lines[1]["i"] == 4
    assert all("ts" in l for l in lines)


def test_flight_recorder_no_dir_is_noop():
    fr = telemetry.FlightRecorder()
    fr.record("x")
    os.environ.pop(telemetry.EVENTS_DIR_ENV, None)
    assert fr.dump() is None  # fatal-path hooks are always safe to call


def test_log_event_and_fired_faults_feed_recorder():
    """Satellite: every fault that actually fires lands in the flight
    recorder as a structured event carrying role + its spec, via the
    ``faults.log_event`` hook — chaos-run failures stay attributable."""
    faults.log_event("obs_unit_probe", role="obsrole", k=1)
    inj = faults.ClientFaultInjector(
        role="obsrole", plan="drop_conn:role=obsrole,op=1;"
        "delay:role=obsrole,op=2,ms=1",
    )
    assert inj.before_op(17) is True  # drop fires on op 1
    inj.before_op(18)  # delay fires on op 2
    by_name: dict = {}
    for e in telemetry.RECORDER.events():
        by_name[e["event"]] = e  # latest occurrence wins
    assert "obs_unit_probe" in by_name
    drop = by_name.get("inject_drop_conn")
    assert drop is not None and drop["role"] == "obsrole"
    assert drop["spec"].startswith("drop_conn:"), drop
    delay = by_name.get("inject_delay")
    assert delay is not None and delay["spec"].startswith("delay:"), delay


def test_divergence_dumps_flight_recorder(tmp_path, monkeypatch):
    """Satellite + tentpole: a forced replication divergence (partitioned
    pair, then a state-mutating op) raises the loud PSError AND dumps the
    flight recorder into --obs_events_dir, with the divergence event and
    the partition injection retained — the post-mortem exists even though
    nothing was watching the process."""
    monkeypatch.setenv(telemetry.EVENTS_DIR_ENV, str(tmp_path))
    monkeypatch.setenv("DTX_FAULT_ROLE", "obsdiv")
    pa = ps_service.start_server(0)
    pb = ps_service.start_server(0, peer=("127.0.0.1", pa), sync_wait_s=10.0)
    ps_service.set_server_peer(pa, ("127.0.0.1", pb))
    try:
        c = ps_service.PSClient("127.0.0.1", pa, op_timeout_s=5.0)
        st = ps_service.RemoteParamStore(c, "params", 4, cache_pulls=False)
        st.set(1, np.zeros(4, np.float32))
        ps_service.set_server_partitioned(pa, True)
        with pytest.raises(ps_service.PSError, match="replication diverged"):
            st.set(2, np.ones(4, np.float32))
        dumps = sorted(tmp_path.glob("flight-obsdiv-*.jsonl"))
        assert dumps, list(tmp_path.iterdir())
        lines = [json.loads(l) for l in open(dumps[-1])]
        assert lines[0]["event"] == "dump"
        assert lines[0]["reason"] == "repl_diverged"
        assert any(e["event"] == "repl_diverged" for e in lines), lines
        c.close()
    finally:
        ps_service.stop_server()


# ---------------------------------------------------------------------------
# STATS round trips, service by service
# ---------------------------------------------------------------------------


def test_ps_stats_roundtrip_f32_and_bf16():
    port = ps_service.start_server(0)
    try:
        c = ps_service.PSClient("127.0.0.1", port, timeout_s=5.0)
        st = ps_service.RemoteParamStore(c, "params", 8)
        st.set(1, np.arange(8, dtype=np.float32))
        s = c.stats()
        for k in REQUIRED_KEYS["ps"]:
            assert k in s, (k, s)
        assert s["service"] == "ps" and s["requests"] > 0
        assert s["shard_id"] == 0 and s["shard_count"] == 1
        assert s["replicated"] == 0 and s["diverged"] == 0
        assert s["incarnation"] == c.incarnation()
        # Observation must not perturb the observed counter: ``requests``
        # is the die:after_reqs fault trigger, so the WHOLE scrape
        # footprint — a fresh dial's HELLO + INCARNATION + the STATS op —
        # is excluded.  Two complete fresh-client scrapes of an idle
        # server read the SAME count.
        def fresh_scrape() -> int:
            c2 = ps_service.PSClient(
                "127.0.0.1", port, timeout_s=5.0, expect_shard=(0, 1)
            )
            try:
                return c2.stats()["requests"]
            finally:
                c2.close()

        assert fresh_scrape() == fresh_scrape()
        # The blob is raw bytes in 4-byte units: a bf16 connection reads
        # the SAME table, never a dtype-mangled one.
        cb = ps_service.PSClient(
            "127.0.0.1", port, timeout_s=5.0, wire_dtype="bf16"
        )
        sb = cb.stats()
        assert sb["service"] == "ps" and sb["incarnation"] == s["incarnation"]
        cb.close()
        c.close()
    finally:
        ps_service.stop_server()


def test_ps_stats_replication_counters_visible():
    """The r12 replication machinery is externally countable: the backup's
    start-time REPL_SYNC shows on the primary, forwarded publishes count
    as fwd_ok, and dedup-mirror applies show on the backup."""
    pa = ps_service.start_server(0)
    pb = ps_service.start_server(0, peer=("127.0.0.1", pa), sync_wait_s=10.0)
    ps_service.set_server_peer(pa, ("127.0.0.1", pb))
    try:
        c = ps_service.PSClient(
            "127.0.0.1", pa, op_timeout_s=5.0, worker_tag=3
        )
        st = ps_service.RemoteParamStore(c, "params", 4, cache_pulls=False)
        st.set(1, np.zeros(4, np.float32))
        gq = ps_service.RemoteGradientQueue(c, "grads", 4)
        gq.push(1, np.ones(4, np.float32))
        sa = ps_service.PSClient("127.0.0.1", pa, timeout_s=5.0).stats()
        sb = ps_service.PSClient("127.0.0.1", pb, timeout_s=5.0).stats()
        assert sa["replicated"] == 1 and sb["replicated"] == 1
        assert sa["repl_syncs_served"] >= 1  # the backup's start catch-up
        assert sa["fwd_ok"] >= 2  # create + publish + tagged mirror
        assert sb["mirror_applies"] >= 1  # the tagged push's mirror
        assert sa["state_token"] == sb["state_token"]  # one lineage
        c.close()
    finally:
        ps_service.stop_server()


def test_dsvc_stats_assignment_counters_and_registry():
    from distributed_tensorflow_examples_tpu.data import data_service

    splits = [{"x": np.arange(4, dtype=np.float32)} for _ in range(3)]
    server = data_service.DataServiceServer(splits, batch_size=2)
    try:
        c = data_service.DataServiceClient(
            "127.0.0.1", server.port, worker_id=0, reconnect_deadline_s=0.0,
        )
        s0, _ = c.call(data_service.DSVC_GET_SPLIT, name="epoch=0", a=0, b=-1)
        assert s0 >= 0
        c.call(data_service.DSVC_GET_SPLIT, name="epoch=0", a=0, b=s0)  # ack
        s = c.stats()
        for k in REQUIRED_KEYS["dsvc"]:
            assert k in s, (k, s)
        assert s["service"] == "dsvc"
        assert s["assigned_total"] >= 2 and s["acks"] >= 1
        assert isinstance(s["registry"], dict)
        c.close()

        # Observation must not perturb the die:after_reqs trigger here
        # either: a dtxtop-style probe (fresh dial = HELLO + metadata
        # REGISTER + STATS) leaves the request counter unchanged.
        def fresh_scrape() -> int:
            p = data_service.DataServiceClient(
                "127.0.0.1", server.port, worker_id=-1,
                reconnect_deadline_s=0.0, role="dtxtop",
            )
            try:
                return p.stats()["requests"]
            finally:
                p.close()

        assert fresh_scrape() == fresh_scrape()
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# The acceptance e2e + dtxtop schema
# ---------------------------------------------------------------------------


def _replicated_ps(n_shards: int):
    """2-replica in-process PS: returns the replica-major address list
    (primaries then backups, the --ps_hosts convention)."""
    primaries = [
        ps_service.start_server(0, shard_id=i, shard_count=n_shards)
        for i in range(n_shards)
    ]
    backups = [
        ps_service.start_server(
            0, shard_id=i, shard_count=n_shards,
            peer=("127.0.0.1", primaries[i]), sync_wait_s=10.0,
        )
        for i in range(n_shards)
    ]
    for i in range(n_shards):
        ps_service.set_server_peer(primaries[i], ("127.0.0.1", backups[i]))
    return [("127.0.0.1", p) for p in primaries + backups]


def test_dtxtop_scrapes_full_replicated_cluster(capsys):
    """THE acceptance scenario: a live 2-shard x 2-replica PS + data
    service + 2-replica serve cluster under load answers ONE dtxtop
    scrape with every role's counters — the native servers' replication
    counters included — and `dtxtop --json` exits 0 on it."""
    import jax

    from distributed_tensorflow_examples_tpu import models, serve
    from distributed_tensorflow_examples_tpu.data import data_service
    from distributed_tensorflow_examples_tpu.serve import model_server

    CFG = models.mlp.Config(hidden=(8,), compute_dtype="float32")
    all_addrs = _replicated_ps(2)
    primaries = all_addrs[:2]
    rng = np.random.default_rng(0)
    splits = [
        {"image": rng.normal(size=(8, 784)).astype(np.float32)}
        for _ in range(3)
    ]
    dsvc = data_service.DataServiceServer(splits, batch_size=4)
    group = None
    servers, clients = [], []
    try:
        # Publisher: a REPLICATED client group, so publishes forward to
        # the backups (fwd_ok lights up on the primaries).
        group = ps_shard.ShardedPSClients(all_addrs, role="obs_pub", replicas=2)
        params = models.mlp.init(CFG, jax.random.key(0))
        total, _ = ps_shard.flat_param_spec(params)
        store = ps_shard.ShardedParamStore(
            group, "params", ps_shard.ShardLayout(total, 2)
        )
        flat = np.concatenate(
            [np.asarray(l).reshape(-1) for l in jax.tree.leaves(params)]
        ).astype(np.float32)
        for step in (1, 2, 3):
            store.set(step, flat)
        for _ in range(2):
            servers.append(model_server.ModelReplicaServer(
                lambda r: models.mlp.init(CFG, r),
                lambda p, batch: models.mlp.apply(CFG, p, batch["image"]),
                primaries, max_batch=8, refresh_ms=20.0,
            ))
        serve_addrs = [("127.0.0.1", s.port) for s in servers]
        for s in servers:
            assert s.wait_for_model(60)
        # Load on every wire: predicts on both replicas, a batch pull.
        x = np.zeros((4, 784), np.float32)
        for h, p in serve_addrs:
            sc = serve.ServeClient(
                h, p, role="obs_load_sv", reconnect_deadline_s=0.0
            )
            clients.append(sc)
            for _ in range(8):
                step, out = sc.predict({"image": x})
                assert step == 3 and out["output"].shape == (4, 10)
        dc = data_service.DataServiceClient(
            "127.0.0.1", dsvc.port, worker_id=0, reconnect_deadline_s=0.0,
        )
        clients.append(dc)
        dc.call(data_service.DSVC_GET_BATCH, name="0", a=0, b=0, batch=True)

        snap = dtxtop.snapshot(
            all_addrs, ps_shards=2, ps_replicas=2,
            dsvc_addrs=[("127.0.0.1", dsvc.port)], serve_addrs=serve_addrs,
        )
        assert snap["schema_version"] == dtxtop.SNAPSHOT_SCHEMA_VERSION
        assert snap["summary"]["roles_total"] == 7
        assert snap["summary"]["roles_ok"] == 7, [
            (r["role"], r.get("error")) for r in snap["roles"]
        ]
        assert missing_counters(snap) == []
        by_role = {r["role"]: r["stats"] for r in snap["roles"]}
        # Native replication counters, in one scrape, from outside.
        for i in (0, 1):  # primaries forwarded the publishes
            assert by_role[f"ps{i}"]["fwd_ok"] >= 1, by_role[f"ps{i}"]
            assert by_role[f"ps{i}"]["replicated"] == 1
            assert by_role[f"ps{i}"]["repl_syncs_served"] >= 1
        for i in (2, 3):  # backups: shard identity matches the flat order
            assert by_role[f"ps{i}"]["shard_id"] == i - 2
        assert by_role["data_service0"]["batches_served"] >= 1
        assert snap["summary"]["serve"]["model_steps"] == [3, 3]
        assert snap["summary"]["serve"]["predict_rows"] == 64
        for i in (0, 1):
            assert by_role[f"serve{i}"]["batcher_batch_rows_count"] >= 8
            assert by_role[f"serve{i}"]["registry"]["ps_shard/pulls"] >= 1
        # The human renderer covers every role kind without choking.
        table = dtxtop.render(snap, None)
        assert "serve1" in table and "data_service0" in table

        # `dtxtop --json` one-shot: machine snapshot on stdout, exit 0.
        rc = dtxtop.main([
            "--json",
            "--ps_hosts", ",".join(f"{h}:{p}" for h, p in all_addrs),
            "--ps_shards", "2", "--ps_replicas", "2",
            "--data_service_hosts", f"127.0.0.1:{dsvc.port}",
            "--serve_hosts", ",".join(f"{h}:{p}" for h, p in serve_addrs),
        ])
        out = capsys.readouterr().out
        doc = json.loads(out.strip().splitlines()[-1])
        assert rc == 0 and doc["summary"]["roles_ok"] == 7
        # Serve STATS carries everything REQUIRED_KEYS pins (checked via
        # missing_counters above) — spot-check the histogram family.
        srv_stats = by_role["serve0"]
        assert srv_stats["batcher_queue_depth_p99"] >= 1
        # And the scrape footprint (fresh dial's HELLO + STATS) is
        # excluded from the replica's die:after_reqs trigger too.
        h, p = serve_addrs[0]

        def fresh_serve_scrape() -> int:
            pr = serve.ServeClient(
                h, p, role="probe_sv", reconnect_deadline_s=0.0
            )
            try:
                return pr.stats()["requests"]
            finally:
                pr.close()

        assert fresh_serve_scrape() == fresh_serve_scrape()
    finally:
        for c in clients:
            c.close()
        for s in servers:
            s.stop()
        dsvc.stop()
        if group is not None:
            group.close()
        ps_service.stop_server()


def test_dtxtop_wrong_service_and_down_roles_fail_loudly():
    """A mis-wired scrape is a LOUD row, never a misread table: a PS
    entry pointing at a data service names the service actually reached,
    and a dead port reports DOWN with the transport error."""
    from distributed_tensorflow_examples_tpu.data import data_service

    splits = [{"x": np.arange(4, dtype=np.float32)}]
    dsvc = data_service.DataServiceServer(splits, batch_size=2)
    pa = ps_service.start_server(0)
    try:
        snap = dtxtop.snapshot(
            [("127.0.0.1", dsvc.port)], ps_shards=1,
            dsvc_addrs=[("127.0.0.1", pa)],
        )
        ps_row, dsvc_row = snap["roles"]
        assert not ps_row["ok"] and "wrong-service" in ps_row["error"]
        assert "data service" in ps_row["error"]
        assert not dsvc_row["ok"]
        assert "native PS state service" in dsvc_row["error"]
        assert snap["summary"]["roles_ok"] == 0
        # a dead port: DOWN row, not an exception out of snapshot()
        dead = dtxtop.snapshot([], dsvc_addrs=[("127.0.0.1", 1)])
        assert not dead["roles"][0]["ok"]
    finally:
        dsvc.stop()
        ps_service.stop_server()


def test_dtxtop_resolves_shards_from_replica_tier():
    """--ps_replicas without --ps_shards: a 4-host 2-replica cluster is 2
    shards — deriving 4 would pin every scrape's HELLO to a wrong shard
    identity and render a healthy cluster DOWN."""
    addrs = [("h", 1), ("h", 2), ("h", 3), ("h", 4)]
    assert dtxtop.resolve_shards(addrs, -1, 2) == 2
    assert dtxtop.resolve_shards(addrs, -1, 1) == 4
    assert dtxtop.resolve_shards(addrs, 3, 2) == 3  # explicit wins
    roles = dtxtop.cluster_roles(addrs, ps_shards=-1, ps_replicas=2)
    assert [(r["shard"], r["replica"]) for r in roles] == [
        (0, 0), (1, 0), (0, 1), (1, 1)
    ]


def test_obs_snapshot_step_missing_counter_detection():
    """The CI gate really fails on a hole: a role with a missing counter
    or a DOWN role is reported by name."""
    snap = {
        "roles": [
            {"role": "ps0", "kind": "ps", "ok": True,
             "stats": {k: 0 for k in REQUIRED_KEYS["ps"] if k != "fwd_ok"}},
            {"role": "serve0", "kind": "serve", "ok": False,
             "error": "ConnectionRefusedError"},
        ],
    }
    problems = missing_counters(snap)
    assert any("ps0" in p and "fwd_ok" in p for p in problems), problems
    assert any("serve0" in p and "DOWN" in p for p in problems), problems
