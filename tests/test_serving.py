"""Online inference plane (r10 tentpole): batcher semantics, wire service
identity, PS hot-tracking, and batched/unbatched output parity.

The serving plane is the first consumer of the parameter-store substrate
that is not a training worker: replicas track the published (step, params)
snapshot with versioned pulls, coalesce predict requests into one jitted
apply, and stamp every response with the served ``model_step``.  These
tests pin the pieces the fault matrix (tests/test_faults.py) then composes:

- DynamicBatcher: coalesce-to-full, flush-on-timeout, bounded-queue
  OVERLOAD admission control, oversized-request carry, error propagation.
- HELLO service identity: every wrong-service dial (ps/dsvc/msrv in any
  pairing) fails the connect loudly naming both ends.
- ModelReplicaServer: served ``model_step`` advances after a PS publish
  with NO restart; outputs are byte-identical batched vs unbatched (the
  padded-apply contract); OVERLOAD surfaces to clients as the typed error.
- LatencyRecorder: percentile/qps scalar family naming.
"""

from __future__ import annotations

import inspect
import threading
import time

import numpy as np
import pytest

from distributed_tensorflow_examples_tpu import serve
from distributed_tensorflow_examples_tpu.data import data_service as dsvc
from distributed_tensorflow_examples_tpu.models.decoding import DecodeFns
from distributed_tensorflow_examples_tpu.parallel import (
    ps_service,
    ps_shard,
    wire,
)
from distributed_tensorflow_examples_tpu.serve import batcher as batcher_lib
from distributed_tensorflow_examples_tpu.utils import metrics, telemetry

D = 16


def _init_fn(rng):
    import jax.numpy as jnp

    return {"w": jnp.zeros((D, 4), jnp.float32), "b": jnp.zeros((4,), jnp.float32)}


def _predict_fn(params, batch):
    return batch["x"] @ params["w"] + params["b"]


def _publish(addrs, step, scale=1.0):
    """The chief's publish path (ShardedParamStore.set — what
    RemotePSChief._publish runs) with deterministic step-dependent values."""
    group = ps_shard.ShardedPSClients(addrs, role="pub", op_timeout_s=10.0)
    layout = ps_shard.ShardLayout(D * 4 + 4, len(addrs))
    pstore = ps_shard.ShardedParamStore(group, "params", layout)
    flat = scale * np.arange(D * 4 + 4, dtype=np.float32) / (D * 4 + 4)
    pstore.set(step, flat)
    return group, pstore, flat


def _params_of(flat):
    # jax.tree.flatten orders dict leaves by sorted key: "b" before "w".
    return {
        "b": flat[:4],
        "w": flat[4:].reshape(D, 4),
    }


# ----------------------------------------------------------------------------
# DynamicBatcher
# ----------------------------------------------------------------------------


def test_ticket_on_resolve_runs_exactly_once_and_resolve_is_idempotent():
    """r17 async-reply contract: the register/resolve handoff is
    lock-guarded (a double callback would queue two response frames for
    one request), and a SECOND resolve — the wedged-apply timeout sweep
    racing a genuine late resolution — is a no-op (first wins)."""
    from distributed_tensorflow_examples_tpu.serve import batcher as b

    # Register-then-resolve: exactly one invocation, with the value.
    t = b.Ticket(1)
    calls = []
    t.on_resolve(lambda v, e: calls.append((v, e)))
    t._resolve(value="first")
    t._resolve(error=TimeoutError("sweep raced in late"))  # discarded
    assert calls == [("first", None)]
    assert t.result(timeout_s=1.0) == "first"
    # Resolve-then-register: the callback fires immediately, once.
    t2 = b.Ticket(1)
    t2._resolve(error=RuntimeError("boom"))
    calls2 = []
    t2.on_resolve(lambda v, e: calls2.append((v, e)))
    assert len(calls2) == 1 and isinstance(calls2[0][1], RuntimeError)
    # Hammer the handoff from two threads: never zero, never double.
    import threading as th

    for _ in range(200):
        tk = b.Ticket(1)
        got = []
        barrier = th.Barrier(2)

        def registrar():
            barrier.wait()
            tk.on_resolve(lambda v, e: got.append(v))

        def resolver():
            barrier.wait()
            tk._resolve(value=42)

        a, c = th.Thread(target=registrar), th.Thread(target=resolver)
        a.start(); c.start(); a.join(); c.join()
        assert got == [42]


def test_batcher_coalesces_concurrent_requests_into_one_apply():
    applies: list[list] = []

    def run_batch(items):
        applies.append(items)
        return [sum(it) for it in items]

    b = batcher_lib.DynamicBatcher(
        run_batch, max_batch=8, max_wait_ms=500.0, queue_depth=64
    )
    try:
        results = [None] * 8
        barrier = threading.Barrier(8)

        def submit(i):
            barrier.wait()
            results[i] = b.submit([i, i], rows=1).result(timeout_s=10.0)

        ts = [threading.Thread(target=submit, args=(i,)) for i in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=10)
        assert results == [2 * i for i in range(8)]
        # 8 concurrent submits under a 500 ms window with max_batch=8:
        # ONE full flush, not eight applies.
        assert len(applies) == 1 and len(applies[0]) == 8
        s = b.stats()
        assert s["flush_full"] == 1 and s["batches"] == 1
        assert s["rows_batched"] == 8 and s["inflight"] == 0
    finally:
        b.stop()


def test_batcher_flushes_lone_request_on_timeout():
    b = batcher_lib.DynamicBatcher(
        lambda items: [len(items)], max_batch=8, max_wait_ms=40.0
    )
    try:
        t0 = time.monotonic()
        out = b.submit("x").result(timeout_s=10.0)
        dt = time.monotonic() - t0
        assert out == 1
        assert dt >= 0.030, dt  # the window was honored (lone request waits)
        s = b.stats()
        assert s["flush_timeout"] == 1 and s["flush_full"] == 0
        assert s["last_batch_rows"] == 1
    finally:
        b.stop()


def test_batcher_overload_is_immediate_and_bounded():
    gate = threading.Event()

    def run_batch(items):
        gate.wait(timeout=30.0)
        return list(items)

    b = batcher_lib.DynamicBatcher(
        run_batch, max_batch=1, max_wait_ms=1.0, queue_depth=2
    )
    try:
        t1 = b.submit("a")
        t2 = b.submit("b")
        # Two in-system requests at depth 2: admission control refuses the
        # third IMMEDIATELY (no queuing, no blocking).
        t0 = time.monotonic()
        with pytest.raises(batcher_lib.Overloaded):
            b.submit("c")
        assert time.monotonic() - t0 < 1.0
        assert b.stats()["overloads"] == 1
        gate.set()
        assert t1.result(timeout_s=10.0) == "a"
        assert t2.result(timeout_s=10.0) == "b"
        # Drained: admission reopens.
        assert b.submit("d").result(timeout_s=10.0) == "d"
    finally:
        gate.set()
        b.stop()


def test_batcher_row_budget_carries_overflow_and_runs_oversized_alone():
    sizes: list[list[int]] = []

    def run_batch(items):
        sizes.append([r for r in items])
        return list(items)

    b = batcher_lib.DynamicBatcher(
        run_batch, max_batch=4, max_wait_ms=300.0, queue_depth=64
    )
    try:
        # 3 + 3 rows: the second request would overflow the 4-row budget,
        # so it is CARRIED whole into the next batch — never split.
        t1 = b.submit(3, rows=3)
        t2 = b.submit(3, rows=3)
        assert t1.result(timeout_s=10.0) == 3
        assert t2.result(timeout_s=10.0) == 3
        assert sizes == [[3], [3]]
        # A lone request larger than max_batch runs as its own batch.
        t3 = b.submit(9, rows=9)
        assert t3.result(timeout_s=10.0) == 9
        assert sizes[-1] == [9]
    finally:
        b.stop()


def test_batcher_apply_error_reaches_every_submitter():
    def run_batch(items):
        raise ValueError("bad apply")

    b = batcher_lib.DynamicBatcher(run_batch, max_batch=4, max_wait_ms=50.0)
    try:
        t1, t2 = b.submit("a"), b.submit("b")
        for t in (t1, t2):
            with pytest.raises(ValueError, match="bad apply"):
                t.result(timeout_s=10.0)
        assert b.stats()["inflight"] == 0  # errors still release admission
    finally:
        b.stop()


# ----------------------------------------------------------------------------
# HELLO service identity (the r10 wire satellite)
# ----------------------------------------------------------------------------


def test_hello_answer_helper_matrix():
    V = wire.WIRE_VERSION
    # Right service, right version: success + tag.
    st, tag = wire.hello_answer(V, wire.pack_hello_b(0, service="msrv"), service="msrv")
    assert st == V and tag == b"msrv"
    # No announcement (legacy): accepted.
    st, tag = wire.hello_answer(V, 0, service="dsvc")
    assert st == V and tag == b"dsvc"
    # Wrong service: refused with a status naming the ANSWERING service.
    st, tag = wire.hello_answer(V, wire.pack_hello_b(0, service="ps"), service="msrv")
    assert tag is None and wire.unpack_wrong_service(st) == "msrv"
    # Bad version / bad dtype: plain -1.
    assert wire.hello_answer(V + 1, 0, service="msrv")[0] == -1
    assert wire.hello_answer(V, 1, service="msrv")[0] == -1
    # The announcement bits coexist with the shard-identity bits.
    b = wire.pack_hello_b(1, 3, 7, service="ps")
    assert b & 0xFF == 1
    assert wire.hello_expected_service(b) == "ps"
    assert (b >> wire.HELLO_SHARD_ID_SHIFT) & wire.HELLO_SHARD_MASK == 3
    assert (b >> wire.HELLO_SHARD_COUNT_SHIFT) & wire.HELLO_SHARD_MASK == 7
    # hello_failure: success answers None, everything else names both ends.
    assert wire.hello_failure(V, b"msrv", service="msrv", host="h", port=1) is None
    msg = wire.hello_failure(
        wire.wrong_service_status("dsvc"), None, service="msrv", host="h", port=1
    )
    assert "data service" in msg and "msrv" in msg
    msg = wire.hello_failure(V, None, service="dsvc", host="h", port=1)
    assert "PS state service" in msg and "not a data service" in msg


def test_every_wrong_service_dial_fails_loudly():
    """The full 3-service pairing matrix: dialing any service with another
    service's client fails the CONNECT naming both ends — never misparses
    op codes, never silently serves."""
    ps_port = ps_service.start_server(0)
    dsrv = dsvc.DataServiceServer(
        [{"image": np.zeros((8, 4), np.uint8), "label": np.zeros(8, np.int64)}],
        batch_size=4,
    )
    msrv = serve.ModelReplicaServer(
        _init_fn, _predict_fn, [("127.0.0.1", ps_port)], role="srv_t"
    )
    try:
        with pytest.raises(dsvc.DSVCError, match="model-serving"):
            dsvc.DataServiceClient(
                "127.0.0.1", msrv.port, role="x_ds", reconnect_deadline_s=0.0
            )
        with pytest.raises(serve.ServeError, match="data service"):
            serve.ServeClient(
                "127.0.0.1", dsrv.port, role="x_sv", reconnect_deadline_s=0.0
            )
        with pytest.raises(serve.ServeError, match="PS state service"):
            serve.ServeClient(
                "127.0.0.1", ps_port, role="x_sv", reconnect_deadline_s=0.0
            )
        # The PS client HELLOs whenever it carries an expectation (shard or
        # bf16); both must refuse loudly against a serving replica.
        with pytest.raises(ps_service.PSError, match="model-serving"):
            ps_service.PSClient(
                "127.0.0.1", msrv.port, timeout_s=5.0, expect_shard=(0, 1)
            )
        with pytest.raises(ps_service.PSError, match="data service"):
            ps_service.PSClient(
                "127.0.0.1", dsrv.port, timeout_s=5.0, wire_dtype="bf16"
            )
        # Correct dials still work after the refusals.
        c = ps_service.PSClient("127.0.0.1", ps_port, timeout_s=5.0,
                                expect_shard=(0, 1))
        c.ping()
        c.close()
    finally:
        msrv.stop()
        dsrv.stop()
        ps_service.stop_server()


# ----------------------------------------------------------------------------
# ModelReplicaServer: hot-tracking + parity + overload
# ----------------------------------------------------------------------------


def test_model_step_advances_after_publish_without_restart():
    ports = [ps_service.start_server(0, shard_id=i, shard_count=2) for i in (0, 1)]
    addrs = [("127.0.0.1", p) for p in ports]
    group, pstore, flat0 = _publish(addrs, step=0, scale=1.0)
    srv = serve.ModelReplicaServer(
        _init_fn, _predict_fn, addrs, max_batch=8, max_wait_ms=2.0,
        refresh_ms=10.0, role="srv_t",
    )
    try:
        assert srv.wait_for_model(30.0)
        c = serve.ServeClient("127.0.0.1", srv.port, role="t_sv")
        x = np.random.default_rng(0).normal(size=(3, D)).astype(np.float32)
        step, out = c.predict({"x": x})
        assert step == 0
        np.testing.assert_allclose(
            out["output"], x @ _params_of(flat0)["w"] + _params_of(flat0)["b"],
            rtol=1e-5,
        )
        incarnation0 = c.stats()["incarnation"]
        # The chief publishes a new update: the replica's served step must
        # advance via the versioned-pull refresher — no restart, same
        # incarnation.
        flat7 = 3.0 * flat0
        pstore.set(7, flat7)
        deadline = time.monotonic() + 30
        while True:
            step, out = c.predict({"x": x})
            if step == 7:
                break
            assert time.monotonic() < deadline, "model_step never advanced"
            time.sleep(0.02)
        np.testing.assert_allclose(
            out["output"], x @ _params_of(flat7)["w"] + _params_of(flat7)["b"],
            rtol=1e-5,
        )
        st = c.stats()
        assert st["incarnation"] == incarnation0  # hot update, not restart
        assert st["model_step"] == 7
        assert st["refreshes"] >= 2
        # The latency family rides the STATS payload under the
        # shard_scalars-style naming (dashboards glob serve/latency_*).
        assert "serve/latency_p50_ms" in st and "serve/qps" in st
        c.close()
    finally:
        srv.stop()
        group.close()
        ps_service.stop_server()


def test_extension_dtype_predict_round_trips_bf16():
    """The example models compute in bf16 by default, so the serving wire
    must move ml_dtypes extension dtypes BOTH ways: PEP 3118 has no format
    code for them (memoryview casts raise), and their ``dtype.str`` is a
    void '<V2' that would silently decode as raw bytes — the codec must
    use uint8 views and the registered dtype NAME instead (the r10 CLI
    drive caught exactly this)."""
    import ml_dtypes

    ports = [ps_service.start_server(0, shard_id=0, shard_count=1)]
    addrs = [("127.0.0.1", p) for p in ports]
    group, pstore, flat0 = _publish(addrs, step=0, scale=1.0)

    def bf16_predict(params, batch):
        import jax.numpy as jnp

        x = batch["x"].astype(jnp.bfloat16)
        return (x @ params["w"].astype(jnp.bfloat16)).astype(jnp.bfloat16)

    srv = serve.ModelReplicaServer(
        _init_fn, bf16_predict, addrs, max_batch=8, max_wait_ms=2.0,
        refresh_ms=10.0, role="srv_bf",
    )
    try:
        assert srv.wait_for_model(30.0)
        c = serve.ServeClient("127.0.0.1", srv.port, role="bf_sv")
        x = np.random.default_rng(3).normal(size=(4, D)).astype(np.float32)
        # bf16 INPUTS must survive the client-side encode too.
        xb = x.astype(ml_dtypes.bfloat16)
        step, out = c.predict({"x": xb})
        assert step == 0
        assert out["output"].dtype == np.dtype(ml_dtypes.bfloat16)
        expect = (
            xb.astype(np.float32) @ _params_of(flat0)["w"]
        ).astype(ml_dtypes.bfloat16)
        np.testing.assert_allclose(
            out["output"].astype(np.float32), expect.astype(np.float32),
            rtol=0.05, atol=0.05,
        )
        c.close()
    finally:
        srv.stop()
        group.close()
        ps_service.stop_server()


def test_batched_and_unbatched_outputs_byte_identical():
    """The padded-apply contract: a request's output rows are bitwise
    identical whether it was served alone or coalesced with 7 peers —
    padding keeps every apply at ONE shape, and row-wise models make the
    other rows inert."""
    port = ps_service.start_server(0)
    addrs = [("127.0.0.1", port)]
    group, _, _ = _publish(addrs, step=0)
    srv = serve.ModelReplicaServer(
        _init_fn, _predict_fn, addrs, max_batch=8, max_wait_ms=60.0,
        refresh_ms=10.0, role="srv_t",
    )
    try:
        assert srv.wait_for_model(30.0)
        rng = np.random.default_rng(1)
        xs = [rng.normal(size=(1, D)).astype(np.float32) for _ in range(8)]
        # Unbatched reference: one connection, strictly sequential — each
        # request flushes alone (on the generous window, as a 1-row batch).
        solo = serve.ServeClient("127.0.0.1", srv.port, role="solo_sv")
        ref = [solo.predict({"x": x})[1]["output"] for x in xs]
        flushes_before = srv.stats()["batcher_batches"]
        # Batched: 8 concurrent clients, coalesced into one full apply.
        outs: list = [None] * 8
        barrier = threading.Barrier(8)

        def body(i):
            c = serve.ServeClient("127.0.0.1", srv.port, role=f"b{i}_sv")
            barrier.wait()
            outs[i] = c.predict({"x": xs[i]})[1]["output"]
            c.close()

        ts = [threading.Thread(target=body, args=(i,)) for i in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=30)
        assert all(o is not None for o in outs)
        for i in range(8):
            # Byte-identical, not allclose: same padded shape, same kernel,
            # row-independent math.
            assert np.array_equal(ref[i], outs[i]), i
        st = srv.stats()
        assert st["batcher_flush_full"] >= 1  # the 8 really coalesced
        assert st["batcher_batches"] >= flushes_before + 1
        solo.close()
    finally:
        srv.stop()
        group.close()
        ps_service.stop_server()


def test_overload_answers_explicit_status_and_recovers():
    port = ps_service.start_server(0)
    addrs = [("127.0.0.1", port)]
    group, _, _ = _publish(addrs, step=0)
    # A slow apply + depth 2: concurrent load must trip admission control.
    import jax.numpy as jnp

    def slow_predict(params, batch):
        return batch["x"] @ params["w"] + params["b"] + 0 * jnp.sum(
            batch["x"] ** 2
        )

    srv = serve.ModelReplicaServer(
        _init_fn, slow_predict, addrs, max_batch=1, max_wait_ms=1.0,
        queue_depth=2, refresh_ms=10.0, role="srv_t",
    )
    try:
        assert srv.wait_for_model(30.0)
        x = np.ones((1, D), np.float32)
        n_overload = [0]
        n_ok = [0]

        def hammer(i):
            c = serve.ServeClient("127.0.0.1", srv.port, role=f"h{i}_sv")
            for _ in range(25):
                try:
                    c.predict({"x": x})
                    n_ok[0] += 1
                except serve.ServeOverloadError:
                    n_overload[0] += 1
            c.close()

        ts = [threading.Thread(target=hammer, args=(i,)) for i in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
        assert n_ok[0] > 0
        assert n_overload[0] > 0, "depth-2 admission control never tripped"
        assert srv.stats()["overloads"] == n_overload[0]
        # The replica recovers once load stops: a fresh request succeeds.
        c = serve.ServeClient("127.0.0.1", srv.port, role="after_sv")
        step, out = c.predict({"x": x})
        assert step == 0 and out["output"].shape == (1, 4)
        c.close()
    finally:
        srv.stop()
        group.close()
        ps_service.stop_server()


def test_pool_round_robins_and_ejects_dead_replica():
    port = ps_service.start_server(0)
    addrs = [("127.0.0.1", port)]
    group, _, _ = _publish(addrs, step=0)
    srv1 = serve.ModelReplicaServer(
        _init_fn, _predict_fn, addrs, max_wait_ms=2.0, refresh_ms=10.0,
        role="srv_a",
    )
    srv2 = serve.ModelReplicaServer(
        _init_fn, _predict_fn, addrs, max_wait_ms=2.0, refresh_ms=10.0,
        role="srv_b",
    )
    try:
        assert srv1.wait_for_model(30.0) and srv2.wait_for_model(30.0)
        pool = serve.ServePool(
            [("127.0.0.1", srv1.port), ("127.0.0.1", srv2.port)],
            role="pool_sv", op_timeout_s=5.0, eject_s=0.5, deadline_s=30.0,
        )
        x = np.ones((2, D), np.float32)
        seen = set()
        for _ in range(6):
            pool.predict({"x": x})
            seen.add(pool.last_replica)
        assert seen == {0, 1}  # round-robin reached both replicas
        # Kill replica 0: the pool ejects it and every request still
        # succeeds on the survivor — zero failed client requests.
        srv1.stop()
        for _ in range(10):
            step, out = pool.predict({"x": x})
            assert step == 0 and out["output"].shape == (2, 4)
        assert pool.ejections >= 1
        assert pool.last_replica == 1
        pool.close()
    finally:
        for s in (srv1, srv2):
            try:
                s.stop()
            except Exception:
                pass
        group.close()
        ps_service.stop_server()


def test_mismatched_schema_cannot_poison_a_neighbours_batch():
    """Requests coalesce only with schema-identical neighbours: a client
    sending the wrong trailing shape fails ALONE (typed rejection), while
    schema-matched concurrent requests keep succeeding — and at the
    batcher level, differing keys land in separate applies."""
    applies: list[list] = []

    def run_batch(items):
        applies.append(list(items))
        return items

    b = batcher_lib.DynamicBatcher(
        run_batch, max_batch=8, max_wait_ms=50.0, queue_depth=64
    )
    try:
        ts = [
            b.submit(f"a{i}" if i % 2 == 0 else f"b{i}",
                     key="A" if i % 2 == 0 else "B")
            for i in range(6)
        ]
        for t in ts:
            t.result(timeout_s=10.0)
        assert len(applies) >= 2  # alternating keys can never share one
        for batch in applies:
            assert len({it[0] for it in batch}) == 1  # key-homogeneous
    finally:
        b.stop()

    port = ps_service.start_server(0)
    addrs = [("127.0.0.1", port)]
    group, _, _ = _publish(addrs, step=0)
    srv = serve.ModelReplicaServer(
        _init_fn, _predict_fn, addrs, max_batch=8, max_wait_ms=20.0,
        refresh_ms=10.0, role="srv_mix",
    )
    try:
        assert srv.wait_for_model(30.0)
        good = serve.ServeClient("127.0.0.1", srv.port, role="good_sv")
        bad = serve.ServeClient("127.0.0.1", srv.port, role="bad_sv")
        x = np.ones((2, D), np.float32)
        stop = threading.Event()
        failures: list[BaseException] = []

        def good_loop():
            while not stop.is_set():
                try:
                    step, out = good.predict({"x": x})
                    assert out["output"].shape == (2, 4)
                except BaseException as e:  # noqa: BLE001 — the assertion
                    failures.append(e)
                    return

        th = threading.Thread(target=good_loop)
        th.start()
        try:
            # Wrong trailing dim: same field name, so only the schema key
            # keeps it out of the good client's batches.  It must fail
            # alone, every time, while the good stream never errors.
            for _ in range(20):
                with pytest.raises(serve.ServeRejectedError):
                    bad.predict({"x": np.ones((2, D + 1), np.float32)})
        finally:
            stop.set()
            th.join(timeout=30.0)
        assert not failures, f"well-formed neighbour failed: {failures[0]!r}"
        good.close()
        bad.close()
    finally:
        srv.stop()
        group.close()
        ps_service.stop_server()


def test_pool_surfaces_rejection_immediately_without_ejecting():
    """An application-level rejection (the replica ANSWERED: bad request)
    must reach the caller as ServeRejectedError at once — not bench the
    healthy replica, not replay on peers until the deadline."""
    port = ps_service.start_server(0)
    addrs = [("127.0.0.1", port)]
    group, _, _ = _publish(addrs, step=0)
    srv = serve.ModelReplicaServer(
        _init_fn, _predict_fn, addrs, max_wait_ms=2.0, refresh_ms=10.0,
        role="srv_rej",
    )
    try:
        assert srv.wait_for_model(30.0)
        pool = serve.ServePool(
            [("127.0.0.1", srv.port)], role="rej_sv", op_timeout_s=5.0,
            deadline_s=30.0,
        )
        # Mismatched per-field leading dims: the replica's own validation
        # answers ERR.
        t0 = time.monotonic()
        with pytest.raises(serve.ServeRejectedError):
            pool.predict({
                "x": np.ones((2, D), np.float32),
                "y": np.ones((3, D), np.float32),
            })
        assert time.monotonic() - t0 < 5.0  # no deadline-long replay loop
        assert pool.ejections == 0  # the healthy replica was not benched
        step, out = pool.predict({"x": np.ones((2, D), np.float32)})
        assert step == 0 and out["output"].shape == (2, 4)
        pool.close()
    finally:
        srv.stop()
        group.close()
        ps_service.stop_server()


# ----------------------------------------------------------------------------
# LatencyRecorder (r10 metrics satellite)
# ----------------------------------------------------------------------------


def test_latency_recorder_percentiles_qps_and_naming():
    r = metrics.LatencyRecorder(capacity=64)
    assert r.percentile_scalars("serve") == {}  # empty: emit nothing
    # 100 ops over 10 seconds of (synthetic) wall time, 1..100 ms.
    for i in range(100):
        r.record((i + 1) / 1e3, at=i * 0.1)
    s = r.percentile_scalars("serve")
    # The ring keeps the newest 64 (37..100 ms): percentiles over THAT
    # window, qps over its timestamps (63 intervals across 6.3 s).
    assert set(s) == {
        "serve/latency_p50_ms", "serve/latency_p90_ms",
        "serve/latency_p99_ms", "serve/qps",
    }
    assert s["serve/latency_p50_ms"] == pytest.approx(68.5, abs=1.0)
    assert s["serve/latency_p99_ms"] <= 100.0
    assert s["serve/qps"] == pytest.approx(10.0, rel=0.01)
    assert len(r) == 64 and r.total == 100
    # One op: percentiles defined, qps degrades to 0 (no interval).
    r2 = metrics.LatencyRecorder()
    r2.record(0.005)
    s2 = r2.percentile_scalars("x")
    assert s2["x/latency_p50_ms"] == pytest.approx(5.0)
    assert s2["x/qps"] == 0.0


# ----------------------------------------------------------------------------
# SlotBatcher: the sequence-slot mode (r19)
# ----------------------------------------------------------------------------


def test_slot_batcher_advances_sessions_and_frees_slots():
    """Variable-length sessions share a fixed slot width: a finished
    session frees its slot for a QUEUED one mid-flight, and every
    session's emission stream is cursor-replayable."""

    def run_step(slots):
        out = []
        for t in slots:
            if t is None:
                continue
            st = t.state
            st["count"] = st.get("count", 0) + 1
            out.append((t, [st["count"]], st["count"] >= st["n"]))
        return out

    b = batcher_lib.SlotBatcher(run_step, slots=2, max_sessions=3)
    try:
        t1 = b.open({"n": 3})
        t2 = b.open({"n": 1})
        t3 = b.open({"n": 2})  # queued: both slots busy
        with pytest.raises(batcher_lib.Overloaded):
            b.open({"n": 1})  # admission bound
        deadline = time.monotonic() + 10
        while not (t1.done and t2.done and t3.done):
            assert time.monotonic() < deadline
            time.sleep(0.01)
        assert t1.snapshot() == ([1, 2, 3], True)
        assert t2.snapshot() == ([1], True)
        assert t3.snapshot() == ([1, 2], True)
        # Cursor addressing: a replayed poll re-reads, never re-drains.
        assert t3.snapshot(1) == ([2], True)
        assert t3.snapshot(1) == ([2], True)
        s = b.stats()
        assert s["sessions"] == 3 and s["overloads"] == 1
        assert s["slots_active"] == 0
    finally:
        b.stop()


def test_slot_batcher_step_error_fails_active_sessions_only():
    fail = threading.Event()

    def run_step(slots):
        if fail.is_set():
            raise ValueError("bad step")
        return [(t, ["x"], True) for t in slots if t is not None]

    b = batcher_lib.SlotBatcher(run_step, slots=1)
    try:
        ok = b.open({})
        deadline = time.monotonic() + 10
        while not ok.done:
            assert time.monotonic() < deadline
            time.sleep(0.01)
        assert ok.snapshot() == (["x"], True)
        fail.set()
        bad = b.open({})
        while not bad.done:
            assert time.monotonic() < deadline
            time.sleep(0.01)
        with pytest.raises(ValueError, match="bad step"):
            bad.snapshot()
        # The batcher survived: a later session succeeds again.
        fail.clear()
        again = b.open({})
        while not again.done:
            assert time.monotonic() < deadline
            time.sleep(0.01)
        assert again.snapshot() == (["x"], True)
        assert b.stats()["step_errors"] == 1
    finally:
        b.stop()


def _wait_done(tickets, timeout_s: float = 20.0):
    deadline = time.monotonic() + timeout_s
    while not all(t.done for t in tickets):
        assert time.monotonic() < deadline
        time.sleep(0.005)


def _scripted_step(step_s: float):
    """A ``run_step`` whose sessions feed ``state["feed"]`` inputs (no
    emission), then emit one item a step up to ``state["n"]``; every step
    sleeps ``step_s``."""

    def run_step(slots):
        time.sleep(step_s)
        out = []
        for t in slots:
            if t is None:
                continue
            st = t.state
            st["seen"] = st.get("seen", 0) + 1
            if st["seen"] <= st["feed"]:
                out.append((t, [], False))
            else:
                k = st["seen"] - st["feed"]
                out.append((t, [k], k >= st["n"]))
        return out

    return run_step


def test_slot_batcher_counts_feeding_seating_and_first_tokens():
    """The batcher's inside counters, against a script: ``fed`` is every
    occupied slot-step that emitted nothing, ``seated`` / ``first_tokens``
    count sessions, and the two sums of nanoseconds are at least the
    sleeps the script put between the stamps."""
    step_s = 0.02
    b = batcher_lib.SlotBatcher(_scripted_step(step_s), slots=2)
    try:
        t1 = b.open({"feed": 2, "n": 2})  # fed, fed, emit, emit
        t2 = b.open({"feed": 0, "n": 1})  # emit
        _wait_done([t1, t2])
        assert t1.snapshot() == ([1, 2], True) and t2.snapshot() == ([1], True)
        s = b.stats()
        assert s["fed"] == 2 and s["emitted"] == 3
        assert s["seated"] == 2 and s["first_tokens"] == 2
        # Occupied slot-steps split exactly into feeding and emitting.
        assert s["fed"] + s["emitted"] == 4 + 1
        # t1's first token came out of its third step, t2's of its first.
        assert s["first_token_ns"] >= (3 + 1) * step_s * 1e9
        assert s["seat_wait_ns"] == sum(
            t.seated_ns - t.opened_ns for t in (t1, t2)
        )
        # A session that never emits counts as fed only, and as no first
        # token, when it is cancelled.
        t3 = b.open({"feed": 10_000, "n": 1})
        deadline = time.monotonic() + 10
        while b.stats()["fed"] < 4:
            assert time.monotonic() < deadline
            time.sleep(0.005)
        t3.cancel()
        s = b.stats()
        assert s["seated"] == 3 and s["first_tokens"] == 2
    finally:
        b.stop()


def test_slot_batcher_seat_wait_is_the_queued_sessions_own():
    """Three sessions on ONE slot: the first is seated at once, the second
    waits out the first, the third waits out both — each stamp on its own
    ticket, the batcher's sum exactly their total."""
    step_s, n = 0.02, 3
    b = batcher_lib.SlotBatcher(_scripted_step(step_s), slots=1)
    try:
        ts = [b.open({"feed": 0, "n": n}) for _ in range(3)]
        _wait_done(ts)
        waits = [t.seated_ns - t.opened_ns for t in ts]
        one_session_ns = n * step_s * 1e9
        assert waits[0] < waits[1] < waits[2]
        assert waits[0] < one_session_ns  # never queued behind a session
        assert waits[1] >= one_session_ns
        assert waits[2] >= 2 * one_session_ns
        s = b.stats()
        assert s["seated"] == 3 and s["seat_wait_ns"] == sum(waits)
        assert s["fed"] == 0 and s["first_tokens"] == 3
        # First token = one step after seating, for each of them.
        assert s["first_token_ns"] >= 3 * step_s * 1e9
    finally:
        b.stop()


_WAIT_HISTS = ("decode/seat_wait_ms", "decode/ttft_ms", "decode/itl_ms")


def _hist_counts() -> dict:
    """Each of the batcher's three histograms: its lifetime count and what
    its buckets hold (the last cumulative count), which have to agree."""
    out = {}
    for name in _WAIT_HISTS:
        h = telemetry.REGISTRY.histogram(name)
        out[name] = h.count
        assert max(h.cumulative().values(), default=0) == out[name]
    return out


def _assert_hists_count_the_counters(before: dict, stats: dict) -> None:
    """The three histograms against the sums the batcher kept before them:
    one seat wait a session seated, one first token a session that emitted,
    one gap for every later item."""
    got = {k: v - before[k] for k, v in _hist_counts().items()}
    assert got == {
        "decode/seat_wait_ms": stats["seated"],
        "decode/ttft_ms": stats["first_tokens"],
        "decode/itl_ms": stats["emitted"] - stats["first_tokens"],
    }


@pytest.mark.parametrize("items", [1, 3])
def test_slot_batcher_observes_every_wait_once(items):
    """``decode/seat_wait_ms``, ``decode/ttft_ms`` and ``decode/itl_ms``
    count what ``seated``, ``first_tokens`` and ``emitted - first_tokens``
    count, whether a step hands a session one item or several (the items of
    one step are emitted at one instant: gaps of 0); a session that is fed
    and never emits observes a seat wait and nothing else."""

    def run_step(slots):
        out = []
        for t in slots:
            if t is None:
                continue
            st = t.state
            st["seen"] = st.get("seen", 0) + 1
            if st["seen"] <= st["feed"]:
                out.append((t, [], False))
            else:
                k = st["seen"] - st["feed"]
                out.append((t, [k] * items, k >= st["n"]))
        return out

    before = _hist_counts()
    zeros0 = telemetry.snapshot().get("decode/itl_ms/le/0.000976562", 0)  # the lowest edge
    b = batcher_lib.SlotBatcher(run_step, slots=2)
    try:
        ts = [b.open({"feed": f, "n": n}) for f, n in ((2, 4), (0, 1), (1, 3))]
        _wait_done(ts)
        mute = b.open({"feed": 10**9, "n": 1})
        deadline = time.monotonic() + 10
        while b.stats()["seated"] < 4:
            assert time.monotonic() < deadline
            time.sleep(0.005)
        mute.cancel()
        stats = b.stats()
    finally:
        b.stop()
    assert stats["seated"] == 4 and stats["first_tokens"] == 3
    assert stats["emitted"] == (4 + 1 + 3) * items
    _assert_hists_count_the_counters(before, stats)
    # Every item of a step after its first is 0 after the one before it.
    zeros = telemetry.snapshot().get("decode/itl_ms/le/0.000976562", 0) - zeros0
    assert zeros == (4 + 1 + 3) * (items - 1)
    # A session's stamps are its own: opened, seated, last emitted, in order.
    for t in ts:
        assert t.opened_ns <= t.seated_ns <= t.emitted_ns
    assert mute.emitted_ns is None


# ----------------------------------------------------------------------------
# Decode sessions over the wire (r19)
# ----------------------------------------------------------------------------


def _toy_decode_fns(vocab: int = 11):
    """next token = (token + 1) mod vocab — deterministic, stateless in
    the cache (which just counts steps), so expectations are exact."""
    import jax
    import jax.numpy as jnp

    def init_cache_fn(slots, max_len):
        return jnp.zeros((slots,), jnp.int32)

    def step_fn(params, cache, tokens, pos):
        return jax.nn.one_hot((tokens + 1) % vocab, vocab), cache + 1

    return init_cache_fn, step_fn


def _toy_cached_decode_fns(vocab: int = 11):
    """A toy whose cache matters, with a prefill: the cache keeps every
    token a slot was given at its position, and the next token is the
    position-weighted sum of what the row's mask lets it see — a prompt
    token missing from the cache, or cached at a wrong position or in a
    wrong slot, changes the stream.  ``_toy_cached_stream`` is the same
    in plain Python."""
    import jax
    import jax.numpy as jnp

    def init_cache_fn(slots, max_len):
        return jnp.zeros((slots, max_len), jnp.int32)

    def step_fn(params, cache, tokens, pos):
        t = jnp.arange(cache.shape[1])[None]
        cache = jnp.where(t == pos[:, None], tokens[:, None], cache)
        seen = jnp.where(t <= pos[:, None], cache * (t + 1), 0).sum(-1)
        return jax.nn.one_hot(seen % vocab, vocab), cache

    def prefill_fn(params, cache, tokens, slot, offset, n_valid):
        i = jnp.arange(tokens.shape[0])
        rows = jnp.where(i < n_valid, offset + i, cache.shape[1])
        return cache.at[slot, rows].set(tokens, mode="drop")

    return init_cache_fn, step_fn, prefill_fn


def _toy_cached_stream(prompt, n: int, vocab: int = 11) -> list:
    seq = [int(t) for t in prompt]
    for _ in range(n):
        seq.append(sum((i + 1) * t for i, t in enumerate(seq)) % vocab)
    return seq[len(prompt):]


def _pinned_decode_server(tmp_path, role, decode_fns=None, **kw):
    from distributed_tensorflow_examples_tpu.serve.registry import (
        ModelRegistry,
    )

    reg = ModelRegistry(str(tmp_path))
    if not reg.versions("default"):
        reg.publish("default", np.zeros(D * 4 + 4, np.float32), step=7)
    return serve.ModelReplicaServer(
        _init_fn, _predict_fn, [], registry_dir=str(tmp_path),
        model_version=1, role=role,
        decode_fns=decode_fns or _toy_decode_fns(),
        decode_slots=2, decode_max_len=32, **kw,
    )


@pytest.mark.parametrize("n", [2, 3])
def test_a_plain_pair_and_a_plain_triple_are_driven_as_before(tmp_path, n):
    """``decode_fns`` that is still the bare ``(init_cache_fn, step_fn[,
    prefill_fn])`` says nothing of ``live`` or of what it reads: the replica
    serves it through a four-argument step and counts all ``max_len`` rows a
    step; only the triple's prompt goes in by chunks."""
    fns = _toy_cached_decode_fns()[:n]
    assert type(fns) is tuple
    srv = _pinned_decode_server(tmp_path, f"plain{n}", decode_fns=fns)
    try:
        eng = srv._engine
        assert not eng._wants_live and (eng._prefill_jit is not None) == (n == 3)
        c = serve.ServeClient("127.0.0.1", srv.port, role="plain_sv")
        prompt = np.array([3, 4, 5, 6], np.int32)
        assert c.generate(prompt, 5).tolist() == _toy_cached_stream(prompt, 5)
        c.close()
        stats = eng.stats()
    finally:
        srv.stop()
    assert stats["cache_rows_read"] == stats["steps"] * eng.max_len
    assert stats["prefill_tokens"] == (3 if n == 3 else 0)
    assert stats["steps"] == (5 if n == 3 else 8)


def test_decode_stream_end_to_end_and_session_errors(tmp_path):
    srv = _pinned_decode_server(tmp_path, "dec0")
    try:
        c = serve.ServeClient("127.0.0.1", srv.port, role="dec_sv")
        out = c.generate(np.array([3, 4, 5], np.int32), 5)
        assert out.tolist() == [6, 7, 8, 9, 10]
        # Stamps ride the decode wire too.
        assert c.last_model_version == 1
        # Cursor replay at the op level: the same poll twice returns the
        # same suffix (a reconnect replay cannot double-drain).
        sid = c.decode_open(np.array([1], np.int32), 3)
        deadline = time.monotonic() + 10
        while True:
            toks, done, step = c.decode_next(sid, cursor=0)
            if done:
                break
            assert time.monotonic() < deadline
            time.sleep(0.01)
        assert toks.tolist() == [2, 3, 4] and step == 7
        toks2, done2, _ = c.decode_next(sid, cursor=1)
        assert toks2.tolist() == [3, 4] and done2
        c.decode_close(sid)
        c.decode_close(sid)  # idempotent
        # Unknown session: the typed error, immediately.
        with pytest.raises(serve.ServeSessionError):
            c.decode_next(99999)
        # Bad budget: rejected, not a hang.
        with pytest.raises(serve.ServeRejectedError):
            c.decode_open(np.array([1], np.int32), 10_000)
        c.close()
    finally:
        srv.stop()


def test_decode_concurrent_sessions_byte_identical_to_solo(tmp_path):
    """The sequence-slot contract (the decode analog of the padded-apply
    r10 contract): a session's token stream is identical whether it ran
    alone or coalesced with concurrent sessions of OTHER lengths."""
    srv = _pinned_decode_server(tmp_path, "dec1")
    try:
        solo = serve.ServeClient("127.0.0.1", srv.port, role="solo_sv")
        prompt = np.array([2, 9], np.int32)
        ref = solo.generate(prompt, 6)
        prompts = [prompt, np.array([5], np.int32),
                   np.array([1, 2, 3, 4], np.int32), np.array([8], np.int32)]
        outs: list = [None] * 4

        def body(i):
            ci = serve.ServeClient("127.0.0.1", srv.port, role=f"dc{i}_sv")
            outs[i] = ci.generate(prompts[i], 6)
            ci.close()

        ts = [threading.Thread(target=body, args=(i,)) for i in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=30)
        assert all(o is not None for o in outs)
        assert np.array_equal(outs[0], ref)
        # Sessions genuinely interleaved through 2 slots.
        st = solo.stats()
        assert st["decode_sessions"] >= 5 and st["decode_steps"] > 0
        solo.close()
    finally:
        srv.stop()


# ----------------------------------------------------------------------------
# The held poll (PR 44): a DECODE_NEXT that finds nothing waits for a token
# ----------------------------------------------------------------------------


@pytest.mark.parametrize("how", [
    "held_already", "emitted", "below_cursor", "finished", "failed",
    "cancelled", "replaced", "forgotten", "released",
])
def test_a_tickets_waiter_is_called_once_when_its_cursor_has_something(how):
    t = batcher_lib.StreamTicket(None)
    calls: list = []
    first = lambda: calls.append("first")  # noqa: E731
    if how == "held_already":
        t._emit([5])
        t.when_ready(0, first)
        assert calls == ["first"]  # at once, from the registering thread
    else:
        t.when_ready(1 if how == "below_cursor" else 0, first)
        assert calls == []
    if how in ("emitted", "below_cursor"):
        t._emit([5])
        # An emission BELOW the waiter's cursor is not what it waits for.
        assert calls == ([] if how == "below_cursor" else ["first"])
        t._emit([6])
        assert calls == ["first"]
    elif how == "finished":
        t._finish()
    elif how == "failed":
        t._finish(error=RuntimeError("boom"))
    elif how == "cancelled":
        t.cancel()
    elif how == "replaced":
        # One waiter a ticket: the second answers the first, then waits.
        t.when_ready(0, lambda: calls.append("second"))
        assert calls == ["first"]
        t._emit([5])
        assert calls == ["first", "second"]
    elif how == "forgotten":
        assert t.forget(first) and not t.forget(first)
        t._emit([5])
        assert calls == []
    elif how == "released":
        t.release()
    if how != "forgotten":
        assert calls[0] == "first" and not t.forget(first)
    n = len(calls)
    t._emit([7])
    t._finish()
    t.release()
    assert len(calls) == n  # a waiter is called once


def _gated_decode_server(tmp_path, role, monkeypatch, hold_s=30.0, **kw):
    """A decode replica whose step thread makes a call into the engine only
    while ``gate`` is set (and fails the step while ``boom`` is): tokens
    exist when the test says so.  The step is compiled before the gate is
    there, and the hold is long unless the test is about its end."""
    from distributed_tensorflow_examples_tpu.serve import model_server

    monkeypatch.setattr(model_server, "DECODE_HOLD_S", hold_s)
    srv = _pinned_decode_server(tmp_path, role, **kw)
    warm = serve.ServeClient("127.0.0.1", srv.port, role=f"{role}_warm")
    assert warm.generate(np.array([1], np.int32), 2).tolist() == [2, 3]
    warm.close()
    gate, boom = threading.Event(), threading.Event()
    run = srv._engine.batcher._run

    def gated(slots):
        gate.wait(60)
        if boom.is_set():
            raise RuntimeError("boom")
        return run(slots)

    srv._engine.batcher._run = gated
    return srv, gate, boom


def _poll_in_thread(port: int, role: str, sid: int, cursor: int = 0):
    """``decode_next`` from a thread of its own, on a connection of its own:
    ``(thread, client, out)``, ``out`` filled when the answer has come."""
    c = serve.ServeClient("127.0.0.1", port, role=role)
    out: dict = {}

    def body():
        try:
            out["answer"] = c.decode_next(sid, cursor=cursor)
        except Exception as e:  # noqa: BLE001 — the test looks at it
            out["error"] = e
        out["t"] = time.monotonic()

    th = threading.Thread(target=body, daemon=True)
    th.start()
    return th, c, out


def _polls_since(client, base: dict) -> tuple:
    """``(polls, held, expired)`` the replica has counted since ``base``."""
    st = client.stats()
    return tuple(
        st[k] - base[k]
        for k in ("decode_polls", "decode_polls_held", "decode_polls_expired"))


def _wait_held(client, base: dict, want: int, timeout_s: float = 20.0) -> None:
    t_end = time.monotonic() + timeout_s
    while _polls_since(client, base)[1] < want:
        assert time.monotonic() < t_end, (_polls_since(client, base), want)
        time.sleep(0.01)


def test_a_poll_sent_before_the_token_exists_is_answered_when_it_is_emitted(
    tmp_path, monkeypatch,
):
    srv, gate, _ = _gated_decode_server(tmp_path, "hp0", monkeypatch)
    try:
        c = serve.ServeClient("127.0.0.1", srv.port, role="hp0_sv")
        base = c.stats()
        sid = c.decode_open(np.array([3], np.int32), 4)
        th, pc, out = _poll_in_thread(srv.port, "hp0_poll", sid)
        _wait_held(c, base, 1)
        time.sleep(0.05)
        assert th.is_alive() and not out  # held: no empty answer came
        t_open = time.monotonic()
        gate.set()
        th.join(timeout=20)
        toks, done, step = out["answer"]
        # The token, with the frame a poll always got, and long before the
        # hold (30 s here) would have run out.
        assert toks.tolist()[0] == 4 and step == 7 and pc.last_model_version == 1
        assert out["t"] - t_open < 10.0
        assert _polls_since(c, base) == (1, 1, 0)
        # The rest of the stream, by cursor, is what it always was.
        rest = toks.tolist()
        while not done:
            got, done, _ = pc.decode_next(sid, cursor=len(rest))
            rest += got.tolist()
        assert rest == [4, 5, 6, 7]
        pc.close()
        c.close()
    finally:
        gate.set()
        srv.stop()


def test_a_held_poll_past_the_limit_is_answered_empty_and_not_done(
    tmp_path, monkeypatch,
):
    from distributed_tensorflow_examples_tpu.serve import model_server

    # The limit as shipped: a tenth of a second, far under every client's
    # op timeout and under the idle sweep.
    assert model_server.DECODE_HOLD_S == 0.1
    srv, gate, _ = _gated_decode_server(tmp_path, "hp1", monkeypatch, hold_s=0.1)
    try:
        c = serve.ServeClient("127.0.0.1", srv.port, role="hp1_sv")
        base = c.stats()
        sid = c.decode_open(np.array([3], np.int32), 4)
        t0 = time.monotonic()
        toks, done, step = c.decode_next(sid)
        took = time.monotonic() - t0
        assert toks.tolist() == [] and not done and step == 7
        assert 0.09 <= took < 5.0
        assert _polls_since(c, base) == (1, 1, 1)
        # The session lives on: the next poll is held in its turn, and
        # answered with the token when there is one.
        th, pc, out = _poll_in_thread(srv.port, "hp1_poll", sid)
        gate.set()
        th.join(timeout=20)
        assert out["answer"][0].tolist()[0] == 4
        pc.close()
        c.close()
    finally:
        gate.set()
        srv.stop()


@pytest.mark.parametrize(
    "cause", ["decode_close", "idle_sweep", "failed_step", "stop", "second_poll"])
def test_a_held_poll_is_answered_once_by_whatever_ends_its_wait(
    tmp_path, monkeypatch, cause,
):
    kw = {"session_idle_s": 0.4} if cause == "idle_sweep" else {}
    srv, gate, boom = _gated_decode_server(tmp_path, "hp2", monkeypatch, **kw)
    stopped = False
    try:
        c = serve.ServeClient("127.0.0.1", srv.port, role="hp2_sv")
        base = c.stats()
        sid = c.decode_open(np.array([3], np.int32), 4)
        th, pc, out = _poll_in_thread(srv.port, "hp2_poll", sid)
        _wait_held(c, base, 1)
        assert th.is_alive()
        if cause == "decode_close":
            c.decode_close(sid)
        elif cause == "failed_step":
            boom.set()
            gate.set()
        elif cause == "stop":
            c.close()
            # The step thread may go on once the poll is answered: ``stop``
            # joins it, and the test's gate is not what is being timed.
            threading.Thread(
                target=lambda: (th.join(20), gate.set()), daemon=True).start()
            t0 = time.monotonic()
            srv.stop()
            stopped = True
            # Neither the hold (30 s) nor the core's drain (5 s) was waited out.
            assert time.monotonic() - t0 < 4.0
        elif cause == "second_poll":
            th2, pc2, out2 = _poll_in_thread(srv.port, "hp2_poll2", sid)
        th.join(timeout=20)
        assert not th.is_alive()
        if cause in ("decode_close", "idle_sweep"):
            toks, done, _ = out["answer"]
            assert toks.tolist() == [] and done  # a cancelled session's end
        elif cause == "failed_step":
            assert isinstance(out["error"], serve.ServeRejectedError)
            with pytest.raises(serve.ServeSessionError):
                pc.decode_next(sid)  # the session went with its step
        else:
            toks, done, step = out["answer"]
            assert toks.tolist() == [] and not done and step == 7
        if cause == "second_poll":
            # The second poll holds on; the token answers it.
            _wait_held(c, base, 2)
            assert th2.is_alive()
            gate.set()
            th2.join(timeout=20)
            assert out2["answer"][0].tolist()[0] == 4
            pc2.close()
        if not stopped:
            # Once: a second frame for the poll would be read as the answer
            # to the connection's next request.
            assert _polls_since(pc, base)[2] == 0
            c.close()
        pc.close()
    finally:
        gate.set()
        if not stopped:
            srv.stop()


def test_more_polls_are_held_than_the_core_has_workers_and_it_still_answers(
    tmp_path, monkeypatch,
):
    """Twelve sessions' first polls wait on a core of eight workers: none
    holds a worker, so STATS (the control worker's) and a thirteenth
    session's open, poll and close (the pool's) are answered at once, and
    every ``generate`` returns its stream as it always did."""
    srv, gate, _ = _gated_decode_server(tmp_path, "hp3", monkeypatch)
    try:
        base = srv.stats()
        assert base["core"]["worker_threads"] == 8
        prompts = [np.array([i % 11], np.int32) for i in range(12)]
        outs: list = [None] * 12

        def body(i):
            ci = serve.ServeClient("127.0.0.1", srv.port, role=f"hp3_{i}")
            outs[i] = ci.generate(prompts[i], 3)
            ci.close()

        ts = [threading.Thread(target=body, args=(i,), daemon=True)
              for i in range(12)]
        for t in ts:
            t.start()
        c = serve.ServeClient("127.0.0.1", srv.port, role="hp3_sv")
        _wait_held(c, base, 12)
        t0 = time.monotonic()
        st = c.stats()
        sid = c.decode_open(np.array([1], np.int32), 1)
        c.decode_close(sid)
        with pytest.raises(serve.ServeSessionError):
            c.decode_next(sid)
        assert time.monotonic() - t0 < 2.0
        assert st["decode_polls_held"] - base["decode_polls_held"] == 12
        assert st["decode_polls_expired"] == base["decode_polls_expired"]
        assert st["core"]["dispatch_depth"] == 0
        gate.set()
        for t in ts:
            t.join(timeout=30)
        for i, o in enumerate(outs):
            assert o.tolist() == [(i + k) % 11 for k in (1, 2, 3)]
        st = c.stats()
        assert 0 <= st["decode_polls_expired"] <= st["decode_polls_held"] \
            <= st["decode_polls"]
        c.close()
    finally:
        gate.set()
        srv.stop()


def test_held_poll_share_names_a_reader_and_counters_that_exist(
    tmp_path, monkeypatch,
):
    """The metric file this PR adds: its reader is there, ``server.stats()``
    carries the two counters it divides, ``generate`` over polls that wait
    gives the stream a plain loop gives - and the reader finds nothing,
    without raising, on the parent's replica, which counts no polls."""
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks.harness import manifest

    spec = manifest.layer_metric("held_poll_share")
    read = manifest.reader(spec["reader"])
    assert spec["args"] == {"num": "decode_polls_held", "den": "decode_polls"}
    entry = {p["name"]: p for p in manifest.benchmark()["per_layer"]}[
        "held_poll_share"]
    itl = {e["name"]: e for e in manifest.benchmark()["end_to_end"]}["itl_p95_ms"]
    assert entry["workloads"] == itl["workloads"]
    srv = _pinned_decode_server(
        tmp_path, "hp4", decode_fns=_toy_cached_decode_fns())
    run = srv._engine.batcher._run

    def slow(slots):  # tokens come slower than ``generate`` polls
        time.sleep(0.02)
        return run(slots)

    srv._engine.batcher._run = slow
    try:
        c = serve.ServeClient("127.0.0.1", srv.port, role="hp4_sv")
        start = c.stats()
        prompt = np.array([2, 9, 4], np.int32)
        assert c.generate(prompt, 6).tolist() == _toy_cached_stream(prompt, 6)
        end = c.stats()
        c.close()
    finally:
        srv.stop()
    polls = end["decode_polls"] - start["decode_polls"]
    held = end["decode_polls_held"] - start["decode_polls_held"]
    assert 1 <= held <= polls
    assert end["decode_polls_expired"] <= end["decode_polls_held"]
    share = read({"counters": {"start": start, "end": end}}, **spec["args"])
    assert share == pytest.approx(100 * held / polls)
    for stats in (start, end):
        del stats["decode_polls_held"]  # the parent's replica
    assert read({"counters": {"start": start, "end": end}}, **spec["args"]) is None


def test_predict_only_replica_answers_no_decoder(tmp_path):
    from distributed_tensorflow_examples_tpu.serve.registry import (
        ModelRegistry,
    )

    ModelRegistry(str(tmp_path)).publish(
        "default", np.zeros(D * 4 + 4, np.float32), step=1
    )
    srv = serve.ModelReplicaServer(
        _init_fn, _predict_fn, [], registry_dir=str(tmp_path),
        model_version=1, role="nodec",
    )
    try:
        c = serve.ServeClient("127.0.0.1", srv.port, role="nd_sv")
        with pytest.raises(serve.ServeRejectedError, match="no decode path"):
            c.decode_open(np.array([1], np.int32), 2)
        c.close()
    finally:
        srv.stop()


def test_hot_tracking_replica_stamps_version_zero():
    """A hot-tracking replica is version 0 on every stamp — the pre-r19
    wire shape, so mixed pools keep working."""
    port = ps_service.start_server(0)
    addrs = [("127.0.0.1", port)]
    group, _, _ = _publish(addrs, step=0)
    srv = serve.ModelReplicaServer(
        _init_fn, _predict_fn, addrs, max_wait_ms=2.0, refresh_ms=10.0,
        role="srv_v0",
    )
    try:
        assert srv.wait_for_model(30.0)
        c = serve.ServeClient("127.0.0.1", srv.port, role="v0_sv")
        assert c.server_model_version == 0
        c.predict({"x": np.ones((1, D), np.float32)})
        assert c.last_model_version == 0
        st = c.stats()
        assert st["model_version"] == 0 and st["pinned"] is False
        c.close()
    finally:
        srv.stop()
        group.close()
        ps_service.stop_server()


# ----------------------------------------------------------------------------
# The step thread's spans and the compile counter (PR 24)
# ----------------------------------------------------------------------------

_STEP_SPANS = (
    "decode/fill", "decode/prepare", "decode/dispatch", "decode/fetch",
    "decode/select", "decode/emit",
)


def _step_args(slots: int, live: bool = False) -> tuple:
    """What the engine's compiled step takes after ``(params, cache)``:
    ``prev``, ``tokens``, ``from_host``, ``pos`` and, for a model that
    asks, ``live``."""
    z = np.zeros(slots, np.int32)
    return (z, z, np.ones(slots, bool), z) + ((np.ones(slots, bool),) * live)


@pytest.mark.parametrize("prefill", [False, True])
def test_decode_spans_reach_the_profiler_trace_as_leaves(tmp_path, prefill):
    """A ``jax.profiler`` trace of a live replica holds all seven span
    names on the step thread's ``python`` line — where the benchmark's
    ``trace.load`` collects host events — and no two of them overlap: the
    spans are leaves that follow each other.  ``decode/prefill`` (the wait
    for a chunk) and ``decode/chunk_launch`` (its dispatch) are there only
    where a chunk ran: an adapter of two functions feeds its prompts
    through the step and never enters them."""
    import glob

    import jax
    from jax.profiler import ProfileData

    srv = _pinned_decode_server(
        tmp_path / "reg", "trc0",
        decode_fns=_toy_cached_decode_fns() if prefill else None,
    )
    want = _toy_cached_stream([3, 4, 5], 5) if prefill else [6, 7, 8, 9, 10]
    try:
        c = serve.ServeClient("127.0.0.1", srv.port, role="trc_sv")
        c.generate(np.array([1, 2], np.int32), 2)  # compile outside the trace
        before = c.stats()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(tmp_path / "trace"), profiler_options=opts)
        try:
            time.sleep(0.25)  # idle: the step thread parks
            out = c.generate(np.array([3, 4, 5], np.int32), 5)
        finally:
            jax.profiler.stop_trace()
        assert out.tolist() == want
        after = c.stats()
        c.close()
    finally:
        srv.stop()
    (path,) = glob.glob(
        str(tmp_path / "trace" / "plugins" / "profile" / "*" / "*.xplane.pb")
    )
    lines = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = sorted(
                (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                for ev in line.events if ev.name.startswith("decode/")
            )
            if evs:
                assert line.name.startswith("python")
                lines.append(evs)
    assert len(lines) == 1, "one step thread, one line"
    (evs,) = lines
    assert {name for _s, _e, name in evs} == {
        *_STEP_SPANS, "decode/park",
        *(["decode/prefill", "decode/chunk_launch"] if prefill else []),
    }
    for (_s0, e0, n0), (s1, _e1, n1) in zip(evs, evs[1:]):
        assert e0 <= s1, f"{n0} overlaps {n1}"
    # The same intervals reached the registry: seven steps of the traced
    # request (five where one chunk cached the prompt's first two tokens),
    # each span entered once a step.
    steps = after["decode_steps"] - before["decode_steps"]
    assert steps == (5 if prefill else 7)
    chunks = (
        after["registry"]["decode/prefill/n"]
        - before["registry"]["decode/prefill/n"]
    )
    assert chunks == (1 if prefill else 0)
    assert after["decode_prefill_chunks"] - before["decode_prefill_chunks"] == chunks
    assert (
        after["registry"]["decode/chunk_launch/n"]
        - before["registry"]["decode/chunk_launch/n"]
    ) == chunks
    for name in _STEP_SPANS[1:]:
        assert (
            after["registry"][f"{name}/n"] - before["registry"][f"{name}/n"]
            == steps
        )
        assert after["registry"][f"{name}/ns"] > before["registry"][f"{name}/ns"]
    for key in ("fed", "seated", "seat_wait_ns", "first_tokens", "first_token_ns"):
        assert f"decode_{key}" in after
    # A 3-token prompt: two steps feed it, or none once it is prefilled.
    assert after["decode_fed"] - before["decode_fed"] == (0 if prefill else 2)


def test_served_decode_program_is_named_step_fn():
    """The benchmark finds the decode step in a device trace by its
    program's name (``decode_step_ms``'s ``module`` is ``jit_step_fn``):
    the function the engine jits must keep the name ``step_fn``."""
    import jax

    from distributed_tensorflow_examples_tpu import models
    from distributed_tensorflow_examples_tpu.serve import model_server

    cfg = models.transformer.Config(
        vocab_size=32, dim=16, n_layers=1, n_heads=2, max_seq_len=16,
    )
    fns = models.transformer.serve_decode_fns(cfg)
    assert fns.step.__name__ == "step_fn"
    engine = model_server._DecodeEngine(
        lambda: None, fns, slots=2, max_len=16, max_sessions=4,
    )
    try:
        params = jax.eval_shape(
            lambda k: models.transformer.init(cfg, k), jax.random.key(0)
        )
        lowered = engine._step_jit.lower(params, engine._cache, *_step_args(2))
        assert "module @jit_step_fn" in lowered.as_text()
        # The chunk program must NOT be found under that name: the step's
        # metrics would count its launches as steps.
        chunk = engine._prefill_jit.lower(
            params, engine._cache, np.zeros(16, np.int32), np.int32(0),
            np.int32(0), np.int32(0),
        ).as_text()
        assert "module @jit_prefill_fn" in chunk and "step_fn" not in chunk
    finally:
        engine.stop()


# ----------------------------------------------------------------------------
# Prefill: a seated prompt enters the cache a chunk per forward pass (PR 25)
# ----------------------------------------------------------------------------


@pytest.mark.parametrize(
    "P,chunks,floor,width",
    [
        # One width (the floor is over half the chunk): every chunk is 4 wide.
        (1, 0, 128, 0), (2, 1, 128, 4), (5, 1, 128, 4), (6, 2, 128, 8),
        (14, 4, 128, 16),
        # Widths 1 / 2 / 4: the tokens owed sit on, one under and one over
        # each edge; whole chunks of 4, then the narrowest that holds the rest.
        (2, 1, 1, 1), (3, 1, 1, 2), (4, 1, 1, 4), (5, 1, 1, 4), (6, 2, 1, 4 + 1),
        (7, 2, 1, 4 + 2), (8, 2, 1, 4 + 4), (14, 4, 1, 4 + 4 + 4 + 1),
    ],
)
def test_prefilled_prompt_costs_its_chunks_then_one_step(
    tmp_path, monkeypatch, P, chunks, floor, width,
):
    """A ``P``-token prompt costs ``ceil((P - 1) / C)`` chunks — one an
    iteration, each followed by a decode step in which the slot's row is
    inert — and the step after the last chunk emits its first token.  Each
    chunk is dispatched at the narrowest width that holds its tokens
    (``decode_prefill_width``), every width was compiled before the first
    answer, and the tokens are the one-width engine's."""
    from distributed_tensorflow_examples_tpu.serve import model_server

    monkeypatch.setattr(model_server, "PREFILL_CHUNK", 4)
    monkeypatch.setattr(model_server, "PREFILL_FLOOR", floor)
    srv = _pinned_decode_server(
        tmp_path, "pf0", decode_fns=_toy_cached_decode_fns()
    )
    try:
        c = serve.ServeClient("127.0.0.1", srv.port, role="pf_sv")
        c.generate(np.array([1], np.int32), 1)  # compile both programs
        before = c.stats()
        prompt = (np.arange(P, dtype=np.int32) * 3 + 2) % 11
        out = c.generate(prompt, 3)
        after = c.stats()
        c.close()
    finally:
        srv.stop()
    assert out.tolist() == _toy_cached_stream(prompt, 3)
    d = lambda k: after[k] - before[k]
    assert d("decode_prefill_chunks") == chunks
    assert d("decode_prefill_tokens") == P - 1
    assert d("decode_prefill_width") == width >= P - 1
    assert after["decode_prefill_width"] >= after["decode_prefill_tokens"]
    # All but the last chunk's iteration leave the row inert; the three
    # tokens then take three steps.
    assert d("decode_fed") == max(chunks - 1, 0)
    assert d("decode_steps") == max(chunks - 1, 0) + 3
    assert d("decode_emitted") == 3 and d("decode_first_tokens") == 1
    r = lambda k: after["registry"][k] - before["registry"][k]
    assert r("decode/prefill/n") == chunks
    assert r("decode/dispatch/n") == d("decode_steps")
    # Whatever the prompt's length, its programs were there.
    assert r("jax/compiles") == 0


def test_prefill_is_one_chunk_a_step_and_leaves_decoding_sessions_alone(
    monkeypatch,
):
    """Several long prompts seated at once: never two chunks between two
    decode steps, the longest-seated session's chunks first; a session
    that decodes meanwhile gets the tokens it gets alone, and so does
    each prefilled one."""
    from distributed_tensorflow_examples_tpu.serve import model_server

    monkeypatch.setattr(model_server, "PREFILL_CHUNK", 4)
    gate = threading.Event()

    def model():
        gate.wait(10)
        return 0, None

    eng = model_server._DecodeEngine(
        model, DecodeFns(*_toy_cached_decode_fns()), slots=4, max_len=32,
        max_sessions=8,
    )
    log: list = []
    step_jit, prefill_jit = eng._step_jit, eng._prefill_jit

    def logged_step(*a):
        log.append("step")
        return step_jit(*a)

    def logged_chunk(params, cache, tokens, slot, offset, n_valid):
        if n_valid:  # not the chunk of no token that compiles the program
            log.append((int(slot), int(offset), int(n_valid)))
        return prefill_jit(params, cache, tokens, slot, offset, n_valid)

    eng._step_jit, eng._prefill_jit = logged_step, logged_chunk
    try:
        # The first session is seated and the step thread held at the
        # model; the other three queue up and are seated together.
        prompts = [
            np.array([7], np.int32),
            (np.arange(14, dtype=np.int32) * 5 + 1) % 11,
            (np.arange(10, dtype=np.int32) * 2 + 3) % 11,
            (np.arange(6, dtype=np.int32) * 7 + 4) % 11,
        ]
        tickets = [eng.open(prompts[0], 12)]
        time.sleep(0.1)
        tickets += [eng.open(p, 4) for p in prompts[1:]]
        gate.set()
        deadline = time.monotonic() + 30
        for t in tickets:
            while not t.done:
                assert time.monotonic() < deadline
                t.wait(0.5)
        outs = [t.snapshot(0)[0] for t in tickets]
        stats = eng.stats()
    finally:
        eng.stop()
    for p, o, n in zip(prompts, outs, (12, 4, 4, 4)):
        assert o == _toy_cached_stream(p, n), len(p)
    chunks = [e for e in log if e != "step"]
    # Slots 1-3 in the order seated; 13, 9 and 5 tokens in chunks of 4.
    assert chunks == [
        (1, 0, 4), (1, 4, 4), (1, 8, 4), (1, 12, 1),
        (2, 0, 4), (2, 4, 4), (2, 8, 1),
        (3, 0, 4), (3, 4, 1),
    ]
    assert all(
        a == "step" or b == "step" for a, b in zip(log, log[1:])
    ), "two chunks between two steps"
    assert stats["prefill_chunks"] == 9 and stats["prefill_tokens"] == 27


def test_a_failed_chunk_leaves_the_engine_a_cache():
    """The cache is donated to the chunk; a chunk that raises fails the
    active sessions, as any step does, and the next session is served."""
    import jax.numpy as jnp

    from distributed_tensorflow_examples_tpu.serve import model_server

    init_cache_fn, step_fn, prefill_fn = _toy_cached_decode_fns()
    calls = []

    def flaky_prefill_fn(params, cache, tokens, slot, offset, n_valid):
        calls.append(1)  # runs when the program is traced
        if len(calls) == 1:
            raise FloatingPointError("chunk failed")
        return prefill_fn(params, cache, tokens, slot, offset, n_valid)

    eng = model_server._DecodeEngine(
        lambda: (0, None), DecodeFns(init_cache_fn, step_fn, flaky_prefill_fn),
        slots=2, max_len=16, max_sessions=4,
    )
    try:
        t = eng.open(np.array([1, 2, 3], np.int32), 2)
        while not t.done:
            t.wait(5)
        with pytest.raises(FloatingPointError):
            t.snapshot(0)
        assert isinstance(eng._cache, jnp.ndarray) and not eng._cache.is_deleted()
        t = eng.open(np.array([1, 2, 3], np.int32), 2)
        while not t.done:
            t.wait(5)
        assert t.snapshot(0)[0] == _toy_cached_stream([1, 2, 3], 2)
        assert eng.stats()["step_errors"] == 1
    finally:
        eng.stop()


# ----------------------------------------------------------------------------
# The step is donated its cache and reads no further than its deepest row
# ----------------------------------------------------------------------------


def test_a_failed_step_leaves_the_engine_a_cache():
    """The cache is donated to the step as it is to the chunk; a step that
    raises fails the active sessions and the next session is served from a
    live cache."""
    import jax.numpy as jnp

    from distributed_tensorflow_examples_tpu.serve import model_server

    init_cache_fn, step_fn, prefill_fn = _toy_cached_decode_fns()
    calls = []

    def flaky_step_fn(params, cache, tokens, pos):
        calls.append(1)  # runs when the program is traced
        if len(calls) == 1:
            raise FloatingPointError("step failed")
        return step_fn(params, cache, tokens, pos)

    eng = model_server._DecodeEngine(
        lambda: (0, None), DecodeFns(init_cache_fn, flaky_step_fn, prefill_fn),
        slots=2, max_len=16, max_sessions=4,
    )
    try:
        first = eng._cache
        t = eng.open(np.array([1, 2, 3], np.int32), 2)
        while not t.done:
            t.wait(5)
        with pytest.raises(FloatingPointError):
            t.snapshot(0)
        assert isinstance(eng._cache, jnp.ndarray) and not eng._cache.is_deleted()
        assert eng._cache is not first
        t = eng.open(np.array([1, 2, 3], np.int32), 2)
        while not t.done:
            t.wait(5)
        assert t.snapshot(0)[0] == _toy_cached_stream([1, 2, 3], 2)
        assert eng.stats()["step_errors"] == 1
    finally:
        eng.stop()


def _tiny_transformer_engine(max_len: int = 16, slots: int = 2):
    import jax

    from distributed_tensorflow_examples_tpu import models
    from distributed_tensorflow_examples_tpu.serve import model_server

    cfg = models.transformer.Config(
        vocab_size=32, dim=16, n_layers=2, n_heads=2, max_seq_len=max_len,
    )
    params = models.transformer.init(cfg, jax.random.key(0))
    return model_server._DecodeEngine(
        lambda: (0, params), models.transformer.serve_decode_fns(cfg),
        slots=slots, max_len=max_len, max_sessions=4,
    ), params


def test_the_step_is_donated_the_cache_it_returns():
    """Donation is real: the lowered step aliases every leaf of the cache
    to the output that replaces it, and after a step the cache the engine
    held before is gone (a second 3.2 GB array in the served cell, were
    it not)."""
    eng, params = _tiny_transformer_engine()
    try:
        lowered = eng._step_jit.lower(params, eng._cache, *_step_args(2))
        assert lowered.as_text().count("tf.aliasing_output") == 2 * 2
        assert lowered.compile().memory_analysis().alias_size_in_bytes == eng.state_bytes
        before = eng._cache["block_0"]["k"]
        _run_sessions(eng, [[1, 2, 3]], [2])
        assert before.is_deleted() and not eng._cache["block_0"]["k"].is_deleted()
    finally:
        eng.stop()


def test_a_freed_slot_is_stepped_at_position_zero():
    """A slot whose session ended stands at token 0, position 0 in every
    later step, not where that session left it: the step's read is held to
    the sessions that are seated."""
    from distributed_tensorflow_examples_tpu.serve import model_server

    gate = threading.Event()

    def model():
        gate.wait(10)
        return 0, None

    eng = model_server._DecodeEngine(
        model, DecodeFns(*_toy_cached_decode_fns()[:2]), slots=2, max_len=32,
        max_sessions=4,
    )
    log: list = []
    step_jit = eng._step_jit

    def logged_step(params, cache, prev, tokens, from_host, pos):
        # What the row is fed: the host's token, or (not read here) the
        # selection of the step before.
        log.append((np.where(from_host, tokens, -1), np.asarray(pos).copy()))
        return step_jit(params, cache, prev, tokens, from_host, pos)

    eng._step_jit = logged_step
    try:
        long = eng.open(np.array([1, 2, 3], np.int32), 12)  # holds the thread
        time.sleep(0.1)
        short = eng.open(np.array([4, 5, 6, 7], np.int32), 2)
        gate.set()
        for t in (long, short):
            while not t.done:
                t.wait(5)
        assert short.snapshot(0)[0] == _toy_cached_stream([4, 5, 6, 7], 2)
        assert long.snapshot(0)[0] == _toy_cached_stream([1, 2, 3], 12)
    finally:
        eng.stop()
    theirs = [int(p[1]) for _t, p in log]
    # The short session's five steps at positions 0-4, then the freed slot.
    last = max(i for i, p in enumerate(theirs) if p)
    assert theirs[last] == 4 and len(theirs) > last + 3
    assert all(int(t[1]) == 0 and int(p[1]) == 0 for t, p in log[last + 1:])


@pytest.mark.parametrize("bounded", [True, False])
def test_cache_rows_read_counts_what_a_step_reads(monkeypatch, bounded):
    """``cache_rows_read`` grows a step by the positions of each slot the
    step's attention read: the model's own bound where its ``step_fn``
    gives one (whole blocks up to the deepest row), ``max_len`` for a model
    that does not say."""
    from distributed_tensorflow_examples_tpu import models
    from distributed_tensorflow_examples_tpu.serve import model_server

    if bounded:
        monkeypatch.setattr(models.transformer, "DECODE_BLOCK", 4)
        eng, _params = _tiny_transformer_engine(max_len=14)
    else:
        eng = model_server._DecodeEngine(
            lambda: (0, None), DecodeFns(*_toy_cached_decode_fns()), slots=2,
            max_len=14, max_sessions=4,
        )
    try:
        # One session alone: a one-token prompt, then positions 0-12, the
        # last in a block that the cache cuts short.
        _run_sessions(eng, [[3]], [13])
        stats = eng.stats()
    finally:
        eng.stop()
    assert stats["steps"] == 13 and stats["max_len"] == 14
    assert stats["cache_rows_read"] == (
        4 * 4 + 4 * 8 + 4 * 12 + 14 if bounded else 13 * 14
    )


def test_cache_rows_read_of_a_latent_cache_is_each_live_slots_own_blocks(monkeypatch):
    """A model on models/mla.py says what its kernel reads: whole blocks up
    to each LIVE slot's own row, nothing of the others, a slot in the mean.
    Three slots, two sessions of different depth (a long prompt held while
    its chunk is due, then decoding beside the short one) and a slot that
    stays empty: the engine's count is the sum, over the steps it launched,
    of that mean - reckoned here from the positions and live rows the
    engine handed the model's hook."""
    from distributed_tensorflow_examples_tpu import models
    from distributed_tensorflow_examples_tpu.serve import model_server

    block, max_len, slots = 4, 30, 3
    monkeypatch.setattr(models.deepseek, "DECODE_BLOCK", block)
    monkeypatch.setattr(model_server, "PREFILL_CHUNK", 8)
    params, fns = _tiny_latent_fns("deepseek")
    said, launches = fns.step_rows_read, []

    def hook(pos, live, max_len):
        launches.append((pos.copy(), live.copy()))
        return said(pos, live, max_len)

    eng = model_server._DecodeEngine(
        lambda: (0, params), fns._replace(step_rows_read=hook),
        slots=slots, max_len=max_len, max_sessions=4,
    )
    try:
        _run_sessions(eng, [list(range(1, 12)), [5]], [14, 20], gap_s=0.05)
        stats = eng.stats()
    finally:
        eng.stop()
    want = 0.0
    for pos, live in launches[:stats["steps"]]:
        own = [min(max_len, (p // block + 1) * block) for p, on in zip(pos, live) if on]
        want += sum(own) / slots
    assert stats["cache_rows_read"] == pytest.approx(want, rel=1e-9)
    # Launched and not counted: at most the step in flight when the last
    # session ended, which had no live row.
    assert all(not live.any() for _pos, live in launches[stats["steps"]:])
    both = [(pos, live) for pos, live in launches if live.sum() == 2]
    assert both and any(abs(int(p[0]) - int(p[1])) >= block for p, _l in both)
    assert all(not live[2] for _pos, live in launches)
    # Far under what a read of every slot to the deepest row would count.
    deepest = sum(min(max_len, (int(pos.max()) // block + 1) * block)
                  for pos, _live in launches[:stats["steps"]])
    assert stats["cache_rows_read"] < 0.7 * deepest


def _tiny_latent_fns(model: str):
    """``(params, serve_decode_fns)`` of one of the two models on
    models/mla.py, cut small."""
    import jax

    from distributed_tensorflow_examples_tpu import models

    if model == "deepseek":
        mod, cfg = models.deepseek, models.deepseek.Config(
            vocab_size=64, hidden_size=32, intermediate_size=32, moe_intermediate_size=16,
            num_hidden_layers=2, num_attention_heads=2, kv_lora_rank=16, q_lora_rank=16,
            qk_rope_head_dim=8, qk_nope_head_dim=8, v_head_dim=8, n_routed_experts=8,
            n_shared_experts=1, n_group=2, topk_group=1, num_experts_per_tok=2,
            rope_original_max_position_embeddings=16, experts_held=4, expert_first=4,
            vocab_rows=64, param_dtype="float32",
        )
    else:
        mod, cfg = models.longcat, models.longcat.Config(
            vocab_size=64, hidden_size=32, ffn_hidden_size=32, expert_ffn_hidden_size=16,
            num_layers=2, num_attention_heads=2, kv_lora_rank=16, q_lora_rank=16,
            qk_rope_head_dim=8, qk_nope_head_dim=8, v_head_dim=8, n_routed_experts=8,
            zero_expert_num=4, moe_topk=2, experts_held=4, expert_first=4,
            vocab_rows=64, param_dtype="float32",
        )
    return mod.init(cfg, jax.random.key(0)), mod.serve_decode_fns(cfg)


@pytest.mark.parametrize("model", ["deepseek", "longcat", "transformer"])
def test_prefill_rows_read_counts_what_a_chunk_reads(monkeypatch, model):
    """``prefill_rows_read`` grows a chunk by the positions of its slot the
    chunk's attention read.  The two models on models/mla.py say what the
    grid of ops/latent_prefill.py runs (the trips of the loop that is its
    form here): whole blocks up to the chunk's last query, whatever the
    chunk holds of real tokens; a model whose ``prefill_fn`` does not say is
    taken to read all ``max_len`` a chunk."""
    from distributed_tensorflow_examples_tpu import models
    from distributed_tensorflow_examples_tpu.ops import latent_prefill
    from distributed_tensorflow_examples_tpu.serve import model_server

    chunk, block, max_len = 8, 4, 30
    monkeypatch.setattr(model_server, "PREFILL_CHUNK", chunk)
    if model == "transformer":
        eng, _params = _tiny_transformer_engine(max_len=max_len)
    else:
        monkeypatch.setattr(getattr(models, model), "PREFILL_BLOCK", block)
        params, fns = _tiny_latent_fns(model)
        eng = model_server._DecodeEngine(
            lambda: (0, params), fns, slots=2, max_len=max_len, max_sessions=4)
    try:
        # 21 and 12 cached positions (a prompt's last token goes through the
        # step): chunks at 0, 8, 16 (5 real tokens) and at 0, 8 (4 real).
        _run_sessions(eng, [list(range(1, 23)), list(range(1, 14))], [2, 2])
        stats = eng.stats()
    finally:
        eng.stop()
    assert stats["prefill_chunks"] == 5 and stats["prefill_tokens"] == 21 + 12
    if model == "transformer":
        assert stats["prefill_rows_read"] == 5 * max_len
    else:
        grid = lambda offset: block * int(
            latent_prefill.blocks_read(np.int32(offset), chunk, block, max_len))
        assert [grid(o) for o in (0, 8, 16)] == [8, 16, 24]
        assert stats["prefill_rows_read"] == 2 * (8 + 16) + 24


# ----------------------------------------------------------------------------
# A chunk is as wide as the tokens it carries (PR 40)
# ----------------------------------------------------------------------------


@pytest.mark.parametrize("chunk,floor,max_len,widths", [
    (512, 256, 2048, (256, 512)),  # the served sizes
    (512, 128, 2048, (128, 256, 512)),  # a floor a halving lower
    (512, 128, 300, (150, 300)),  # a cache shorter than a chunk
    (8, 128, 48, (8,)), (4, 128, 32, (4,)),  # what the tests above patch in
    (128, 128, 2048, (128,)), (200, 128, 2048, (200,)),  # half is under the floor
    (8, 2, 48, (2, 4, 8)), (12, 2, 48, (3, 6, 12)),  # an odd width is not halved
])
def test_the_widths_are_the_chunk_and_its_halvings_down_to_the_floor(
    monkeypatch, chunk, floor, max_len, widths,
):
    from distributed_tensorflow_examples_tpu.serve import model_server

    if (chunk, floor) == (512, 256):  # as the module has them
        assert (model_server.PREFILL_CHUNK, model_server.PREFILL_FLOOR) == (chunk, floor)
    monkeypatch.setattr(model_server, "PREFILL_CHUNK", chunk)
    monkeypatch.setattr(model_server, "PREFILL_FLOOR", floor)
    eng = model_server._DecodeEngine(
        lambda: (0, None), DecodeFns(*_toy_cached_decode_fns()), slots=1,
        max_len=max_len, max_sessions=1)
    try:
        assert eng._widths == widths == model_server.chunk_widths(eng._chunk)
        # No argument of the engine or of the replica chooses a width.
        assert "width" not in str(inspect.signature(model_server._DecodeEngine))
        assert "chunk" not in str(inspect.signature(model_server._DecodeEngine))
    finally:
        eng.stop()


#: Tokens a prompt owes its cache - all but its last - on, one under and one
#: over each edge of the widths 2 / 4 / 8, and past one and two whole chunks.
_OWED = (1, 2, 3, 4, 5, 7, 8, 9, 15, 16, 17)


@pytest.mark.parametrize("family", ["toy", "toy_state", "transformer"])
def test_a_chunk_is_as_wide_as_the_narrowest_width_that_holds_its_tokens(
    monkeypatch, engine_chunks, family,
):
    """Widths 2 / 4 / 8.  Eleven prompts on two slots (sessions seated into
    slots others left, chunks queued behind steps in flight): every session
    gets the tokens the ONE-WIDTH engine gives it and those of ``generate``
    (the plain stream, for a toy), to the token; each real chunk went out at
    the narrowest width that holds its tokens and the hook was told that
    width; ``prefill_width`` is their sum, never under ``prefill_tokens``;
    and every width ran once on no valid token before the first real
    chunk."""
    import jax

    from distributed_tensorflow_examples_tpu import models
    from distributed_tensorflow_examples_tpu.serve import model_server

    monkeypatch.setattr(model_server, "PREFILL_CHUNK", 8)
    max_len, budget = 24, 3
    params = None
    if family == "transformer":
        cfg = models.transformer.Config(
            vocab_size=61, dim=32, n_layers=2, n_heads=4, max_seq_len=max_len,
            compute_dtype="float32",
        )
        params = models.transformer.init(cfg, jax.random.key(3))
        fns = models.transformer.serve_decode_fns(cfg)
        vocab = cfg.vocab_size
    else:
        fns = DecodeFns(
            *(_toy_cached_decode_fns if family == "toy" else _toy_state_decode_fns)())
        vocab = 11
    rng = np.random.default_rng(40)
    prompts = [rng.integers(1, vocab, size=owed + 1).astype(np.int32) for owed in _OWED]

    def serve_all(floor):
        monkeypatch.setattr(model_server, "PREFILL_FLOOR", floor)
        said = fns.chunk_rows_read or (lambda o, c, m: m)
        told, sent = [], []

        def hook(offset, chunk, max_len):
            told.append(chunk)
            return said(offset, chunk, max_len)

        eng = model_server._DecodeEngine(
            lambda: (0, params), fns._replace(chunk_rows_read=hook), slots=2,
            max_len=max_len, max_sessions=16)
        prefill_jit = eng._prefill_jit

        def logged(params, cache, tokens, slot, offset, n_valid):
            assert not tokens[int(n_valid):].any()  # padded with token 0
            sent.append((int(n_valid), len(tokens)))
            return prefill_jit(params, cache, tokens, slot, offset, n_valid)

        eng._prefill_jit = logged
        try:
            outs = _run_sessions(eng, prompts, [budget] * len(prompts))
            return outs, eng.stats(), sent, told, eng._widths
        finally:
            eng.stop()

    due = sorted((n, w) for owed in _OWED for _o, n, w in engine_chunks(owed, 8, 2))
    outs, stats, sent, told, widths = serve_all(floor=2)
    assert widths == (2, 4, 8)
    # Before the first real chunk, each width once on no valid token.
    assert sent[:3] == [(0, 2), (0, 4), (0, 8)]
    assert sorted(sent[3:]) == due
    assert told == [w for _n, w in sent[3:]]
    assert stats["prefill_tokens"] == sum(_OWED)
    assert stats["prefill_width"] == sum(w for _n, w in sent[3:]) == 96
    assert stats["prefill_chunks"] == len(sent) - 3 == 16
    # The engine of one width: the same chunks, each 8 wide; the same tokens.
    outs_one, stats_one, sent_one, told_one, widths_one = serve_all(floor=128)
    assert widths_one == (8,) and sent_one[0] == (0, 8) and set(told_one) == {8}
    assert [n for n, _w in sent_one[1:]] == [n for n, _w in sent[3:]]
    assert stats_one["prefill_width"] == 8 * stats_one["prefill_chunks"] == 8 * 16
    assert outs == outs_one
    for i, (p, o) in enumerate(zip(prompts, outs)):
        if family == "toy":
            assert o == _toy_cached_stream(p, budget)
        elif family == "toy_state":
            assert o == _toy_state_stream(p, budget)
        elif _OWED[i] in (3, 9, 17):  # a program a length: three of them
            ref = np.asarray(models.transformer.generate(
                cfg, params, p[None], max_new_tokens=budget))
            assert o == ref[0, len(p):].tolist(), _OWED[i]


@pytest.mark.parametrize(
    "metric", ["prefill_valid_token_share", "batch_prefill_valid_token_share"])
def test_the_valid_token_metrics_name_a_reader_and_counters_that_exist(
    tmp_path, monkeypatch, metric,
):
    """The metric files the counter ``prefill_width`` came with: the reader
    each names is there, ``server.stats()`` carries the counters it divides,
    the share is valid over dispatched tokens - and nothing, without
    raising, on the parent's replica, which has no such counter."""
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks.harness import manifest
    from distributed_tensorflow_examples_tpu.serve import model_server

    monkeypatch.setattr(model_server, "PREFILL_CHUNK", 16)
    monkeypatch.setattr(model_server, "PREFILL_FLOOR", 4)
    spec = manifest.layer_metric(metric)
    read = manifest.reader(spec["reader"])
    assert spec["args"] == {
        "num": "decode_prefill_tokens", "den": "decode_prefill_width"}
    srv = _pinned_decode_server(tmp_path, "vt0", decode_fns=_toy_cached_decode_fns())
    try:
        c = serve.ServeClient("127.0.0.1", srv.port, role="vt_sv")
        start = c.stats()
        c.generate(np.arange(1, 24, dtype=np.int32) % 11, 2)  # owes 22: 16 + 6 in 8
        end = c.stats()
        c.close()
    finally:
        srv.stop()
    assert end["decode_prefill_width"] - start["decode_prefill_width"] == 24
    share = read({"counters": {"start": start, "end": end}}, **spec["args"])
    assert share == pytest.approx(100 * 22 / 24)
    for stats in (start, end):
        del stats["decode_prefill_width"]  # the parent's replica
    assert read({"counters": {"start": start, "end": end}}, **spec["args"]) is None


# ----------------------------------------------------------------------------
# A cache that holds a STATE: the step is told which rows are live (PR 27)
# ----------------------------------------------------------------------------


def _toy_state_decode_fns(vocab: int = 11):
    """A toy whose slot owns ONE number that every token overwrites: ``s <-
    (3 s + token + 1) mod 1009`` from zero, the next token ``s mod vocab``.
    A step that advanced a row that is not live, a session that started
    from what its slot's last session left, or a chunk that did not carry
    on from the chunk before it changes the stream.  The step takes
    ``live`` and the contract says so.  ``_toy_state_stream`` is the same in
    plain Python."""
    import jax
    import jax.numpy as jnp

    def init_cache_fn(slots, max_len):
        return jnp.zeros((slots,), jnp.int32)

    def step_fn(params, cache, tokens, pos, live):
        s = (3 * jnp.where(pos == 0, 0, cache) + tokens + 1) % 1009
        return jax.nn.one_hot(s % vocab, vocab), jnp.where(live, s, cache)

    def prefill_fn(params, cache, tokens, slot, offset, n_valid):
        def body(i, s):
            return jnp.where(i < n_valid, (3 * s + tokens[i] + 1) % 1009, s)

        s = jax.lax.fori_loop(
            0, tokens.shape[0], body, jnp.where(offset == 0, 0, cache[slot]))
        return cache.at[slot].set(s)

    return DecodeFns(init_cache_fn, step_fn, prefill_fn, wants_live=True)


def _toy_state_stream(prompt, n: int, vocab: int = 11) -> list:
    s, out = 0, []
    for t in prompt:
        s = (3 * s + int(t) + 1) % 1009
    for _ in range(n):
        out.append(s % vocab)
        s = (3 * s + out[-1] + 1) % 1009
    return out


def _run_sessions(eng, prompts, budgets, gap_s: float = 0.0) -> list:
    tickets = []
    for p, n in zip(prompts, budgets):
        tickets.append(eng.open(np.asarray(p, np.int32), n))
        time.sleep(gap_s)
    deadline = time.monotonic() + 120
    for t in tickets:
        while not t.done:
            assert time.monotonic() < deadline
            t.wait(0.5)
    return [t.snapshot(0)[0] for t in tickets]


@pytest.mark.parametrize("prefill", [True, False])
def test_a_state_is_advanced_by_live_rows_only(monkeypatch, prefill):
    """Two slots, five sessions: long prompts are held (not live) over
    several steps while another decodes, sessions are seated into slots
    others just left, one has a one-token prompt (no chunk: the step at
    ``pos == 0`` starts the state) - each gets the stream it gets alone.
    With the prompt fed through the step instead (no ``prefill_fn``) the
    same holds."""
    from distributed_tensorflow_examples_tpu.serve import model_server

    monkeypatch.setattr(model_server, "PREFILL_CHUNK", 4)
    fns = _toy_state_decode_fns()
    eng = model_server._DecodeEngine(
        lambda: (0, None), fns if prefill else fns._replace(prefill=None),
        slots=2, max_len=48, max_sessions=8,
    )
    assert eng._wants_live
    prompts = [
        [7, 3, 9], (np.arange(14) * 5 + 1) % 11, [4], (np.arange(10) * 2 + 3) % 11,
        [1, 2],
    ]
    budgets = [9, 4, 5, 3, 6]
    try:
        outs = _run_sessions(eng, prompts, budgets, gap_s=0.02)
        stats = eng.stats()
    finally:
        eng.stop()
    for p, o, n in zip(prompts, outs, budgets):
        assert o == _toy_state_stream(p, n), list(p)
    assert stats["state_bytes"] == 2 * 4
    if prefill:
        # 13 tokens in chunks of 4 hold their slot for three steps before
        # the fourth chunk's step decodes; 9 tokens for two.
        assert stats["prefill_chunks"] == 4 + 3 + 1 + 0 + 1
        assert stats["held_rows"] == 3 + 2
    else:
        assert stats["held_rows"] == 0


def test_jamba_sessions_through_the_engine_get_the_tokens_they_get_alone(
    monkeypatch,
):
    """models/jamba.py behind ``_DecodeEngine``: a two-chunk prompt held
    while another session decodes, sessions seated into slots others just
    left, a one-token prompt - each session's tokens are those it gets as
    the only session of a fresh engine, to the token."""
    import jax

    from distributed_tensorflow_examples_tpu.models import jamba
    from distributed_tensorflow_examples_tpu.serve import model_server

    monkeypatch.setattr(model_server, "PREFILL_CHUNK", 8)
    cfg = jamba.Config(
        vocab_size=97, hidden_size=32, num_hidden_layers=4,
        attn_layer_period=4, attn_layer_offset=1, num_attention_heads=2,
        num_key_value_heads=1, intermediate_size=64, mamba_dt_rank=4,
        mamba_d_state=8,
    )
    params = jamba.init(cfg, jax.random.key(5))
    # Larger weights than the initialisation's: logits far enough apart
    # that a token is no matter of rounding.
    params = jax.tree.map(
        lambda a: a * 6 if a.ndim == 2 and a.shape[0] > 8 else a, params)
    fns = jamba.serve_decode_fns(cfg)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 97, size=n) for n in (5, 20, 3, 1, 11)]
    budgets = [10, 5, 4, 6, 3]

    def engine():
        return model_server._DecodeEngine(
            lambda: (0, params), fns, slots=2, max_len=40, max_sessions=8)

    eng = engine()
    try:
        assert eng._wants_live
        together = _run_sessions(eng, prompts, budgets, gap_s=0.05)
        stats = eng.stats()
    finally:
        eng.stop()
    assert stats["held_rows"] >= 2  # the 20-token prompt's first two chunks
    assert stats["state_bytes"] == sum(
        a.nbytes for a in jax.tree.leaves(jamba.init_cache(cfg, 2, 40)))
    for p, n, got in zip(prompts, budgets, together):
        eng = engine()
        try:
            alone = _run_sessions(eng, [p], [n])[0]
        finally:
            eng.stop()
        assert got == alone, len(p)


# ----------------------------------------------------------------------------
# The compiled step selects, and the engine launches one step ahead (PR 30)
# ----------------------------------------------------------------------------


def _tied(step_fn):
    """``step_fn`` with every row's best logit met again three places on
    (mod the vocabulary): what is selected is then the LOWER of two equal
    indices, on the host and on the device alike."""
    import jax.numpy as jnp

    def step(*args):
        logits, cache = step_fn(*args)
        return jnp.maximum(logits, jnp.roll(logits, 3, axis=-1)), cache

    return step


def _plain_stream(fns, prompt, n: int, max_len: int, chunk: int):
    """One session alone, a step at a time, the token picked by
    ``np.argmax`` on the host from the logits ``step_fn`` returns: what the
    engine did before it selected on the device.  Returns the tokens and
    what the session left in its (only) slot."""
    import jax.numpy as jnp

    live = (np.ones(1, bool),) * fns.wants_live
    prompt = np.asarray(prompt, np.int32)
    cache, p = fns.init_cache(1, max_len), 0
    if fns.prefill:
        p = len(prompt) - 1
        for off in range(0, p, chunk):
            buf = np.zeros(chunk, np.int32)
            k = min(chunk, p - off)
            buf[:k] = prompt[off:off + k]
            cache = fns.prefill(
                None, cache, jnp.asarray(buf), np.int32(0), np.int32(off),
                np.int32(k))
    tok, out = int(prompt[p]), []
    while len(out) < n:
        logits, cache = fns.step(
            None, cache, np.array([tok], np.int32), np.array([p], np.int32),
            *live)
        p += 1
        if p < len(prompt):
            tok = int(prompt[p])
        else:
            tok = int(np.argmax(np.asarray(logits)[0]))
            out.append(tok)
    return out, cache


#: Unequal sessions on two slots: a long one that decodes throughout, a
#: short one that ends under it, one with a prompt of three chunks seated
#: into the slot the short one left, and a one-token prompt asking for one
#: token, last.
_AHEAD_PROMPTS = (
    [7, 3, 9], [4, 5, 6, 7, 1], (np.arange(10) * 2 + 3) % 11, [8],
)
_AHEAD_BUDGETS = (14, 2, 4, 1)


def _ahead_engine(monkeypatch, fns, gate=None):
    from distributed_tensorflow_examples_tpu.serve import model_server

    monkeypatch.setattr(model_server, "PREFILL_CHUNK", 4)

    def model():
        if gate is not None:
            gate.wait(10)
        return 0, None

    return model_server._DecodeEngine(
        model, DecodeFns(*fns), slots=2, max_len=32, max_sessions=8)


def _watch_calls(eng) -> list:
    """Record, for every call of the batcher's loop into the engine, the
    tickets seated and the engine's counters before and after it."""
    calls: list = []
    run = eng.batcher._run

    def watched(slots):
        before = (eng.prefill_chunks, eng.ahead_steps)
        try:
            return run(slots)
        finally:
            calls.append(
                (list(slots), before, (eng.prefill_chunks, eng.ahead_steps)))

    eng.batcher._run = watched
    return calls


@pytest.mark.parametrize("family", ["prefilled", "teacher_forced", "state"])
def test_the_engine_serves_what_a_plain_loop_selects_on_the_host(
    monkeypatch, family,
):
    """Sessions of unequal lengths, seated while others are mid-flight,
    through an engine whose compiled step selects and which launches a step
    before it has read the last one: every session's tokens, their count
    and ``done`` equal, token for token, a plain loop of the same
    ``step_fn`` one step at a time with ``np.argmax`` on the host - ties
    included; a state model's slot holds, after its row was stepped past
    its session's end, what that session left there; and the last token of
    the last session is out with nothing left in flight."""
    import jax

    fns = {
        "prefilled": _toy_cached_decode_fns(),
        "teacher_forced": _toy_cached_decode_fns()[:2],
        "state": _toy_state_decode_fns(),
    }[family]
    fns = DecodeFns(*fns)
    fns = fns._replace(step=_tied(fns.step))
    gate = threading.Event()
    eng = _ahead_engine(monkeypatch, fns, gate)
    calls = _watch_calls(eng)
    launches: list = []
    step_jit = eng._step_jit

    def counted_step(*a):
        launches.append(1)
        return step_jit(*a)

    eng._step_jit = counted_step
    try:
        tickets = [
            eng.open(np.asarray(p, np.int32), n)
            for p, n in zip(_AHEAD_PROMPTS, _AHEAD_BUDGETS)
        ]
        gate.set()
        _wait_done(tickets, 60)
        # Nothing more arrives: the last token came out on the loop's own
        # account, and the loop then parks with nothing in flight.
        deadline = time.monotonic() + 10
        while eng.stats()["slots_active"]:
            assert time.monotonic() < deadline
            time.sleep(0.005)
        assert eng._flight is None
        stats = eng.stats()
        state = jax.device_get(eng._cache)
    finally:
        eng.stop()
    want = [
        _plain_stream(fns, p, n, 32, 4)
        for p, n in zip(_AHEAD_PROMPTS, _AHEAD_BUDGETS)
    ]
    for t, n, (tokens, _left) in zip(tickets, _AHEAD_BUDGETS, want):
        assert t.error is None
        assert t.snapshot(0) == (tokens, True) and len(tokens) == n
    assert stats["emitted"] == sum(_AHEAD_BUDGETS)
    # Every launch was read and counted as a step, most of them launched
    # ahead; the short session ended under the long one, whose next launch
    # was already out: its row was stepped once more, inert.
    assert stats["steps"] == len(launches)
    assert 0 < stats["ahead_steps"] < stats["steps"]
    assert 1 <= stats["idle_rows"] <= len(tickets)
    if family == "state":
        # What the last session of each slot left there is still there.
        last = {}
        for slots, _before, _after in calls:
            for i, t in enumerate(slots):
                if t is not None:
                    last[i] = tickets.index(t)
        assert len(last) == 2
        for i, k in last.items():
            assert int(state[i]) == int(want[k][1][0]), (i, k)


@pytest.mark.parametrize("live", [False, True])
def test_the_compiled_step_returns_its_selection_and_no_logits(live):
    """What leaves the compiled step besides the cache is ``[S]`` int32:
    no ``[S, V]`` array is among its outputs, for the four-argument and
    for the five-argument (``live``) model alike."""
    import jax

    from distributed_tensorflow_examples_tpu.serve import model_server

    if live:
        eng = model_server._DecodeEngine(
            lambda: (0, None), _toy_state_decode_fns(), slots=2, max_len=16,
            max_sessions=4,
        )
        params = None
    else:
        eng, params = _tiny_transformer_engine()
    shapes = lambda tree: jax.tree.map(lambda a: (a.shape, a.dtype), tree)
    try:
        args = (params, eng._cache, *_step_args(2, live=live))
        out = jax.eval_shape(eng._step_jit, *args)
        held = shapes(eng._cache)
        # The logits are written out in their own type before the selection
        # reads them: fused into the head's product, a TPU's compiler picks
        # among float32 sums where the host picked among their roundings.
        assert "optimization_barrier" in eng._step_jit.lower(*args).as_text()
    finally:
        eng.stop()
    # Two outputs: the selection, and the cache as the engine holds it.
    selection, cache = out
    assert (selection.shape, selection.dtype) == ((2,), np.int32)
    assert shapes(cache) == held


@pytest.mark.parametrize("sessions,ahead", [([(3, 1)], False), ([(3, 6), (2, 5)], True)])
def test_ahead_steps_counts_the_launches_made_before_the_read(
    monkeypatch, sessions, ahead,
):
    """A session of one token is launched once, with nothing before it to
    be ahead of; two concurrent sessions are launched ahead."""
    eng = _ahead_engine(monkeypatch, _toy_cached_decode_fns())
    try:
        outs = _run_sessions(
            eng, [list(range(1, p + 1)) for p, _n in sessions],
            [n for _p, n in sessions])
        stats = eng.stats()
    finally:
        eng.stop()
    for (p, n), out in zip(sessions, outs):
        assert out == _toy_cached_stream(list(range(1, p + 1)), n)
    assert (stats["ahead_steps"] > 0) == ahead
    assert stats["ahead_steps"] <= stats["steps"]


@pytest.mark.parametrize("kth", [1, 4])
def test_a_failed_launch_fails_the_active_sessions_and_leaves_nothing_in_flight(
    monkeypatch, kth,
):
    """The compiled step raises on its k-th launch - with nothing in
    flight (the first) or with the step before it launched and not yet
    read: every active session fails, the engine is left a fresh cache and
    nothing in flight, and the next session is served token for token."""
    import jax.numpy as jnp

    gate = threading.Event()
    eng = _ahead_engine(monkeypatch, _toy_cached_decode_fns(), gate)
    step_jit = eng._step_jit
    n_calls, in_flight = [], []

    def flaky_step(*a):
        n_calls.append(1)
        if len(n_calls) == kth:
            in_flight.append(eng._flight)
            raise FloatingPointError("step failed")
        return step_jit(*a)

    eng._step_jit = flaky_step
    try:
        first = eng._cache
        tickets = [
            eng.open(np.array([1, 2, 3], np.int32), 9),
            eng.open(np.array([5], np.int32), 7),
        ]
        gate.set()
        _wait_done(tickets)
        for t in tickets:
            with pytest.raises(FloatingPointError):
                t.snapshot(0)
        # The step before was out and not read when the k-th was launched.
        assert in_flight == [None] if kth == 1 else in_flight[0] is not None
        assert eng._flight is None
        assert isinstance(eng._cache, jnp.ndarray) and not eng._cache.is_deleted()
        assert eng._cache is not first and not eng._selection.is_deleted()
        again = _run_sessions(eng, [[1, 2, 3], [4, 2]], [5, 3])
        assert again == [
            _toy_cached_stream([1, 2, 3], 5), _toy_cached_stream([4, 2], 3)]
        assert eng.stats()["step_errors"] == 1
    finally:
        eng.stop()


@pytest.mark.parametrize("family", ["prefilled", "teacher_forced", "state"])
def test_the_engine_observes_every_session_wait_once(monkeypatch, family):
    """Through the engine, with prompts longer than a chunk, sessions that
    queue for a slot and steps that emit for one row and not for another:
    the three histograms' counts are the counters' ``seated``,
    ``first_tokens`` and ``emitted - first_tokens``."""
    fns = {
        "prefilled": _toy_cached_decode_fns, "state": _toy_state_decode_fns,
        "teacher_forced": lambda: _toy_cached_decode_fns()[:2],
    }[family]()
    before = _hist_counts()
    eng = _ahead_engine(monkeypatch, fns)
    try:
        _run_sessions(eng, _AHEAD_PROMPTS, _AHEAD_BUDGETS)
        stats = eng.stats()
    finally:
        eng.stop()
    assert stats["seated"] == stats["first_tokens"] == len(_AHEAD_PROMPTS)
    assert stats["emitted"] == sum(_AHEAD_BUDGETS)
    _assert_hists_count_the_counters(before, stats)


class _TickingClock:
    """``time`` with a ``perf_counter_ns`` that advances one tick a read,
    whichever thread reads: every interval is a count of reads."""

    TICK = 1000

    def __init__(self):
        self._now = 10**12
        self._lock = threading.Lock()

    def perf_counter_ns(self) -> int:
        with self._lock:
            self._now += self.TICK
            return self._now

    def __getattr__(self, name):
        return getattr(time, name)


def test_the_host_share_of_a_call_is_its_wall_time_less_its_two_waits(monkeypatch):
    """``decode/host/ns`` + the time inside ``decode/fetch`` + the time
    inside the ``decode/prefill`` wait is the wall time of the engine's
    calls, on a clock that ticks once a read: nothing else is taken off a
    call (not the chunk's launch, not the upload, not the select), and
    nothing is taken off twice.  ``decode/prefill/ns`` still holds each
    chunk's launch and its stretch on the device beside the wait."""
    from distributed_tensorflow_examples_tpu.serve import model_server

    clock = _TickingClock()
    for mod in (model_server, batcher_lib, telemetry):
        monkeypatch.setattr(mod, "time", clock)
    # The wait alone: ``decode/prefill/ns`` also takes the hand-booked part.
    wait = telemetry.span("t_host_share/prefill_wait")
    monkeypatch.setattr(model_server, "_SPAN_PREFILL", wait)
    names = ("decode/host/ns", "decode/fetch/ns", "decode/prefill/ns",
             "decode/chunk_launch/ns", "decode/chunk_launch/n",
             "t_host_share/prefill_wait/ns", "t_host_share/prefill_wait/n")

    def read():
        return {k: telemetry.REGISTRY.counter(k).value for k in names}

    before = read()
    gate = threading.Event()
    eng = _ahead_engine(monkeypatch, _toy_cached_decode_fns(), gate)
    run, walls = eng.batcher._run, []

    def timed(slots):
        t0 = clock.perf_counter_ns()
        try:
            return run(slots)
        finally:
            # Two reads of the clock lie between this pair and the call's own.
            walls.append(clock.perf_counter_ns() - t0 - 2 * clock.TICK)

    eng.batcher._run = timed
    try:
        # Opened while the first call stands at the gate: from there on the
        # step thread alone reads the clock.
        tickets = [
            eng.open(np.asarray(p, np.int32), n)
            for p, n in zip(_AHEAD_PROMPTS, _AHEAD_BUDGETS)
        ]
        gate.set()
        _wait_done(tickets, 60)
        deadline = time.monotonic() + 10
        while eng.stats()["slots_active"]:
            assert time.monotonic() < deadline
            time.sleep(0.005)
        stats = eng.stats()
    finally:
        eng.stop()
    d = {k: v - before[k] for k, v in read().items()}
    assert stats["step_errors"] == 0 and stats["prefill_chunks"] == 5
    assert d["decode/host/ns"] > 0
    assert (
        d["decode/host/ns"] + d["decode/fetch/ns"]
        + d["t_host_share/prefill_wait/ns"]
    ) == sum(walls)
    # Each chunk was launched in a span of its own and waited for once; the
    # launch is booked onto ``decode/prefill/ns`` with the chunk's stretch
    # (at least the one read that ends it).
    assert d["decode/chunk_launch/n"] == d["t_host_share/prefill_wait/n"] == 5
    assert d["decode/chunk_launch/ns"] >= 5 * clock.TICK
    assert d["decode/prefill/ns"] >= d["decode/chunk_launch/ns"] + 5 * clock.TICK


class _Selection:
    """What a launch leaves the host to read, by a script."""

    def __init__(self, ready: bool, slots: int):
        self._ready, self._slots = ready, slots

    def is_ready(self) -> bool:
        return self._ready

    def __array__(self, dtype=None, copy=None):
        return np.zeros(self._slots, np.int32)


@pytest.mark.parametrize(
    "script", [(False,) * 4, (True, False, True, True, False), (True,) * 3])
def test_reads_ready_counts_the_reads_that_found_their_step_done(monkeypatch, script):
    """``reads_ready`` rises by one for each read whose selection says
    ``is_ready()`` before the host waits for it - the step was done first,
    so the host set its pace - and by none for the others."""
    from distributed_tensorflow_examples_tpu.serve import model_server

    eng = _ahead_engine(monkeypatch, _toy_cached_decode_fns())
    try:
        assert eng.stats()["reads_ready"] == 0
        for ready in script:  # the step thread is parked: nothing else reads
            flight = model_server._Flight(
                _Selection(ready, eng.slots), [], 0, 0.0, None)
            assert eng._collect(flight) == []
        assert eng.stats()["reads_ready"] == sum(script)
        # A live engine on the CPU reads each step once, ready or not.
        _run_sessions(eng, [[1, 2, 3]], [6])
        stats = eng.stats()
        assert sum(script) <= stats["reads_ready"] <= sum(script) + stats["steps"]
    finally:
        eng.stop()


def _watch_chunks(eng) -> list:
    """Record, for every chunk dispatched (not the chunk of no token that
    compiles the program), its slot, offset and count and the step that was
    in flight at its dispatch."""
    chunks: list = []
    prefill_jit = eng._prefill_jit

    def watched(params, cache, tokens, slot, offset, n_valid):
        if n_valid:
            chunks.append((int(slot), int(offset), int(n_valid), eng._flight))
        return prefill_jit(params, cache, tokens, slot, offset, n_valid)

    eng._prefill_jit = watched
    return chunks


@pytest.mark.parametrize("family", ["prefilled", "state"])
def test_a_chunk_due_is_queued_behind_the_step_in_flight_and_the_next_step_behind_it(
    monkeypatch, family,
):
    """A chunk is one more member of the device's queue: the call that finds
    one due dispatches it behind the step in flight, launches the next step
    behind it and then reads the step in flight - so ``prefill_chunks`` and
    ``ahead_steps`` rise in ONE call.  Only a parked engine's first chunk
    finds nothing in flight.  The tokens are the plain stream's."""
    fns = _toy_cached_decode_fns() if family == "prefilled" else _toy_state_decode_fns()
    gate = threading.Event()
    eng = _ahead_engine(monkeypatch, fns, gate)
    calls = _watch_calls(eng)
    chunks = _watch_chunks(eng)
    stream = _toy_cached_stream if family == "prefilled" else _toy_state_stream
    try:
        tickets = [
            eng.open(np.asarray(p, np.int32), n)
            for p, n in zip(_AHEAD_PROMPTS, _AHEAD_BUDGETS)
        ]
        gate.set()
        _wait_done(tickets, 60)
        stats = eng.stats()
        assert eng._chunk_echo is None
    finally:
        eng.stop()
    for t, p, n in zip(tickets, _AHEAD_PROMPTS, _AHEAD_BUDGETS):
        assert t.snapshot(0) == (stream(p, n), True)
    # 2, 4 and 9 prompt tokens in chunks of 4; the long session decodes
    # under every one of them but the first.
    assert stats["prefill_chunks"] == 1 + 1 + 3 == len(chunks)
    in_flight = [flight for _slot, _offset, _n, flight in chunks]
    assert in_flight[0] is None and None not in in_flight[1:]
    assert stats["queued_chunks"] == 4
    rose = [
        (after[0] > before[0], after[1] > before[1])
        for _slots, before, after in calls
    ]
    assert [r for r in rose if r[0]] == [(True, False)] + [(True, True)] * 4
    assert (False, True) in rose  # and ordinary calls launch ahead as before


class _FailedEcho:
    """What a chunk that failed on the device leaves the host to wait on."""

    def block_until_ready(self):
        raise FloatingPointError("chunk failed on the device, seen at the wait")


@pytest.mark.parametrize("where", ["dispatch", "wait"])
def test_a_failed_queued_chunk_fails_the_sessions_and_leaves_nothing_outstanding(
    monkeypatch, where,
):
    """A chunk queued behind a step in flight that raises at its dispatch,
    or that fails on the device and surfaces at the next call's wait: every
    active session fails, the engine is left a fresh cache, nothing in
    flight and no chunk outstanding, and the next session is served token
    for token."""
    import jax.numpy as jnp

    gate = threading.Event()
    eng = _ahead_engine(monkeypatch, _toy_cached_decode_fns(), gate)
    prefill_jit = eng._prefill_jit
    in_flight: list = []

    def flaky_chunk(params, cache, tokens, slot, offset, n_valid):
        if n_valid and eng._flight is not None and not in_flight:
            in_flight.append(eng._flight)
            if where == "dispatch":
                raise FloatingPointError("chunk failed at its dispatch")
            return prefill_jit(params, cache, tokens, slot, offset, n_valid)[0], _FailedEcho()
        return prefill_jit(params, cache, tokens, slot, offset, n_valid)

    eng._prefill_jit = flaky_chunk
    try:
        first = eng._cache
        tickets = [
            eng.open(np.array([1, 2, 3], np.int32), 9),
            eng.open((np.arange(10, dtype=np.int32) * 2 + 3) % 11, 4),
        ]
        gate.set()
        _wait_done(tickets)
        for t in tickets:
            with pytest.raises(FloatingPointError, match=where):
                t.snapshot(0)
        assert len(in_flight) == 1  # a step was out and not read
        assert eng._flight is None and eng._chunk_echo is None
        assert isinstance(eng._cache, jnp.ndarray) and not eng._cache.is_deleted()
        assert eng._cache is not first and not eng._selection.is_deleted()
        prompts = [[1, 2, 3], (np.arange(10) * 2 + 3) % 11]
        again = _run_sessions(eng, prompts, [5, 3])
        assert again == [_toy_cached_stream(p, n) for p, n in zip(prompts, [5, 3])]
        assert eng.stats()["step_errors"] == 1
    finally:
        eng.stop()


def test_every_chunk_is_measured_once_and_queued_unless_the_engine_was_parked(
    monkeypatch,
):
    """``decode/prefill/n`` rises by exactly ``prefill_chunks`` - each chunk
    is waited for once, at the top of the call after its dispatch - with its
    time on ``decode/prefill/ns``; ``queued_chunks`` is ``prefill_chunks``
    less the chunks that found the engine parked."""
    from distributed_tensorflow_examples_tpu.utils import telemetry

    eng = _ahead_engine(monkeypatch, _toy_cached_decode_fns())
    chunks = _watch_chunks(eng)
    n, ns = (telemetry.REGISTRY.counter(f"decode/prefill/{k}") for k in ("n", "ns"))
    n0, ns0 = n.value, ns.value
    try:
        # Two waves with a park between them: the second's first chunk finds
        # nothing in flight, its later ones the step launched behind the
        # chunk before them.
        for prompts, budgets in (
            (_AHEAD_PROMPTS, _AHEAD_BUDGETS), ([np.arange(14) % 11], [3]),
        ):
            _run_sessions(eng, prompts, budgets)
            deadline = time.monotonic() + 10
            while eng.stats()["slots_active"]:
                assert time.monotonic() < deadline
                time.sleep(0.005)
        stats = eng.stats()
    finally:
        eng.stop()
    parked = sum(1 for *_c, flight in chunks if flight is None)
    assert stats["prefill_chunks"] == len(chunks) == 5 + 4
    assert n.value - n0 == stats["prefill_chunks"] and ns.value > ns0
    assert 1 <= parked <= 3  # a wave's first chunk; _run_sessions opens one at a time
    assert stats["queued_chunks"] == stats["prefill_chunks"] - parked


def test_a_held_row_is_not_live_in_any_step_dispatched_before_its_last_chunk(
    monkeypatch,
):
    """With chunks and steps queued behind one another, a state model's row
    is live only in steps DISPATCHED after its prompt's last chunk: in every
    step between a session's first chunk and its last the row is held, and
    the step right behind the last chunk decodes it."""
    eng = _ahead_engine(monkeypatch, _toy_state_decode_fns())
    order: list = []
    step_jit, prefill_jit = eng._step_jit, eng._prefill_jit

    def logged_step(*a):
        order.append(("step", np.asarray(a[-1]).copy()))
        return step_jit(*a)

    def logged_chunk(params, cache, tokens, slot, offset, n_valid):
        if n_valid:
            order.append(("chunk", int(slot), int(offset) + int(n_valid)))
        return prefill_jit(params, cache, tokens, slot, offset, n_valid)

    eng._step_jit, eng._prefill_jit = logged_step, logged_chunk
    prompts = [[7, 3, 9], (np.arange(14) * 5 + 1) % 11, (np.arange(10) * 2 + 3) % 11]
    budgets = [16, 3, 4]
    try:
        outs = _run_sessions(eng, prompts, budgets)
    finally:
        eng.stop()
    for p, o, n in zip(prompts, outs, budgets):
        assert o == _toy_state_stream(p, n)
    # The two long prompts pass through slot 1, 13 and 9 tokens in chunks of
    # 4, while slot 0 decodes.
    sessions, first = [], None
    for k, entry in enumerate(order):
        if entry[0] == "chunk" and entry[1] == 1:
            first = k if first is None else first
            if entry[2] in (13, 9) and (first, k) not in sessions:
                sessions.append((first, k))
                first = None
    assert [order[last][2] for _first, last in sessions] == [13, 9]
    for first, last in sessions:
        assert last - first >= 4  # chunks and steps alternate
        between = [e[1] for e in order[first:last] if e[0] == "step"]
        assert between and not any(live[1] for live in between)
        assert order[last + 1][0] == "step" and order[last + 1][1][1]


def test_stop_with_a_chunk_outstanding_leaves_nothing_on_the_device(monkeypatch):
    """The loop ends between the call that dispatched a chunk and the call
    that would have waited for it: ``stop()`` waits for the chunk and for the
    step launched behind it, and the engine holds nothing afterwards."""
    eng = _ahead_engine(monkeypatch, _toy_cached_decode_fns())
    prefill_jit, run = eng._prefill_jit, eng.batcher._run
    waited: list = []

    class Echo:
        def __init__(self, echo):
            self.echo = echo

        def block_until_ready(self):
            waited.append(self.echo.block_until_ready())

    def echoing(params, cache, tokens, slot, offset, n_valid):
        cache, echo = prefill_jit(params, cache, tokens, slot, offset, n_valid)
        return cache, (Echo(echo) if n_valid else echo)

    def last_call(slots):
        try:
            return run(slots)
        finally:
            if eng._chunk_echo is not None:
                eng.batcher._stopped = True  # the loop ends before its next call

    eng._prefill_jit, eng.batcher._run = echoing, last_call
    ticket = eng.open(np.arange(1, 11, dtype=np.int32), 3)
    _wait_done([ticket])
    assert ticket.error is not None  # the stopped batcher failed it
    eng.batcher._thread.join(10)
    assert not eng.batcher._thread.is_alive()
    flight = eng._flight
    assert eng._chunk_echo is not None and flight is not None and not waited
    eng.stop()
    assert [int(n) for n in waited] == [4]
    assert flight.selection.is_ready()
    assert eng._chunk_echo is None and eng._flight is None and eng._cache is None


@pytest.mark.parametrize("metric", ["chunk_queued_share", "batch_chunk_queued_share"])
def test_the_chunk_queued_metrics_name_a_reader_and_counters_that_exist(
    tmp_path, metric,
):
    """The metric files this engine's counter came with: the reader each
    names is there, and ``server.stats()`` of a replica with a ``prefill_fn``
    carries the counters it divides - so the reader finds something to read
    (and nothing, without raising, where the counter is absent)."""
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks.harness import manifest

    spec = manifest.layer_metric(metric)
    read = manifest.reader(spec["reader"])
    assert spec["args"] == {
        "num": "decode_queued_chunks", "den": "decode_prefill_chunks"}
    srv = _pinned_decode_server(tmp_path, "cq0", decode_fns=_toy_cached_decode_fns())
    try:
        c = serve.ServeClient("127.0.0.1", srv.port, role="cq_sv")
        start = c.stats()
        c.generate(np.arange(1, 30, dtype=np.int32) % 11, 2)
        end = c.stats()
        c.close()
    finally:
        srv.stop()
    for key in spec["args"].values():
        assert key in end
    # 28 prompt tokens in one chunk of the replica's 32 positions, parked.
    assert end["decode_prefill_chunks"] - start["decode_prefill_chunks"] == 1
    assert read({"counters": {"start": start, "end": end}}, **spec["args"]) == 0.0
    for stats in (start, end):
        del stats["decode_queued_chunks"]  # the parent's replica: no such counter
    assert read({"counters": {"start": start, "end": end}}, **spec["args"]) is None


# ----------------------------------------------------------------------------
# What a model counts on the device (PR 31)
# ----------------------------------------------------------------------------


def _tiny_longcat():
    import jax

    from distributed_tensorflow_examples_tpu.models import longcat

    cfg = longcat.Config(
        vocab_size=97, hidden_size=32, ffn_hidden_size=64, expert_ffn_hidden_size=16,
        num_layers=2, num_attention_heads=2, kv_lora_rank=16, q_lora_rank=24,
        qk_rope_head_dim=8, qk_nope_head_dim=8, v_head_dim=8, n_routed_experts=8,
        zero_expert_num=4, moe_topk=3, experts_held=4, expert_first=2,
        param_dtype="float32",
    )
    params = longcat.init(cfg, jax.random.key(5))
    # Larger weights than the initialisation's: logits far enough apart
    # that a token is no matter of rounding.
    params = jax.tree.map(lambda a: a * 6 if a.ndim >= 2 else a, params)
    return cfg, params, longcat.serve_decode_fns(cfg)


def test_longcat_sessions_through_the_engine_and_its_counters_add_up(monkeypatch):
    """models/longcat.py behind ``_DecodeEngine``: a three-chunk prompt held
    while others decode, a one-token prompt, slots reseated - each session
    gets the tokens it gets alone; and ``model_moe_*`` say what the expert
    layer did for the LIVE rows: every live token of every layer made
    ``top_k`` choices (a chunk's last layer calls no expert layer), each
    held, zero-compute or absent."""
    from distributed_tensorflow_examples_tpu.serve import model_server

    monkeypatch.setattr(model_server, "PREFILL_CHUNK", 8)
    cfg, params, fns = _tiny_longcat()
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 97, size=n) for n in (5, 20, 3, 1, 11)]
    budgets = [10, 5, 4, 6, 3]

    def engine():
        return model_server._DecodeEngine(
            lambda: (0, params), fns, slots=2, max_len=40, max_sessions=8)

    eng = engine()
    try:
        assert eng._wants_live and eng._counts
        before = eng.stats()
        together = _run_sessions(eng, prompts, budgets, gap_s=0.05)
        stats = eng.stats()
        again = eng.stats()  # parked: read as the last row finished
    finally:
        eng.stop()
    assert all(before[f"model_moe_{k}"] == 0 for k in (
        "choices", "choices_held", "choices_zero", "experts_touched", "calls"))
    assert stats["held_rows"] >= 2  # the 20-token prompt's first chunks
    # Live token-layers: a prompt of P tokens is P - 1 tokens through every
    # layer but the last in chunks, then 1 + (n - 1) steps through both.
    chunked = sum(len(p) - 1 for p in prompts)
    stepped = sum(budgets)
    assert stats["prefill_tokens"] == chunked
    token_layers = chunked * (cfg.num_layers - 1) + stepped * cfg.num_layers
    assert stats["model_moe_choices"] == cfg.moe_topk * token_layers
    # The engine's warm-up chunk of no valid token is a call with no choice.
    assert stats["model_moe_calls"] == (
        (stats["prefill_chunks"] + 1) * (cfg.num_layers - 1)
        + stats["steps"] * cfg.num_layers)
    held, zero = stats["model_moe_choices_held"], stats["model_moe_choices_zero"]
    assert 0 < held and 0 < zero and held + zero < stats["model_moe_choices"]
    assert 0 < stats["model_moe_experts_touched"] <= held
    assert {k: v for k, v in again.items() if k.startswith("model_")} == {
        k: v for k, v in stats.items() if k.startswith("model_")}
    for p, n, got in zip(prompts, budgets, together):
        eng = engine()
        try:
            alone = _run_sessions(eng, [p], [n])[0]
        finally:
            eng.stop()
        assert got == alone, len(p)


def test_counters_are_read_on_the_step_thread_and_survive_a_lost_cache(monkeypatch):
    """``stats()`` from another thread only asks: every read of the cache's
    counters is made by the step thread (or before it exists).  A failed
    step costs the engine its cache; the fresh one counts from zero and the
    host's totals keep what was read."""
    import threading

    from distributed_tensorflow_examples_tpu.serve import model_server

    monkeypatch.setattr(model_server, "PREFILL_CHUNK", 8)
    cfg, params, fns = _tiny_longcat()
    eng = model_server._DecodeEngine(
        lambda: (0, params), fns, slots=2, max_len=40, max_sessions=8)
    readers = []
    read = eng._read_counters
    spans0 = telemetry.REGISTRY.counter("decode/counters/n").value
    monkeypatch.setattr(
        eng, "_read_counters",
        lambda: (readers.append(threading.current_thread().name), read())[1])
    try:
        ticket = eng.open(np.arange(1, 6, dtype=np.int32), 30)
        seen = []
        while not ticket.done:
            seen.append(eng.stats()["model_moe_choices"])
            ticket.wait(0.05)
        first = eng.stats()["model_moe_choices"]
        assert first == cfg.moe_topk * (4 * (cfg.num_layers - 1) + 30 * cfg.num_layers)
        assert seen == sorted(seen) and seen[-1] <= first
        assert readers and set(readers) == {"dtx-decode-slots"}
        # Each of those reads lies in a span of its own, ``decode/counters``.
        assert telemetry.REGISTRY.counter("decode/counters/n").value - spans0 == len(readers)
        # Lose the cache: the next launch raises.
        step = eng._step_jit
        monkeypatch.setattr(eng, "_step_jit", lambda *a: (_ for _ in ()).throw(RuntimeError("x")))
        bad = eng.open(np.arange(1, 3, dtype=np.int32), 2)
        while not bad.done:
            bad.wait(0.05)
        assert bad.error is not None
        monkeypatch.setattr(eng, "_step_jit", step)
        _run_sessions(eng, [np.arange(1, 3)], [2])
        assert eng.stats()["model_moe_choices"] == first + cfg.moe_topk * (
            1 * (cfg.num_layers - 1) + 2 * cfg.num_layers)
    finally:
        eng.stop()


@pytest.mark.parametrize("parked", [False, True])
def test_counters_asked_for_are_read_though_the_last_session_was_closed(monkeypatch, parked):
    """A session its client closes leaves its slot without one more call of
    the step function - a load generator closes every open session at its
    window's end, the instant the window's closing ``stats()`` is made.  The
    ask is answered all the same, as the step thread parks (``_parked``) or,
    woken, after it has: ``stats()`` then gives what the device counted, not
    what the ask before it left."""
    import jax

    from distributed_tensorflow_examples_tpu.serve import model_server

    monkeypatch.setattr(model_server, "PREFILL_CHUNK", 8)
    cfg, params, fns = _tiny_longcat()
    eng = model_server._DecodeEngine(
        lambda: (0, params), fns, slots=2, max_len=40, max_sessions=8)
    try:
        ticket = eng.open(np.arange(1, 6, dtype=np.int32), 30)
        start = eng.stats()["model_moe_choices"]
        while len(ticket.snapshot(0)[0]) < 8:
            ticket.wait(0.05)
        ticket.cancel()
        if parked:
            while eng.batcher.stats()["slots_active"]:
                time.sleep(0.01)
        end = eng.stats()["model_moe_choices"]
        while eng.batcher.stats()["slots_active"]:
            time.sleep(0.01)
        on_device = int(np.asarray(jax.device_get(eng._cache["counters"]["moe_choices"])).sum())
        # Not yet parked, the thread may answer from a call and launch
        # once more before it sees the row gone.
        assert start < end <= on_device and (end == on_device or not parked)
        assert eng.stats()["model_moe_choices"] == end
    finally:
        eng.stop()
    assert eng.stats()["model_moe_choices"] == end  # stopped: asks nobody, waits for nobody


@pytest.mark.parametrize("family", ["transformer", "toy", "toy_state"])
def test_a_model_without_counters_reports_none(family):
    """No ``counters`` entry in the cache tree: no ``model_*`` key, no read,
    and the engine's other numbers as before."""
    from distributed_tensorflow_examples_tpu.serve import model_server

    if family == "transformer":
        eng, _params = _tiny_transformer_engine()
    else:
        fns = _toy_cached_decode_fns() if family == "toy" else _toy_state_decode_fns()
        eng = model_server._DecodeEngine(
            lambda: (0, {"w": np.float32(1.0)}), DecodeFns(*fns), slots=2,
            max_len=16, max_sessions=4)
    try:
        assert not eng._counts
        out = _run_sessions(eng, [[1, 2, 3]], [4])
        stats = eng.stats()
    finally:
        eng.stop()
    assert len(out[0]) == 4
    assert not [k for k in stats if k.startswith("model_")]
    assert stats["emitted"] == 4
    if family == "toy":
        assert out[0] == _toy_cached_stream([1, 2, 3], 4)
    if family == "toy_state":
        assert out[0] == _toy_state_stream([1, 2, 3], 4)


# ----------------------------------------------------------------------------
# A wide engine on an expert model with a grouped choice (PR 33)
# ----------------------------------------------------------------------------


def test_deepseek_at_64_slots_through_the_engine_and_its_counters_add_up(monkeypatch):
    """models/deepseek.py behind ``_DecodeEngine`` at the width of its cell,
    64 slots, at a toy size: 70 sessions (more than there are slots, so some
    are seated into used slots), prompts of no, one and several chunks -
    ``model_moe_*`` say what the expert layers did for the LIVE rows: every
    live token of every expert layer made 6 choices (the leading dense layer
    none; a chunk's last layer calls no expert layer), a token that reaches
    this device (``tokens_reaching``) brings 1 to 6 of them, and a session
    gets the tokens it gets alone."""
    import jax

    from distributed_tensorflow_examples_tpu.models import deepseek
    from distributed_tensorflow_examples_tpu.serve import model_server

    monkeypatch.setattr(model_server, "PREFILL_CHUNK", 8)
    cfg = deepseek.Config(
        vocab_size=97, hidden_size=32, intermediate_size=64, moe_intermediate_size=16,
        num_hidden_layers=3, num_attention_heads=2, kv_lora_rank=16, q_lora_rank=24,
        qk_rope_head_dim=8, qk_nope_head_dim=8, v_head_dim=8, n_routed_experts=32,
        n_group=4, topk_group=2, num_experts_per_tok=6,
        rope_original_max_position_embeddings=16, experts_held=8, expert_first=16,
        param_dtype="float32")
    params = deepseek.init(cfg, jax.random.key(5))
    # Larger weights than the initialisation's: logits far enough apart
    # that a token is no matter of rounding.
    params = jax.tree.map(lambda a: a * 2 if a.ndim >= 2 else a, params)
    fns = deepseek.serve_decode_fns(cfg)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 97, size=n) for n in rng.integers(1, 21, size=70)]
    prompts[0], prompts[1] = prompts[0][:1], rng.integers(0, 97, size=20)
    budgets = [int(n) for n in rng.integers(2, 7, size=70)]

    def engine(slots):
        return model_server._DecodeEngine(
            lambda: (0, params), fns, slots=slots, max_len=32, max_sessions=128)

    eng = engine(64)
    try:
        assert eng._wants_live and eng._counts
        together = _run_sessions(eng, prompts, budgets)
        stats = eng.stats()
    finally:
        eng.stop()
    moe_layers = cfg.layer_kinds.count("moe")
    chunked = sum(len(p) - 1 for p in prompts)
    stepped = sum(budgets)
    assert stats["prefill_tokens"] == chunked and stats["emitted"] == stepped
    # A chunk's last layer is an expert layer and is skipped.
    token_layers = chunked * (moe_layers - 1) + stepped * moe_layers
    assert stats["model_moe_choices"] == 6 * token_layers
    assert stats["model_moe_calls"] == (
        (stats["prefill_chunks"] + 1) * (moe_layers - 1) + stats["steps"] * moe_layers)
    held, reaching = stats["model_moe_choices_held"], stats["model_moe_tokens_reaching"]
    assert 0 < reaching <= held <= 6 * reaching and reaching < token_layers
    assert 0 < stats["model_moe_experts_touched"] <= held
    assert stats["model_moe_chunk_calls"] == (stats["prefill_chunks"] + 1) * (moe_layers - 1)
    assert "model_moe_choices_zero" not in stats
    for i in (0, 1, 17, 69):
        eng = engine(2)
        try:
            alone = _run_sessions(eng, [prompts[i]], [budgets[i]])[0]
        finally:
            eng.stop()
        assert together[i] == alone, i


# ----------------------------------------------------------------------------
# A cache of two kinds of key/value row: rings beside full rows (PR 39)
# ----------------------------------------------------------------------------


@pytest.mark.parametrize("form", ["loop", "kernel"])
def test_afmoe_sessions_over_wrapping_rings_through_the_engine_and_its_counters_add_up(
        monkeypatch, form):
    """models/afmoe.py behind ``_DecodeEngine``: a window of 8 positions,
    rings of 8 + 8 rows (the engine's chunk is 8) in three layers of four and
    full rows in the fourth; four sessions on two slots, so slots are
    reseated over rings another session filled - prompts of no, one and
    several chunks, SHORTER than the window, LONGER than a ring (30) and
    sessions that end past two rings (a one-token prompt stepped 40 times).
    Each session gets the tokens it gets alone (on ONE second engine, one
    session after another: a compile is most of what an engine costs here)
    and the tokens ``generate`` picks (two of them share its program); ``model_attn_*`` say what the step's attention read and needed by
    kind of layer - what was READ is, summed over slots and layers, what the
    model's ``cache_rows_read`` told the engine a slot in the mean layer -
    and ``model_moe_*`` what the expert layers did: every choice held.  In
    both forms of the step's attention: "loop", what
    ``ring_cache.attend_step`` runs on the CPU (every slot read to the
    deepest live row's block), and "kernel", what it runs on a TPU
    (ops/slot_decode.py, interpreted here: each live slot to its own row) -
    the engine, the sessions alone and ``generate`` all in that form."""
    import jax

    from distributed_tensorflow_examples_tpu.models import afmoe, ring_cache
    from distributed_tensorflow_examples_tpu.serve import model_server

    monkeypatch.setattr(model_server, "PREFILL_CHUNK", 8)
    if form == "kernel":
        monkeypatch.setattr(ring_cache, "interpret_mode", lambda: False)
    window, slots, max_len = 8, 2, 48
    cfg = afmoe.Config(
        vocab_size=97, hidden_size=32, intermediate_size=64, moe_intermediate_size=16,
        num_hidden_layers=4, num_dense_layers=1, num_attention_heads=4,
        num_key_value_heads=2, head_dim=8, sliding_window=window, num_experts=8,
        num_experts_per_tok=2, layer_types=(afmoe.SLIDING,) * 3 + (afmoe.FULL,),
        ring_slack=8, attn_block=4, param_dtype="float32")
    params = afmoe.init(cfg, jax.random.key(5))
    # A larger head than the initialisation's: logits far enough apart that
    # a token is no matter of rounding (every other write passes a norm).
    params["head"] = jax.tree.map(lambda a: a * 6, params["head"])
    fns = afmoe.serve_decode_fns(cfg)
    said, launches = fns.step_rows_read, []

    def hook(pos, live, max_len):
        launches.append((pos.copy(), live.copy()))
        return said(pos, live, max_len)

    fns = fns._replace(step_rows_read=hook)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 97, size=n) for n in (5, 30, 1, 5)]
    budgets = [20, 6, 40, 20]

    def engine():
        return model_server._DecodeEngine(
            lambda: (0, params), fns, slots=slots, max_len=max_len, max_sessions=8)

    eng = engine()
    try:
        assert eng._wants_live and eng._counts
        # Keys and values of 2 heads of 8, float32: three rings and a full
        # layer a slot, a spare slot beside the two, and the counters.
        assert eng.state_bytes == (slots + 1) * 2 * 2 * 8 * 4 * (3 * 16 + max_len) + 4 * (
            8 + 5 * slots)
        together = _run_sessions(eng, prompts, budgets, gap_s=0.05)
        stats = eng.stats()
    finally:
        eng.stop()
    n_layers, n_sliding, n_moe = 4, 3, 3
    chunked = sum(len(p) - 1 for p in prompts)
    stepped = sum(budgets)
    assert stats["prefill_tokens"] == chunked and stats["emitted"] == stepped
    # A chunk's last layer is an expert layer and is skipped.
    token_layers = chunked * (n_moe - 1) + stepped * n_moe
    assert stats["model_moe_choices"] == stats["model_moe_choices_held"] == 2 * token_layers
    assert stats["model_moe_calls"] == (
        (stats["prefill_chunks"] + 1) * (n_moe - 1) + stats["steps"] * n_moe)
    # What the live rows needed: a sliding layer min(pos + 1, window) rows,
    # the full layer pos + 1, each session's steps at positions P-1 .. P+n-2.
    at = [range(len(p) - 1, len(p) - 1 + n) for p, n in zip(prompts, budgets)]
    assert stats["model_attn_global_rows_needed"] == sum(t + 1 for r in at for t in r)
    assert stats["model_attn_window_rows_needed"] == n_sliding * sum(
        min(t + 1, window) for r in at for t in r)
    # What was read: the loop every slot to the deepest live row's block, the
    # kernel each live slot to its own, a ring at most whole - as the hook
    # said, launch by launch.
    counted = launches[:stats["steps"]]
    assert all(not live.any() for _pos, live in launches[stats["steps"]:])
    depths = [np.where(live, pos + 1, 0) for pos, live in counted]
    deepest = [int(d.max()) for d in depths]
    if form == "loop":
        depths = [np.full(slots, d) for d in deepest]
    blocks = lambda rows: -(-rows // 4) * 4
    assert stats["model_attn_global_rows_read"] == sum(blocks(d).sum() for d in depths)
    assert stats["model_attn_window_rows_read"] == n_sliding * sum(
        np.minimum(blocks(d), 16).sum() for d in depths)
    assert stats["model_attn_rows_read"] == (
        stats["model_attn_window_rows_read"] + stats["model_attn_global_rows_read"])
    assert stats["cache_rows_read"] == pytest.approx(
        stats["model_attn_rows_read"] / (slots * n_layers), rel=1e-9)
    assert max(deepest) > 2 * 16  # past two rings
    assert stats["model_attn_window_rows_needed"] < stats["model_attn_window_rows_read"]
    eng = engine()
    try:
        alone = [_run_sessions(eng, [p], [n])[0] for p, n in zip(prompts, budgets)]
    finally:
        eng.stop()
    for i, (p, n, got) in enumerate(zip(prompts, budgets, together)):
        assert got == alone[i], i
        if len(p) > 1:
            picked = afmoe.generate(cfg, params, p[None], max_new_tokens=n)
            assert got == np.asarray(picked)[0, len(p):].tolist(), i
