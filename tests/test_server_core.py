"""dtxcore — the unified async server runtime (r17).

What is pinned here, per the acceptance criteria:

- **Handler-table dispatch** — one core hosting BOTH Python services on
  one port routes each connection by its HELLO service tag, and the full
  wrong-service dial matrix fails loudly through the one shared
  ``wire.hello_answer`` path, naming both ends.
- **Bounded threads** — 256 idle connections to a core-hosted service
  add ZERO threads to the process (the thread-per-connection cost the
  core retires), and the service still answers promptly underneath them.
  The native PS keeps its C++ loop but must pass the same
  high-concurrency gate: 256 idle conns, still serving, all accounted.
- **Slow-reader write buffering** — a peer that stops reading its
  responses buffers bytes on its connection; it never wedges a handler
  worker (other clients stay fast even with every-worker's-worth of
  stalled peers).
- **Drain-then-stop** — a request in flight when ``stop()`` is called is
  answered, complete, before the listener dies: zero dropped in-flight
  requests on a graceful stop.
- **Accept-path hardening** — injected transient accept failures
  (``ECONNABORTED``, ``EMFILE``) log + back off and the listener keeps
  serving; they never kill the accept path.
- **Uniform accounting** — one STATS shape (``requests`` /
  ``live_conns``) and one observability-ops-don't-count rule across ALL
  THREE services: dsvc, msrv and the native PS answer the same counters
  with the same control-op exclusion semantics (wire.CONTROL_OPS).
"""

from __future__ import annotations

import errno
import json
import socket
import struct
import threading
import time

import numpy as np
import pytest

from distributed_tensorflow_examples_tpu.data import data_service as dsvc_lib
from distributed_tensorflow_examples_tpu.parallel import (
    ps_service,
    server_core,
    tenancy,
    wire,
)

pytestmark = pytest.mark.usefixtures("no_fault_plan")


@pytest.fixture
def no_fault_plan(monkeypatch):
    monkeypatch.delenv("DTX_FAULT_PLAN", raising=False)


# ----------------------------------------------------------------------------
# Raw-wire helpers (deliberately not the service clients: these tests pin
# the frame-level behavior of the runtime itself)
# ----------------------------------------------------------------------------


def _dial(port: int, service: str = "", timeout: float = 10.0,
          rcvbuf: int | None = None) -> socket.socket:
    """``rcvbuf`` is set BEFORE the connect, so the small window is the one
    the handshake advertises.  Shrinking SO_RCVBUF on an established
    loopback connection leaves the sender holding the 64 KB window it first
    saw: its silly-window avoidance then waits for half of THAT to open,
    which an 8 KB buffer never does, and bytes move only when the persist
    timer fires (measured here: 40 KB/s, 20 s an 800 KB reply, then
    stalls past 30 s as the timer backs off)."""
    s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    if rcvbuf is not None:
        s.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, rcvbuf)
    s.settimeout(timeout)
    s.connect(("127.0.0.1", port))
    s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    if service:
        st, _ = _call(s, wire.HELLO_OP, a=wire.WIRE_VERSION,
                      b=wire.pack_hello_b(0, service=service))
        assert st == wire.WIRE_VERSION, f"HELLO refused: {st}"
    return s


def _send_req(s, op, name="", a=0, b=0, payload=b"") -> None:
    s.sendall(wire.pack_request(op, name, a, b, len(payload)) + payload)


def _read_resp(s) -> tuple[int, bytes]:
    hdr = bytearray(wire.RESP_HDR.size)
    wire.recv_exact(s, memoryview(hdr))
    status, nbytes = wire.RESP_HDR.unpack(hdr)
    buf = bytearray(nbytes)
    if nbytes:
        wire.recv_exact(s, memoryview(buf))
    return status, bytes(buf)


def _call(s, op, name="", a=0, b=0, payload=b"") -> tuple[int, bytes]:
    _send_req(s, op, name, a, b, payload)
    return _read_resp(s)


# ----------------------------------------------------------------------------
# Handler-table dispatch + the wrong-service HELLO matrix
# ----------------------------------------------------------------------------


def _echo_core(**kw) -> server_core.ServerCore:
    """One core hosting BOTH Python services on ONE port: each handler
    answers its service id so the test can see which table entry ran."""
    core = server_core.ServerCore(name="test", workers=2, **kw)

    def handler_for(svc):
        def handle(conn, op, name, a, b, payload):
            return wire.SERVICE_IDS[svc], [f"{svc}:{op}".encode()]
        return handle

    core.add_service(server_core.Service("dsvc", handler_for("dsvc")))
    core.add_service(server_core.Service("msrv", handler_for("msrv")))
    return core.start()


def test_handler_table_routes_by_hello_service_tag():
    core = _echo_core()
    try:
        for svc, op in (("dsvc", 64), ("msrv", 96)):
            s = _dial(core.port, svc)
            status, raw = _call(s, op, a=7)
            assert status == wire.SERVICE_IDS[svc]
            assert raw == f"{svc}:{op}".encode()
            s.close()
    finally:
        core.stop()


def test_hello_answers_the_routed_services_tag():
    core = _echo_core()
    try:
        for svc in ("dsvc", "msrv"):
            s = socket.create_connection(("127.0.0.1", core.port), timeout=5)
            st, tag = _call(s, wire.HELLO_OP, a=wire.WIRE_VERSION,
                            b=wire.pack_hello_b(0, service=svc))
            assert st == wire.WIRE_VERSION
            assert tag == wire.SERVICE_TAGS[svc]
            s.close()
    finally:
        core.stop()


def test_wrong_service_hello_matrix_fails_loudly():
    """Every wrong pairing against single-service cores is refused with a
    status naming the service actually reached — the shared
    ``hello_answer`` refusal, now issued by the core."""
    core = server_core.ServerCore(name="only-dsvc", workers=1)
    core.add_service(server_core.Service(
        "dsvc", lambda conn, op, name, a, b, p: (0, None)
    ))
    core.start()
    try:
        s = socket.create_connection(("127.0.0.1", core.port), timeout=5)
        st, _ = _call(s, wire.HELLO_OP, a=wire.WIRE_VERSION,
                      b=wire.pack_hello_b(0, service="msrv"))
        assert wire.unpack_wrong_service(st) == "dsvc"
        # The shared client-side verdict names both ends.
        err = wire.hello_failure(
            st, None, service="msrv", host="127.0.0.1", port=core.port
        )
        assert err is not None and "data service" in err and "msrv" in err
        s.close()
    finally:
        core.stop()


def test_version_mismatch_refused():
    core = _echo_core()
    try:
        s = socket.create_connection(("127.0.0.1", core.port), timeout=5)
        st, _ = _call(s, wire.HELLO_OP, a=wire.WIRE_VERSION + 1,
                      b=wire.pack_hello_b(0, service="dsvc"))
        assert st == -1
        s.close()
    finally:
        core.stop()


def test_async_handler_replies_from_another_thread():
    """The ASYNC path: a handler that hands the reply to another thread
    (the serve batcher shape) still answers, in order."""
    done = threading.Event()
    core = server_core.ServerCore(name="async", workers=1)

    def handle(conn, op, name, a, b, payload):
        def later():
            done.wait(5.0)
            conn.reply(a * 2, [b"later"])
        threading.Thread(target=later, daemon=True).start()
        return server_core.ASYNC

    core.add_service(server_core.Service("dsvc", handle))
    core.start()
    try:
        s = _dial(core.port, "dsvc")
        _send_req(s, 64, a=21)
        done.set()
        status, raw = _read_resp(s)
        assert status == 42 and raw == b"later"
        s.close()
    finally:
        core.stop()


def test_handler_exception_answers_error_status_not_close():
    core = server_core.ServerCore(name="boom", workers=1)

    def handle(conn, op, name, a, b, payload):
        raise RuntimeError("handler bug")

    core.add_service(server_core.Service("dsvc", handle, error_status=-2))
    core.start()
    try:
        s = _dial(core.port, "dsvc")
        status, _ = _call(s, 64)
        assert status == -2  # loud per-op error, connection still alive
        status, _ = _call(s, 64)
        assert status == -2
        assert core.core_stats()["handler_errors"] == 2
        s.close()
    finally:
        core.stop()


# ----------------------------------------------------------------------------
# 256 idle connections: bounded threads, every service still serving
# ----------------------------------------------------------------------------


def test_256_idle_connections_hold_a_fixed_thread_count():
    srv = dsvc_lib.DataServiceServer(
        [{"x": np.arange(8, dtype=np.float32)}], batch_size=2, shuffle=False,
    )
    conns = []
    try:
        threads_before = threading.active_count()
        for _ in range(256):
            conns.append(_dial(srv.port, "dsvc"))
        # The C10k claim: idle connections cost file descriptors, not
        # threads.  (Thread-per-connection would have added 256 here.)
        assert threading.active_count() == threads_before
        assert srv._core.live_conns() == 256
        # And the service still answers promptly underneath them.
        probe = _dial(srv.port, "dsvc")
        t0 = time.monotonic()
        status, raw = _call(probe, dsvc_lib.DSVC_STATS)
        assert status == dsvc_lib.OK
        assert time.monotonic() - t0 < 2.0
        stats = json.loads(raw)
        assert stats["live_conns"] == 257
        probe.close()
    finally:
        for c in conns:
            c.close()
        srv.stop()


def test_native_ps_passes_the_same_high_concurrency_gate():
    """The native PS keeps its C++ loop but must hold the same gate: 256
    idle connections, still answering, all visible in its STATS."""
    port = ps_service.start_server(0)
    conns = []
    try:
        for _ in range(256):
            conns.append(socket.create_connection(("127.0.0.1", port), 10.0))
        client = ps_service.PSClient("127.0.0.1", port, timeout_s=10.0)
        t0 = time.monotonic()
        stats = client.stats()
        assert time.monotonic() - t0 < 2.0
        assert stats["live_conns"] >= 257
        client.ping()
        client.close()
    finally:
        for c in conns:
            c.close()
        ps_service.stop_server(port)


# ----------------------------------------------------------------------------
# Slow readers buffer, they do not wedge workers
# ----------------------------------------------------------------------------


class _Limit:
    """A test's own time limit: ``left()`` is what remains of it, and fails
    the test once nothing does — so a reply that never comes costs this
    many seconds of a worker, not a socket timeout per read."""

    def __init__(self, seconds: float):
        self._end = time.monotonic() + seconds

    def left(self) -> float:
        left = self._end - time.monotonic()
        assert left > 0, "over the test's own time limit"
        return left


def test_slow_reader_buffers_instead_of_wedging_a_worker():
    """Stalled peers holding unread responses > the worker count must not
    stop other clients from being served — the reply path buffers on the
    connection (flushed by the selector), never blocks a worker in
    sendall."""
    limit = _Limit(30.0)
    payload = {"x": np.zeros(200_000, np.float32)}  # ~800 KB per answer
    core = server_core.ServerCore(name="slow", workers=2)
    core.add_service(server_core.Service(
        "dsvc", lambda conn, op, name, a, b, p: (0, wire.encode_batch(payload))
    ))
    core.start()
    stalled = []
    try:
        # MORE stalled peers than workers, each with several unread
        # responses outstanding: under thread-per-connection-with-sendall
        # (or worker-pool-with-sendall) this wedges the whole service.
        for _ in range(4):
            s = _dial(core.port, "dsvc", rcvbuf=4096)
            for _ in range(8):
                _send_req(s, 64)
            stalled.append(s)
        time.sleep(0.3)  # let the workers chew through the stalled queue
        live = _dial(core.port, "dsvc")
        t0 = time.monotonic()
        status, raw = _call(live, 64)
        dt = time.monotonic() - t0
        assert status == 0
        assert dt < 2.0, f"live client stalled {dt:.1f}s behind slow readers"
        live.close()
        # The stalled peers' responses are all still delivered in full
        # once they start reading (nothing dropped, framing intact).
        for s in stalled:
            for _ in range(8):
                s.settimeout(min(5.0, limit.left()))
                status, raw = _read_resp(s)
                assert status == 0
                assert np.array_equal(
                    wire.decode_batch_bytes(raw)["x"], payload["x"]
                )
    finally:
        for s in stalled:
            s.close()
        core.stop()


def test_slow_reader_past_the_buffer_bound_is_dropped_not_served():
    limit = _Limit(20.0)
    core = server_core.ServerCore(
        name="cap", workers=1, max_buffered_bytes=64 * 1024,
        slow_reader_grace_s=0.3,
    )
    # 12 unread replies of ~1 MB: more than the kernel will take off the
    # server's hands (the send buffer grows to tcp_wmem's ceiling, 4 MB
    # here and by Linux's default, and took the old 8 x 400 KB whole), so
    # the rest stands on the connection, past the bound.
    big = {"x": np.zeros(250_000, np.float32)}
    core.add_service(server_core.Service(
        "dsvc", lambda conn, op, name, a, b, p: (0, wire.encode_batch(big))
    ))
    core.start()
    s = None
    try:
        s = _dial(core.port, "dsvc", rcvbuf=4096)
        for _ in range(12):
            _send_req(s, 64)
        while not core.core_stats()["dropped_slow_readers"]:
            time.sleep(min(0.05, limit.left()))
        # Dropped means cut: the peer reads what the kernel already held
        # and then the end of the stream, never the twelve replies.
        got = 0
        try:
            for _ in range(12):
                s.settimeout(min(5.0, limit.left()))
                _read_resp(s)
                got += 1
        except ConnectionError:
            pass
        assert got < 12
    finally:
        if s is not None:
            s.close()
        core.stop()


def test_one_reply_larger_than_the_bound_is_delivered_to_a_reading_peer():
    """The drop is progress-gated: a single legitimate reply BIGGER than
    ``max_buffered_bytes`` streams to a peer that is actually reading —
    size alone never cuts the connection (the old send_frames path
    delivered replies of any size; the buffered path must too)."""
    core = server_core.ServerCore(
        name="bigreply", workers=1, max_buffered_bytes=64 * 1024,
        slow_reader_grace_s=30.0,
    )
    big = {"x": np.arange(1_000_000, dtype=np.float32)}  # ~4 MB >> 64 KB
    core.add_service(server_core.Service(
        "dsvc", lambda conn, op, name, a, b, p: (0, wire.encode_batch(big))
    ))
    core.start()
    try:
        s = _dial(core.port, "dsvc")
        s.settimeout(30.0)
        status, raw = _call(s, 64)
        assert status == 0
        got = wire.decode_batch_bytes(raw)
        assert np.array_equal(got["x"], big["x"])
        assert core.core_stats()["dropped_slow_readers"] == 0
        s.close()
    finally:
        core.stop()


# ----------------------------------------------------------------------------
# Drain-then-stop: zero dropped in-flight requests
# ----------------------------------------------------------------------------


def test_drain_then_stop_answers_the_in_flight_request():
    started = threading.Event()

    def handle(conn, op, name, a, b, payload):
        started.set()
        time.sleep(0.5)  # a genuinely in-flight handler when stop() lands
        return 123, [b"answered"]

    core = server_core.ServerCore(name="drain", workers=1)
    core.add_service(server_core.Service("dsvc", handle))
    core.start()
    s = _dial(core.port, "dsvc")
    _send_req(s, 64)
    assert started.wait(5.0)
    stopper = threading.Thread(target=core.stop)
    stopper.start()
    # The already-dispatched request completes and its full response
    # arrives even though stop() was called mid-handler.
    s.settimeout(10.0)
    status, raw = _read_resp(s)
    assert status == 123 and raw == b"answered"
    stopper.join(timeout=10.0)
    assert not stopper.is_alive()
    s.close()
    # And the port is actually released (a fresh bind succeeds).
    probe = socket.socket()
    probe.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    probe.bind(("127.0.0.1", core.port))
    probe.close()


def test_drain_reports_clean_completion():
    core = server_core.ServerCore(name="quiesce", workers=1)
    core.add_service(server_core.Service(
        "dsvc", lambda conn, op, name, a, b, p: (0, None)
    ))
    core.start()
    try:
        s = _dial(core.port, "dsvc")
        assert _call(s, 64)[0] == 0
        assert core.drain(timeout_s=5.0) is True
        # Draining: new connections are refused (the listener is down)...
        with pytest.raises(OSError):
            socket.create_connection(("127.0.0.1", core.port), timeout=1.0)
        s.close()
    finally:
        core.stop()


# ----------------------------------------------------------------------------
# Accept-path hardening: transient failures never kill the listener
# ----------------------------------------------------------------------------


class _FlakyListener:
    """Listener proxy injecting accept() failures (socket methods are
    read-only, so the core's listener handle is swapped for this)."""

    def __init__(self, sock, failures: list[int]):
        self._sock = sock
        self.failures = failures

    def accept(self):
        if self.failures:
            e = self.failures.pop(0)
            raise OSError(e, errno.errorcode.get(e, "E?"))
        return self._sock.accept()

    def __getattr__(self, item):
        return getattr(self._sock, item)


def test_transient_accept_errors_do_not_kill_the_listener():
    core = server_core.ServerCore(name="acc", workers=1, accept_backoff_s=0.1)
    core.add_service(server_core.Service(
        "dsvc", lambda conn, op, name, a, b, p: (0, None)
    ))
    failures = [errno.ECONNABORTED, errno.EMFILE]
    core._listener = _FlakyListener(core._listener, failures)
    core.start()
    try:
        # Both injected failures fire on the first connection attempts;
        # the listener survives both (ECONNABORTED skipped, EMFILE backed
        # off) and every client eventually connects and is served.
        for _ in range(3):
            s = _dial(core.port, "dsvc", timeout=15.0)
            assert _call(s, 64)[0] == 0
            s.close()
        assert not failures, "injected accept failures never fired"
        assert core.core_stats()["accept_errors"] == 2
        assert core.core_stats()["accepts"] >= 3
    finally:
        core.stop()


# ----------------------------------------------------------------------------
# Uniform accounting: one STATS shape, one ops-don't-count rule, all three
# services
# ----------------------------------------------------------------------------


def _scrape_twice_and_probe(make_scrape, read_requests):
    """The parity harness: two complete fresh-dial scrapes of an idle
    server must read the SAME request count (observation does not
    perturb ``die:after_reqs`` triggers), and one counted data-plane op
    must advance it by exactly 1."""
    make_scrape()
    before = read_requests()
    make_scrape()
    after = read_requests()
    return before, after


def test_control_op_exclusion_parity_across_all_three_services(tmp_path):
    import jax.numpy as jnp

    from distributed_tensorflow_examples_tpu import serve

    counts: dict[str, tuple[int, int, int]] = {}

    # dsvc --------------------------------------------------------------
    dsrv = dsvc_lib.DataServiceServer(
        [{"x": np.arange(8, dtype=np.float32)}], batch_size=2, shuffle=False,
    )
    try:
        def dsvc_scrape():
            c = dsvc_lib.DataServiceClient(
                "127.0.0.1", dsrv.port, worker_id=-1, reconnect_deadline_s=0.0,
            )
            st = c.stats()
            assert st["service"] == "dsvc"
            assert "requests" in st and "live_conns" in st  # one STATS shape
            c.close()

        b, a = _scrape_twice_and_probe(dsvc_scrape, dsrv.request_count)
        c = dsvc_lib.DataServiceClient(
            "127.0.0.1", dsrv.port, worker_id=3, reconnect_deadline_s=0.0,
        )  # REGISTER with a real worker id: exactly one counted op
        after_op = dsrv.request_count()
        c.close()
        counts["dsvc"] = (b, a, after_op)
    finally:
        dsrv.stop()

    # msrv --------------------------------------------------------------
    def init_fn(rng):
        return {"w": jnp.zeros((4, 2), jnp.float32)}

    def predict_fn(params, batch):
        return batch["x"] @ params["w"]

    port = ps_service.start_server(0)
    try:
        addrs = [("127.0.0.1", port)]
        from distributed_tensorflow_examples_tpu.parallel import ps_shard

        group = ps_shard.ShardedPSClients(addrs, role="t17_pub")
        pstore = ps_shard.ShardedParamStore(
            group, "params", ps_shard.ShardLayout(8, 1)
        )
        pstore.set(1, np.zeros(8, np.float32))
        msrv = serve.ModelReplicaServer(
            init_fn, predict_fn, addrs, membership=False, refresh_ms=20.0,
        )
        try:
            assert msrv.wait_for_model(30.0)

            def msrv_scrape():
                c = serve.ServeClient(
                    "127.0.0.1", msrv.port, reconnect_deadline_s=0.0,
                )
                st = c.stats()
                assert st["service"] == "msrv"
                assert "requests" in st and "live_conns" in st
                c.close()

            b, a = _scrape_twice_and_probe(msrv_scrape, msrv.request_count)
            c = serve.ServeClient(
                "127.0.0.1", msrv.port, reconnect_deadline_s=0.0,
            )
            c.predict({"x": np.zeros((1, 4), np.float32)})  # one counted op
            after_op = msrv.request_count()
            c.close()
            counts["msrv"] = (b, a, after_op)
        finally:
            msrv.stop()
            group.close()
    finally:
        ps_service.stop_server(port)

    # native ps ---------------------------------------------------------
    port = ps_service.start_server(0)
    try:
        def ps_scrape():
            c = ps_service.PSClient("127.0.0.1", port, timeout_s=10.0)
            st = c.stats()
            assert "requests" in st and "live_conns" in st
            c.close()

        b, a = _scrape_twice_and_probe(
            ps_scrape, lambda: ps_service.server_request_count(port)
        )
        c = ps_service.PSClient("127.0.0.1", port, timeout_s=10.0)
        c.ping()  # one counted data-plane op
        after_op = ps_service.server_request_count(port)
        c.close()
        counts["ps"] = (b, a, after_op)
    finally:
        ps_service.stop_server(port)

    # THE parity assertion: on every service, a full fresh-dial scrape
    # adds ZERO to the request counter, and one data-plane op adds
    # exactly one — the single observability-ops-don't-count rule.
    for svc, (before, after, after_op) in counts.items():
        assert after == before, f"{svc}: a scrape perturbed the counter"
        assert after_op == after + 1, (
            f"{svc}: one data-plane op advanced the counter by "
            f"{after_op - after}, not 1"
        )


def test_request_counter_is_the_core_counter():
    srv = dsvc_lib.DataServiceServer(
        [{"x": np.arange(8, dtype=np.float32)}], batch_size=2, shuffle=False,
    )
    try:
        assert srv.request_count() == srv._core.request_count()
        s = _dial(srv.port, "dsvc")
        _call(s, dsvc_lib.DSVC_HEARTBEAT, a=0)
        assert srv.request_count() == 1
        _call(s, dsvc_lib.DSVC_STATS)  # control op: uncounted
        assert srv.request_count() == 1
        s.close()
    finally:
        srv.stop()


# ----------------------------------------------------------------------------
# Frame parsing details the blocking reader used to get for free
# ----------------------------------------------------------------------------


def test_fragmented_frames_parse_and_pipelined_frames_answer_in_order():
    core = server_core.ServerCore(name="frag", workers=1)
    core.add_service(server_core.Service(
        "dsvc", lambda conn, op, name, a, b, p: (a, [p] if p else None)
    ))
    core.start()
    try:
        s = _dial(core.port, "dsvc")
        # One request dribbled a byte at a time...
        req = wire.pack_request(64, "nm", 5, 0, 3) + b"xyz"
        for i in range(len(req)):
            s.sendall(req[i : i + 1])
            time.sleep(0.001)
        status, raw = _read_resp(s)
        assert status == 5 and raw == b"xyz"
        # ...and three pipelined in one write answer in order.
        s.sendall(b"".join(
            wire.pack_request(64, "", i, 0, 0) for i in (1, 2, 3)
        ))
        assert [_read_resp(s)[0] for _ in range(3)] == [1, 2, 3]
        s.close()
    finally:
        core.stop()


def test_per_service_payload_bound_drops_before_buffering():
    """A frame announcing a payload past the SERVICE's bound (dsvc: no
    request carries one) drops at header time — the payload is never
    buffered, so a bogus length costs no memory."""
    srv = dsvc_lib.DataServiceServer(
        [{"x": np.arange(8, dtype=np.float32)}], batch_size=2, shuffle=False,
    )
    try:
        s = _dial(srv.port, "dsvc")
        s.sendall(struct.pack("<BB", dsvc_lib.DSVC_REGISTER, 0)
                  + wire.REQ_TAIL.pack(0, 0, 2 << 20))  # > the 1 MB bound
        s.settimeout(5.0)
        with pytest.raises((ConnectionError, socket.timeout, OSError)):
            _read_resp(s)
        s.close()
        # The service itself is untouched: a well-formed dial still works.
        probe = _dial(srv.port, "dsvc")
        assert _call(probe, dsvc_lib.DSVC_STATS)[0] == dsvc_lib.OK
        probe.close()
    finally:
        srv.stop()


def test_wedged_batch_thread_answers_timeout_err_and_frees_the_conn():
    """The r17 async-predict backstop: a wedged batch thread must not pin
    the connection in_flight forever — the refresher's ticket sweep
    resolves it with TimeoutError, the client reads a loud ERR, and the
    server still drains/stops promptly."""
    import jax.numpy as jnp

    from distributed_tensorflow_examples_tpu import serve
    from distributed_tensorflow_examples_tpu.parallel import ps_shard

    def init_fn(rng):
        return {"w": jnp.zeros((4, 2), jnp.float32)}

    def predict_fn(params, batch):
        return batch["x"] @ params["w"]

    port = ps_service.start_server(0)
    try:
        addrs = [("127.0.0.1", port)]
        group = ps_shard.ShardedPSClients(addrs, role="t17_wedge")
        pstore = ps_shard.ShardedParamStore(
            group, "params", ps_shard.ShardLayout(8, 1)
        )
        pstore.set(1, np.zeros(8, np.float32))
        srv = serve.ModelReplicaServer(
            init_fn, predict_fn, addrs, membership=False, refresh_ms=50.0,
            max_wait_ms=1.0,
        )
        try:
            assert srv.wait_for_model(30.0)
            srv._ticket_deadline_s = 0.5
            srv._batcher._run = lambda items: time.sleep(3.0) or []  # wedge
            c = serve.ServeClient(
                "127.0.0.1", srv.port, reconnect_deadline_s=0.0,
            )
            t0 = time.monotonic()
            with pytest.raises(serve.ServeRejectedError):
                c.predict({"x": np.zeros((1, 4), np.float32)})
            # Answered by the sweep, long before the wedge clears.
            assert time.monotonic() - t0 < 2.5
            c.close()
            # And the connection was freed: the core drains promptly.
            assert srv._core.drain(timeout_s=2.0) is True
        finally:
            srv.stop()
            group.close()
    finally:
        ps_service.stop_server(port)


def test_unserializable_predict_output_answers_err_not_a_wedged_conn():
    """The async-reply twin of the worker guard: an output the wire
    cannot encode answers a loud ERR — the connection stays usable and
    the server still drains (a swallowed encode failure used to leave
    the conn in_flight forever with no reply)."""
    import jax.numpy as jnp

    from distributed_tensorflow_examples_tpu import serve
    from distributed_tensorflow_examples_tpu.parallel import ps_shard

    def init_fn(rng):
        return {"w": jnp.zeros((4, 2), jnp.float32)}

    def predict_fn(params, batch):
        return batch["x"] @ params["w"]

    port = ps_service.start_server(0)
    try:
        addrs = [("127.0.0.1", port)]
        group = ps_shard.ShardedPSClients(addrs, role="t17_enc")
        pstore = ps_shard.ShardedParamStore(
            group, "params", ps_shard.ShardLayout(8, 1)
        )
        pstore.set(1, np.zeros(8, np.float32))
        srv = serve.ModelReplicaServer(
            init_fn, predict_fn, addrs, membership=False, refresh_ms=50.0,
            max_wait_ms=1.0,
        )
        try:
            assert srv.wait_for_model(30.0)
            # The apply "succeeds" but yields an output the wire codec
            # cannot move (object dtype has no byte view).
            srv._batcher._run = lambda items: [
                (5, {"y": np.empty(1, dtype=object)}) for _ in items
            ]
            c = serve.ServeClient(
                "127.0.0.1", srv.port, reconnect_deadline_s=0.0,
            )
            with pytest.raises(serve.ServeRejectedError):
                c.predict({"x": np.zeros((1, 4), np.float32)})
            # The SAME connection still answers — nothing wedged.
            assert c.stats()["service"] == "msrv"
            c.close()
            assert srv._core.drain(timeout_s=2.0) is True
        finally:
            srv.stop()
            group.close()
    finally:
        ps_service.stop_server(port)


# ----------------------------------------------------------------------------
# Admission control (r18): every shed path answers typed RETRY_LATER
# ----------------------------------------------------------------------------


def test_retry_later_band_roundtrips_and_misses_other_statuses():
    """The status band codec: every encodable hint roundtrips, and the
    statuses that LOOK negative (errors, shard-mismatch echoes far below
    the band) never decode as a shed."""
    for ms in (0, 1, 50, 600_000, 999_999):
        st = wire.retry_later_status(ms)
        assert wire.retry_after_ms(st) == min(ms, wire.RETRY_LATER_SPAN)
    for not_shed in (0, 1, -1, -2, -7, -999, wire.RETRY_LATER_BASE
                     - wire.RETRY_LATER_SPAN - 1, -5_000_000):
        assert wire.retry_after_ms(not_shed) is None


def test_deadline_stamped_frame_parses_and_unstamped_is_v3_identical():
    """The r18 deadline stamp: flagged frames carry one trailing <I
    field; un-stamped frames are byte-identical to the v3 layout."""
    plain = wire.pack_request(7, "nm", 1, 2, 3)
    stamped = wire.pack_request(7, "nm", 1, 2, 3, deadline_ms=1500)
    assert stamped[0] == 7 | wire.DEADLINE_FLAG
    assert plain[0] == 7
    assert len(stamped) == len(plain) + wire.DEADLINE_TAIL.size
    assert stamped[1:-wire.DEADLINE_TAIL.size] == plain[1:]
    (ms,) = wire.DEADLINE_TAIL.unpack(stamped[-wire.DEADLINE_TAIL.size:])
    assert ms == 1500
    # And the core's incremental parser reads both shapes.
    got, used = server_core.ServerCore._parse_header(bytearray(stamped))
    assert got == (7, "nm", 1, 2, 3, 1500) and used == len(stamped)
    got, used = server_core.ServerCore._parse_header(bytearray(plain))
    assert got == (7, "nm", 1, 2, 3, 0) and used == len(plain)


def _blocked_core(release: threading.Event, **kw):
    """A core whose dsvc handler BLOCKS until ``release`` fires — the
    saturated-worker-pool fixture for every shed test."""
    svc_kw = {
        k: kw.pop(k)
        for k in ("queue_deadline_s", "max_inflight_per_conn",
                  "retry_after_ms", "control_ops")
        if k in kw
    }
    core = server_core.ServerCore(name="shed", workers=1, **kw)

    def handle(conn, op, name, a, b, payload):
        if op != 65:  # 65 = the test's control/fast op: never blocks
            release.wait(30.0)
        return a, None

    core.add_service(server_core.Service("dsvc", handle, **svc_kw))
    return core.start()


def _wait_stat(core, key, minimum=1, timeout=10.0) -> int:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        v = core.core_stats()[key]
        if v >= minimum:
            return v
        time.sleep(0.02)
    return core.core_stats()[key]


def test_inflight_cap_sheds_pipelined_excess_in_order():
    """Per-connection in-flight cap: pipelined excess on ONE connection
    answers typed RETRY_LATER (hint included), response order preserved,
    and the cause-split counters fold into core_stats()."""
    release = threading.Event()
    core = _blocked_core(
        release, max_inflight_per_conn=2, retry_after_ms=70,
    )
    try:
        s = _dial(core.port, "dsvc")
        for i in range(6):
            _send_req(s, 64, a=i)
        # 2 dispatched (cap), the rest shed the moment they parse.
        assert _wait_stat(core, "shed_inflight_cap", 4) == 4
        release.set()
        statuses = [_read_resp(s)[0] for _ in range(6)]
        # In order: the two admitted echo their operand, the shed four
        # answer the RETRY_LATER band carrying the service's hint.
        assert statuses[:2] == [0, 1]
        for st in statuses[2:]:
            assert wire.retry_after_ms(st) == 70
        stats = core.core_stats()
        assert stats["shed_total"] == 4
        assert stats["shed_inflight_cap"] == 4
        assert stats["shed_dispatch_full"] == 0
        assert stats["queue_deadline_drops"] == 0
        # The connection is NOT poisoned: the same socket still serves.
        assert _call(s, 64, a=9)[0] == 9
        s.close()
    finally:
        release.set()
        core.stop()


def test_dispatch_queue_bound_sheds_across_connections():
    """The core-wide dispatch bound: once the queue is full, a NEW
    connection's request sheds instead of queueing unboundedly."""
    release = threading.Event()
    core = _blocked_core(release, max_dispatch_depth=1)
    conns = []
    try:
        # First request occupies the one worker; the queue then holds at
        # most 1; further requests shed with the dispatch-full cause.
        for i in range(4):
            s = _dial(core.port, "dsvc")
            _send_req(s, 64, a=i)
            conns.append(s)
        assert _wait_stat(core, "shed_dispatch_full", 2) >= 2
        # The shed answers arrive NOW, while the worker is still wedged —
        # admission refusals never wait on handler progress.  (WHICH two
        # connections shed depends on parse order, so select for the
        # readable ones.)
        import select

        readable, _, _ = select.select(conns, [], [], 5.0)
        assert len(readable) >= 2
        sheds = 0
        for s in readable:
            s.settimeout(5.0)
            if wire.retry_after_ms(_read_resp(s)[0]) is not None:
                sheds += 1
        assert sheds >= 2
        release.set()
        served = 0
        for s in (c for c in conns if c not in readable):
            s.settimeout(10.0)
            if wire.retry_after_ms(_read_resp(s)[0]) is None:
                served += 1
        assert served >= 1  # the dispatched request really completed
    finally:
        release.set()
        for s in conns:
            s.close()
        core.stop()


def test_queue_deadline_policy_sheds_waiting_requests():
    """A request that waited past the SERVICE's queue-deadline budget is
    shed before a worker touches it — even while every worker is wedged
    (the selector sweep answers it)."""
    release = threading.Event()
    core = _blocked_core(release, queue_deadline_s=0.2)
    a = b = None
    try:
        a = _dial(core.port, "dsvc")
        _send_req(a, 64, a=1)  # occupies the one worker
        time.sleep(0.1)
        b = _dial(core.port, "dsvc")
        _send_req(b, 64, a=2)  # queued behind the wedge
        b.settimeout(10.0)
        t0 = time.monotonic()
        status, _ = _read_resp(b)  # answered by the ~1/s sweep
        assert wire.retry_after_ms(status) is not None
        assert time.monotonic() - t0 < 5.0
        stats = core.core_stats()
        assert stats["queue_deadline_drops"] == 1
        assert stats["shed_total"] == 1
        release.set()
        a.settimeout(10.0)
        assert _read_resp(a)[0] == 1  # the dispatched request completes
    finally:
        release.set()
        for s in (a, b):
            if s is not None:
                s.close()
        core.stop()


def test_caller_stamped_deadline_sheds_abandoned_work():
    """Deadline propagation: with NO service policy, the deadline the
    CALLER stamped on the frame alone sheds the request once it expires
    in the queue — servers do not burn workers on abandoned work."""
    release = threading.Event()
    core = _blocked_core(release)  # queue_deadline_s=None: stamp only
    a = b = None
    try:
        a = _dial(core.port, "dsvc")
        _send_req(a, 64, a=1)
        time.sleep(0.1)
        b = _dial(core.port, "dsvc")
        b.sendall(wire.pack_request(64, "", 2, 0, 0, deadline_ms=150))
        b.settimeout(10.0)
        status, _ = _read_resp(b)
        assert wire.retry_after_ms(status) is not None
        assert core.core_stats()["queue_deadline_drops"] == 1
        release.set()
    finally:
        release.set()
        for s in (a, b):
            if s is not None:
                s.close()
        core.stop()


def test_control_ops_never_shed_under_saturated_pool():
    """Priority classes: with the worker wedged AND the dispatch queue
    full AND the in-flight cap at 1, a control op on the SAME connection
    still answers promptly (dedicated control worker + cap/bound
    exemption) — under saturation the cluster stays observable."""
    release = threading.Event()
    core = _blocked_core(
        release, max_dispatch_depth=1, max_inflight_per_conn=1,
        control_ops=frozenset({65}),
    )
    extra = []
    try:
        s = _dial(core.port, "dsvc")
        _send_req(s, 64, a=1)  # wedges the one regular worker
        time.sleep(0.1)
        # Fill the dispatch queue from another connection.
        q = _dial(core.port, "dsvc")
        _send_req(q, 64, a=2)
        extra.append(q)
        # Control op from a THIRD connection: bypasses the full queue,
        # rides the priority lane, answered by the control worker.
        c = _dial(core.port, "dsvc")
        extra.append(c)
        t0 = time.monotonic()
        c.settimeout(5.0)
        status, _ = _call(c, 65, a=7)
        dt = time.monotonic() - t0
        assert status == 7, "control op was shed or misrouted"
        assert dt < 2.0, f"control op stalled {dt:.1f}s behind saturation"
        # And NONE of the shed counters moved for it.
        assert core.core_stats()["shed_total"] == 0
        release.set()
    finally:
        release.set()
        for x in extra + [s]:
            x.close()
        core.stop()


def test_stats_scrape_answers_while_predict_sheds():
    """The msrv end-to-end shape: a hammered replica sheds predicts with
    the typed hint, and a STATS scrape DURING the storm answers promptly
    with the shed counters in the uniform top-level shape."""
    import jax.numpy as jnp

    from distributed_tensorflow_examples_tpu import serve
    from distributed_tensorflow_examples_tpu.parallel import ps_shard

    def init_fn(rng):
        return {"w": jnp.zeros((4, 2), jnp.float32)}

    def predict_fn(params, batch):
        return batch["x"] @ params["w"]

    port = ps_service.start_server(0)
    try:
        addrs = [("127.0.0.1", port)]
        group = ps_shard.ShardedPSClients(addrs, role="t18_shed")
        pstore = ps_shard.ShardedParamStore(
            group, "params", ps_shard.ShardLayout(8, 1)
        )
        pstore.set(1, np.zeros(8, np.float32))
        srv = serve.ModelReplicaServer(
            init_fn, predict_fn, addrs, membership=False, refresh_ms=20.0,
            max_batch=1, max_wait_ms=1.0, queue_depth=1,
        )
        try:
            assert srv.wait_for_model(30.0)
            srv._batcher._run = lambda items: time.sleep(0.2) or [
                (1, {"y": np.zeros((1, 2), np.float32)}) for _ in items
            ]
            overloads = [0]
            stop = threading.Event()

            def hammer(i):
                c = serve.ServeClient(
                    "127.0.0.1", srv.port, role=f"h{i}_sv",
                    reconnect_deadline_s=0.0,
                )
                while not stop.is_set():
                    try:
                        c.predict({"x": np.zeros((1, 4), np.float32)})
                    except serve.ServeOverloadError as e:
                        overloads[0] += 1
                        # The typed hint rode in on the status band.
                        assert e.retry_after_s > 0
                    except serve.ServeError:
                        pass
                c.close()

            ts = [threading.Thread(target=hammer, args=(i,))
                  for i in range(4)]
            for t in ts:
                t.start()
            try:
                # STATS scrapes DURING the storm: prompt, with the shed
                # telemetry visible in the uniform top-level shape.
                deadline = time.monotonic() + 10.0
                seen_overload = False
                while time.monotonic() < deadline and not seen_overload:
                    sc = serve.ServeClient(
                        "127.0.0.1", srv.port, role="scrape_sv",
                        reconnect_deadline_s=0.0,
                    )
                    t0 = time.monotonic()
                    st = sc.stats()
                    assert time.monotonic() - t0 < 2.0
                    assert "shed_total" in st
                    assert "queue_deadline_drops" in st
                    sc.close()
                    seen_overload = st["overloads"] >= 1
                assert seen_overload, "hammer never tripped admission"
            finally:
                stop.set()
                for t in ts:
                    t.join(timeout=15.0)
            assert overloads[0] >= 1
        finally:
            srv.stop()
            group.close()
    finally:
        ps_service.stop_server(port)


def test_native_ps_sheds_blocking_op_with_exhausted_stamp():
    """The native mirror: a blocking op whose stamped deadline budget is
    below the minimum useful wait answers the same typed RETRY_LATER
    band, and the shed shows in the PS's STATS counters."""
    port = ps_service.start_server(0)
    s = None
    try:
        client = ps_service.PSClient("127.0.0.1", port, timeout_s=10.0)
        ps_service.RemoteAccumulator(client, "acc0", 4)
        client.close()
        # Raw dial: stamp a 1ms deadline on a would-block ACC_TAKE — the
        # server must shed it (typed, with hint) instead of parking a
        # thread it knows the caller will abandon.
        s = socket.create_connection(("127.0.0.1", port), timeout=10.0)
        s.sendall(wire.pack_request(
            wire.PS_OPS["ACC_TAKE"], "acc0", 1, 5_000, 0, deadline_ms=1,
        ))
        status, _ = _read_resp(s)
        hint = wire.retry_after_ms(status)
        assert hint is not None and hint > 0
        c2 = ps_service.PSClient("127.0.0.1", port, timeout_s=10.0)
        st = c2.stats()
        assert st["shed_total"] >= 1
        assert st["queue_deadline_drops"] >= 1
        c2.close()
    finally:
        if s is not None:
            s.close()
        ps_service.stop_server(port)


def test_oversize_frame_announcement_drops_the_connection():
    core = server_core.ServerCore(name="huge", workers=1)
    core.add_service(server_core.Service(
        "dsvc", lambda conn, op, name, a, b, p: (0, None)
    ))
    core.start()
    try:
        s = _dial(core.port, "dsvc")
        s.sendall(struct.pack("<BB", 64, 0) + wire.REQ_TAIL.pack(
            0, 0, server_core.MAX_FRAME_BYTES + 1
        ))
        s.settimeout(5.0)
        with pytest.raises((ConnectionError, socket.timeout, OSError)):
            _read_resp(s)
        s.close()
    finally:
        core.stop()


# ----------------------------------------------------------------------------
# Per-tenant admission: weighted-fair dispatch + quotas (r20)
# ----------------------------------------------------------------------------


def _tenant_core(release: threading.Event, order: list, **core_kw):
    """One-worker core whose handler blocks until ``release`` and records
    each dispatched request's tenant — the dispatch-order probe for the
    stride scheduler.  Tenants ride the dsvc name tag."""
    lock = threading.Lock()
    core = server_core.ServerCore(name="tshed", workers=1, **core_kw)

    def handle(conn, op, name, a, b, payload):
        release.wait(30.0)
        with lock:
            order.append(tenancy.untag_name(name)[1])
        return a, None

    core.add_service(server_core.Service(
        "dsvc", handle,
        tenant_of=lambda op, name, a, b: tenancy.untag_name(name)[1],
        retry_after_ms=90,
    ))
    return core.start()


def _wait_tenant_queued(core, tenant, n, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        row = core.core_stats()["tenants"].get(tenant)
        if row and row["queued"] >= n:
            return
        time.sleep(0.02)
    raise AssertionError(
        f"{tenant} never reached {n} queued: {core.core_stats()['tenants']}"
    )


def test_weighted_fair_dispatch_follows_the_stride_weights():
    """Under saturation a 3:1 weight split dispatches 3:1: of the first 8
    backlogged requests served, EXACTLY 6 are the heavy tenant's — the
    stride invariant, independent of arrival/tie order."""
    release = threading.Event()
    order: list[str] = []
    core = _tenant_core(
        release, order,
        tenant_quotas={"runa": tenancy.TenantQuota(weight=3.0)},
    )
    sa = sb = w = None
    try:
        w = _dial(core.port, "dsvc")
        _send_req(w, 64, name=tenancy.tag_name("", "wedge"))  # occupies the worker
        time.sleep(0.1)
        sa = _dial(core.port, "dsvc")
        sb = _dial(core.port, "dsvc")
        for i in range(8):
            _send_req(sa, 64, name=tenancy.tag_name("", "runa"), a=i)
            _send_req(sb, 64, name=tenancy.tag_name("", "runb"), a=i)
        _wait_tenant_queued(core, "runa", 8)
        _wait_tenant_queued(core, "runb", 8)
        release.set()
        for s in (w, sa, sb):
            s.settimeout(20.0)
        _read_resp(w)
        for _ in range(8):
            _read_resp(sa)
            _read_resp(sb)
        # order[0] is the wedge; the next 8 are the contested window.
        window = order[1:9]
        assert window.count("runa") == 6 and window.count("runb") == 2, order
        stats = core.core_stats()
        assert stats["tenants"]["runa"]["weight"] == 3.0
        assert stats["tenants"]["runa"]["requests"] == 8
        assert stats["shed_total"] == 0
    finally:
        release.set()
        for s in (w, sa, sb):
            if s is not None:
                s.close()
        core.stop()


def test_tenant_quota_sheds_only_the_capped_tenant():
    """A tenant at its in-flight cap answers typed RETRY_LATER (hint
    included) while the other tenant's identical traffic flows — and the
    cause lands in the per-tenant ``shed_quota`` counter, not the
    neighbors'."""
    release = threading.Event()
    order: list[str] = []
    core = _tenant_core(
        release, order,
        tenant_quotas={"runa": tenancy.TenantQuota(max_inflight=2)},
    )
    sa = sb = w = None
    try:
        w = _dial(core.port, "dsvc")
        _send_req(w, 64, name=tenancy.tag_name("", "wedge"))
        time.sleep(0.1)
        sa = _dial(core.port, "dsvc")
        sb = _dial(core.port, "dsvc")
        for i in range(5):
            _send_req(sa, 64, name=tenancy.tag_name("", "runa"), a=i)
        for i in range(3):
            _send_req(sb, 64, name=tenancy.tag_name("", "runb"), a=i)
        assert _wait_stat(core, "shed_quota", 3) == 3
        sa.settimeout(20.0)
        sb.settimeout(20.0)
        # The shed answers arrive NOW, while the worker is still wedged,
        # in sequence order behind runa's two admitted requests' replies —
        # so release first, then read runa's stream in order.
        release.set()
        statuses_a = [_read_resp(sa)[0] for _ in range(5)]
        assert statuses_a[:2] == [0, 1]  # the two admitted requests served
        for st in statuses_a[2:]:
            assert wire.retry_after_ms(st) == 90  # the service hint
        # The neighbor tenant flowed untouched.
        assert [_read_resp(sb)[0] for _ in range(3)] == [0, 1, 2]
        stats = core.core_stats()
        assert stats["tenants"]["runa"]["shed_quota"] == 3
        assert stats["tenants"]["runa"]["max_inflight"] == 2
        assert stats["tenants"]["runb"]["shed_total"] == 0
        assert stats["shed_quota"] == 3 and stats["shed_total"] == 3
    finally:
        release.set()
        for s in (w, sa, sb):
            if s is not None:
                s.close()
        core.stop()


def test_tenant_dispatch_quota_caps_the_queue_not_the_neighbors():
    """``max_dispatch`` bounds how much BACKLOG one tenant may queue:
    excess sheds at parse time while an uncapped tenant queues freely."""
    release = threading.Event()
    order: list[str] = []
    core = _tenant_core(
        release, order,
        tenant_quotas={"runa": tenancy.TenantQuota(max_dispatch=1)},
    )
    sa = sb = w = None
    try:
        w = _dial(core.port, "dsvc")
        _send_req(w, 64, name=tenancy.tag_name("", "wedge"))
        time.sleep(0.1)
        sa = _dial(core.port, "dsvc")
        sb = _dial(core.port, "dsvc")
        for i in range(4):
            _send_req(sa, 64, name=tenancy.tag_name("", "runa"), a=i)
            _send_req(sb, 64, name=tenancy.tag_name("", "runb"), a=i)
        assert _wait_stat(core, "shed_quota", 3) == 3
        stats = core.core_stats()
        assert stats["tenants"]["runa"]["shed_quota"] == 3
        assert stats["tenants"]["runb"]["queued"] == 4
        release.set()
    finally:
        release.set()
        for s in (w, sa, sb):
            if s is not None:
                s.close()
        core.stop()
