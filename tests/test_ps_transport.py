"""Wire-format matrix for the PS transport fast path (r7 tentpole).

Covers the protocol surface the zero-copy/versioned/bf16 overhaul touched:
round trips for every payload-carrying op x {f32, bf16} x {empty, small,
multi-MB} payloads, HELLO version negotiation (a mismatched peer fails the
CONNECT loudly instead of misparsing frames mid-stream), ``get_if_newer``
semantics (fresh step -> payload, same step -> status-only) including
across a server restart.
"""

from __future__ import annotations

import socket
import struct
import threading

import numpy as np
import pytest

from distributed_tensorflow_examples_tpu.parallel import ps_service


def _bf16_exact(n: int) -> np.ndarray:
    """Values exactly representable in bf16 (small integers), so bf16-wire
    round trips compare EXACTLY — a tolerance here could mask a framing bug
    as quantization."""
    return ((np.arange(n) % 251) - 125).astype(np.float32)


@pytest.fixture()
def server_port():
    port = ps_service.start_server(0)
    yield port
    ps_service.stop_server()


@pytest.mark.parametrize("dtype", ["f32", "bf16"])
# 64 elements exercises the small frames; 1M elements (4 MB f32 / 2 MB
# bf16 on the wire) the partial-read/partial-write paths.  The {empty}
# column is the payload-less ops (ping/incarnation/token ops) inside each.
@pytest.mark.parametrize("n", [64, 1_000_000])
def test_wire_roundtrip_matrix(server_port, dtype, n):
    c = ps_service.PSClient(
        "127.0.0.1", server_port, timeout_s=60.0, wire_dtype=dtype,
        worker_tag=3,
    )
    g = _bf16_exact(n)

    # Payload-less ops (the {empty} column): ping / incarnation / cancel.
    c.ping()
    assert c.incarnation() > 0

    # Accumulator: tagged apply (worker_tag client) + timed take.
    acc = ps_service.RemoteAccumulator(c, "acc", n)
    assert acc.apply(0, g)
    assert acc.apply(0, g)
    out = acc.take(2)
    np.testing.assert_array_equal(out, g)
    assert acc.take(1, timeout_s=0.05) is ps_service.TIMED_OUT
    assert acc.dropped == 0 and acc.deduped == 0

    # Token queue (empty payloads both ways, status carries the data).
    tq = ps_service.RemoteTokenQueue(c, "tq")
    tq.push(7, n=2)
    assert tq.pop() == 7 and tq.pop() == 7

    # Gradient queue: tagged push + pop round trip.
    gq = ps_service.RemoteGradientQueue(c, "gq", n, capacity=4)
    assert gq.push(5, g) is True
    step, got = gq.pop()
    assert step == 5
    np.testing.assert_array_equal(got, g)

    # Param store: set / full get / versioned get.
    ps = ps_service.RemoteParamStore(c, "p", n)
    ps.set(3, g)
    s, v = ps.get()
    assert s == 3
    np.testing.assert_array_equal(v, g)
    s2, v2 = ps.get()  # unchanged: served from the client cache
    assert s2 == 3 and v2 is v
    ps.set(4, 2 * g)
    s3, v3 = ps.get()
    assert s3 == 4
    np.testing.assert_array_equal(v3, 2 * g)
    c.close()


def test_bf16_codec_matches_server(server_port):
    """Client and server convert independently (numpy vs C++): a full
    set->get round trip through the bf16 wire must equal the PYTHON codec's
    own round trip bit-for-bit, on awkward values (subnormals, inf, NaN,
    rounding cases) — otherwise the two ends disagree on quantization."""
    x = np.array(
        [1.1, -0.3337, 3.4e38, 1e-40, np.inf, -np.inf, np.nan, 0.0, -0.0],
        np.float32,
    )
    expect = ps_service._bf16_to_f32(ps_service._f32_to_bf16(x))
    c = ps_service.PSClient("127.0.0.1", server_port, timeout_s=30.0,
                            wire_dtype="bf16")
    ps = ps_service.RemoteParamStore(c, "codec", x.size, cache_pulls=False)
    ps.set(1, x)  # client downconverts; server upconverts + stores f32
    _, got = ps.get()  # server downconverts; client upconverts
    np.testing.assert_array_equal(
        np.asarray(got).view(np.uint32), expect.view(np.uint32)
    )
    c.close()


class _FakeServer(threading.Thread):
    """Answers every request with a fixed status and empty payload (v1
    framing) — stands in for a peer that doesn't (or wrongly) speaks the
    negotiated wire version."""

    def __init__(self, status: int):
        super().__init__(daemon=True)
        self._status = status
        self._sock = socket.socket()
        self._sock.bind(("127.0.0.1", 0))
        self._sock.listen(4)
        self.port = self._sock.getsockname()[1]
        self._conns: list = []

    def run(self):
        while True:
            try:
                conn, _ = self._sock.accept()
            except OSError:
                return
            self._conns.append(conn)
            threading.Thread(
                target=self._serve, args=(conn,), daemon=True
            ).start()

    def _serve(self, conn):
        try:
            while True:
                hdr = conn.recv(2)
                if len(hdr) < 2:
                    return
                body = b""
                need = hdr[1] + 20
                while len(body) < need:
                    chunk = conn.recv(need - len(body))
                    if not chunk:
                        return
                    body += chunk
                conn.sendall(struct.pack("<qI", self._status, 0))
        except OSError:
            return

    def stop(self):
        for s in [self._sock, *self._conns]:
            try:
                s.close()
            except OSError:
                pass


@pytest.mark.parametrize(
    "peer_status, blurb",
    [(-2, "pre-v2 server answers unknown-op"), (999, "wrong version echoed")],
)
def test_bf16_rejects_mismatched_peer(peer_status, blurb):
    """A non-f32 encoding REQUIRES the negotiated version: a peer that
    can't (or mis-) speaks wire v2 must fail the connection with a clear
    PSError — never silently misparse bf16 frames."""
    srv = _FakeServer(status=peer_status)
    srv.start()
    try:
        with pytest.raises(ps_service.PSError, match="wire"):
            ps_service.PSClient(
                "127.0.0.1", srv.port, timeout_s=5.0, wire_dtype="bf16"
            )
    finally:
        srv.stop()


def test_bf16_mismatch_is_permanent_not_retried():
    """Version mismatch must NOT be retried by the reconnect machinery — a
    recovering client burns its whole backoff budget against a peer that
    will never agree.  The ctor must fail fast with the negotiation error."""
    import time

    srv = _FakeServer(status=-2)
    srv.start()
    try:
        t0 = time.monotonic()
        with pytest.raises(ps_service.PSError, match="wire"):
            ps_service.PSClient(
                "127.0.0.1", srv.port, op_timeout_s=5.0,
                reconnect_deadline_s=60.0, wire_dtype="bf16",
            )
        assert time.monotonic() - t0 < 10.0, "mismatch was retried"
    finally:
        srv.stop()


def test_f32_client_interops_with_v1_framing():
    """f32 framing is byte-identical to wire v1, so an f32 client must work
    against a peer that knows nothing of HELLO (the _FakeServer answers -2
    to everything, which PING surfaces as a clean error, not a misparse)."""
    srv = _FakeServer(status=0)
    srv.start()
    try:
        c = ps_service.PSClient("127.0.0.1", srv.port, timeout_s=5.0)
        c.ping()  # status 0 == pong: no HELLO was needed
        c.close()
    finally:
        srv.stop()


def test_get_if_newer_wire_semantics(server_port):
    """The raw op contract: fresh step -> status=step + full payload; same
    (or older-than-cached) step -> status-only, EMPTY payload — the
    O(header) unchanged-step pull the acceptance criteria require."""
    n = 4096
    c = ps_service.PSClient("127.0.0.1", server_port, timeout_s=30.0)
    ps = ps_service.RemoteParamStore(c, "p", n, cache_pulls=False)
    # Never published: status-only -1.
    s, out = c.call(ps_service._PSTORE_GET_IF_NEWER, "p", 5)
    assert s == -1 and out.size == 0
    ps.set(7, np.ones(n, np.float32))
    # have_step behind: full payload.
    s, out = c.call(ps_service._PSTORE_GET_IF_NEWER, "p", 6)
    assert s == 7 and out.size == n
    # have_step current (and ahead): status-only.
    for have in (7, 8):
        s, out = c.call(ps_service._PSTORE_GET_IF_NEWER, "p", have)
        assert s == 7 and out.size == 0
    c.close()


def test_param_cache_across_server_restart(server_port):
    """The client cache must not survive a transport gap: a reconnect
    invalidates it (on_reconnect hook), a reincarnated server re-creates
    the (empty) store, and the next pull re-fetches in full once the owner
    reseeds — no stale cached params ever returned as fresh."""
    n = 256
    port = server_port
    c = ps_service.PSClient(
        "127.0.0.1", port, op_timeout_s=5.0, reconnect_deadline_s=30.0,
        backoff_s=0.05,
    )
    ps = ps_service.RemoteParamStore(c, "p", n)
    ps.set(3, np.full(n, 3.0, np.float32))
    s, v = ps.get()
    assert s == 3 and v[0] == 3.0
    assert ps.get()[1] is v  # cache warm
    ps_service.stop_server()
    assert ps_service.start_server(port) == port  # new incarnation
    s, v2 = ps.get()  # reconnect -> invalidate -> full refetch
    assert s == -1, "stale cache served after a server restart"
    ps.set(5, np.full(n, 5.0, np.float32))  # the owner reseeds
    s, v3 = ps.get()
    assert s == 5 and v3[0] == 5.0
    c.close()
