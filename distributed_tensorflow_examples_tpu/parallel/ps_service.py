"""Client for the cross-process PS service (native/ps_server.cc).

The thread-mode async-PS emulation (parallel/async_ps.py) talks to the
native accumulator/token/gradient-queue structs through direct ctypes calls;
this module provides the SAME object APIs over a localhost TCP socket, so
the W1/W2 emulations run across real processes — the reference's PS/worker
process topology (SURVEY.md sections 3.1/3.2), with the chief process
hosting the service (the PS task role) and each worker process connecting.

One socket per client; requests are serialized on it (a worker's op
sequence is sequential anyway, and blocking ops — token pop, accumulator
take, gradient pop — tie up only that client's server-side thread).

Fault tolerance (r6): the reference's fault model lost the whole job when a
PS task died (a stalled session torn down and crash-restarted, SURVEY.md
section 5.3).  Here the client itself heals the connection:

- every op takes a DEADLINE (``op_timeout_s``); blocking ops are issued as
  bounded server-side waits the client re-issues, so a dead peer surfaces
  as a timeout instead of an eternal hang;
- a transport failure triggers exponential-backoff RECONNECT (bounded by
  ``reconnect_deadline_s``), after which the op is REPLAYED.  Gradient
  WRITES are exactly-once: applies/pushes are dedup-tagged with a
  per-worker monotone sequence number the server remembers, so a gradient
  that DID land before the drop is answered "duplicate", never applied
  twice.  Drain ops (take / token pop / gradient pop) are at-most-once:
  a response lost after the server commits loses that drained
  average/token/gradient.  Token pushes are at-LEAST-once: a replayed
  push may add extra same-step tokens, whose extra gradients are averaged
  in or staleness-dropped — the same effect (and tolerance) as the
  chief's stall-triggered token re-push
  (``AsyncPSTrainer.sync_stall_repush_s``), which heals the lost
  tokens/aggregations of the at-most-once drains.  A lost async gradient
  is equivalent to a stale-drop (harmless);
- on reconnect the client compares the server's INCARNATION id: a changed
  id means the PS restarted and lost all state, so the client re-issues
  its object-creation ops and runs registered ``on_reincarnation``
  callbacks (the chief republishes params and re-seeds step/tokens).

Every recovery action logs one structured ``dtx.faults`` line; fault
INJECTION (the ``DTX_FAULT_PLAN`` env var) hooks in at ``call()`` — see
``utils/faults.py``.

Transport fast path (r7): the framing is zero-copy in both directions —
requests leave as a scatter/gather ``sendmsg`` (header bytes + a
``memoryview`` over the caller's contiguous array; no ``tobytes()``, no
concat) and responses land via ``recv_into`` straight into the output
array (the old ``bytes +=`` accumulation was O(n²) in the payload size).
Payload encoding is a per-connection property negotiated at connect (wire
v2 ``HELLO``): f32 — byte-identical to the v1 framing — or bf16
(``wire_dtype="bf16"``), which halves param/grad bytes on the wire while
the server keeps storing f32.  ``RemoteParamStore.get`` is versioned: a
client-side cache plus the ``PSTORE_GET_IF_NEWER`` op make an
unchanged-step pull cost one header-sized round trip instead of re-shipping
the whole flat vector.

The frame layout, HELLO negotiation, zero-copy send/recv and the bf16
codec live in ``parallel/wire.py`` (r8), shared with the disaggregated
data service (``data/data_service.py``) so the two wires cannot drift.
On THIS wire, payload lengths count ELEMENTS of the negotiated dtype (the
C++ server's contract); the data wire counts bytes.

Sharded store (r9): ``parallel/ps_shard.py`` spreads the flat parameter
vector over N of these servers (one ``PSClient`` per shard, HELLO pinned
via ``expect_shard``) and scatter/gathers concurrently; this module stays
the single-connection layer it builds on.  ``call(out=...)`` receives a
response directly into a caller-provided buffer slice — the sharded
gather's zero-staging path.
"""

from __future__ import annotations

import json
import socket
import struct
import threading
import time

import numpy as np

from .. import native
from ..utils import faults, telemetry
from . import retry, tenancy, wire

# Op codes — aliases into the ONE registry (wire.PS_OPS, the single Python
# definition site; tools/dtxlint pins it against native/ps_server.cc's
# enum Op by name and number).  Never restate the numbers here.
_ACC_GET = wire.PS_OPS["ACC_GET"]
_ACC_APPLY = wire.PS_OPS["ACC_APPLY"]
_ACC_TAKE = wire.PS_OPS["ACC_TAKE"]
_ACC_SET_STEP = wire.PS_OPS["ACC_SET_STEP"]
_ACC_DROPPED = wire.PS_OPS["ACC_DROPPED"]
_TQ_GET = wire.PS_OPS["TQ_GET"]
_TQ_PUSH = wire.PS_OPS["TQ_PUSH"]
_TQ_POP = wire.PS_OPS["TQ_POP"]
_GQ_GET = wire.PS_OPS["GQ_GET"]
_GQ_PUSH = wire.PS_OPS["GQ_PUSH"]
_GQ_POP = wire.PS_OPS["GQ_POP"]
_GQ_SET_MIN = wire.PS_OPS["GQ_SET_MIN"]
_GQ_DROPPED = wire.PS_OPS["GQ_DROPPED"]
_CANCEL_ALL = wire.PS_OPS["CANCEL_ALL"]
_PING = wire.PS_OPS["PING"]
_PSTORE_GET_OBJ = wire.PS_OPS["PSTORE_GET_OBJ"]
_PSTORE_SET = wire.PS_OPS["PSTORE_SET"]
_PSTORE_GET = wire.PS_OPS["PSTORE_GET"]
_INCARNATION = wire.PS_OPS["INCARNATION"]
_ACC_APPLY_TAGGED = wire.PS_OPS["ACC_APPLY_TAGGED"]
_GQ_PUSH_TAGGED = wire.PS_OPS["GQ_PUSH_TAGGED"]
_ACC_DEDUPED = wire.PS_OPS["ACC_DEDUPED"]
_GQ_DEDUPED = wire.PS_OPS["GQ_DEDUPED"]
_ACC_RESET_WORKER = wire.PS_OPS["ACC_RESET_WORKER"]
_GQ_RESET_WORKER = wire.PS_OPS["GQ_RESET_WORKER"]
_HELLO = wire.PS_OPS["HELLO"]
_PSTORE_GET_IF_NEWER = wire.PS_OPS["PSTORE_GET_IF_NEWER"]
_REPL_SYNC = wire.PS_OPS["REPL_SYNC"]
_REPL_TOKEN = wire.PS_OPS["REPL_TOKEN"]
_STATS = wire.PS_OPS["STATS"]
_LEASE_ACQUIRE = wire.PS_OPS["LEASE_ACQUIRE"]
_LEASE_RELEASE = wire.PS_OPS["LEASE_RELEASE"]
_LEASE_LIST = wire.PS_OPS["LEASE_LIST"]
_RESHARD_BEGIN = wire.PS_OPS["RESHARD_BEGIN"]
_RESHARD_COMMIT = wire.PS_OPS["RESHARD_COMMIT"]
_RESHARD_GET = wire.PS_OPS["RESHARD_GET"]
_RESHARD_ABORT = wire.PS_OPS["RESHARD_ABORT"]

# Client-side observability (r13 dtxobs): every PSClient in the process
# accumulates into these process-wide instruments — cached handles, so the
# per-op cost is one lock + an int add (the `ps_client/*` family the STATS
# scrapes of Python services, and tests, read via telemetry.snapshot()).
_OBS_OPS = telemetry.REGISTRY.counter("ps_client/ops")
_OBS_ERRS = telemetry.REGISTRY.counter("ps_client/op_errors")
_OBS_TX = telemetry.REGISTRY.counter("ps_client/bytes_tx")
_OBS_RX = telemetry.REGISTRY.counter("ps_client/bytes_rx")
_OBS_OP_MS = telemetry.REGISTRY.histogram("ps_client/op_ms")
_OBS_RECONNECTS = telemetry.REGISTRY.counter("ps_client/reconnects")
_OBS_CONN_LOST = telemetry.REGISTRY.counter("ps_client/conn_lost")
_OBS_REBUILDS = telemetry.REGISTRY.counter("ps_client/state_rebuilds")
_OBS_FAILOVERS = telemetry.REGISTRY.counter("ps_client/failovers")
_OBS_PULL_HITS = telemetry.REGISTRY.counter("ps_client/pull_cache_hits")

#: Wire protocol version this client speaks (ps_server.cc kWireVersion).
WIRE_VERSION = wire.WIRE_VERSION

#: Payload encodings (HELLO dtype codes).  f32 framing is byte-identical
#: to wire v1; bf16 halves payload bytes and REQUIRES a negotiated peer.
WIRE_DTYPES = wire.WIRE_DTYPES

# The bf16 codec (round-to-nearest-even, bit-exact with the C++ server)
# lives in parallel/wire.py; these module names stay as the stable import
# point for tests and the bench.
_f32_to_bf16 = wire.f32_to_bf16
_bf16_to_f32 = wire.bf16_to_f32

#: Deadline sentinel for bounded blocking ops (take/pop with ``timeout_s``).
TIMED_OUT = native.TIMED_OUT

#: How long a tagged gradient push keeps polling a FULL queue before the
#: stall is treated as a dead/wedged chief (PSDeadlineError) rather than
#: ordinary backpressure.
_PUSH_STALL_LIMIT_S = 600.0


class PSError(RuntimeError):
    """A PS op failed terminally (transport down and unrecoverable, or the
    server rejected the request)."""


class _StateLost(Exception):
    """Internal recovery signal: the replica just reconnected to carries a
    DIFFERENT state token (restarted empty, peer unreachable) — try the
    other replicas before falling back to the rebuild/reseed path.
    Deliberately not a PSError: the generic recovery retry must not
    swallow it."""


class PSDeadlineError(PSError):
    """Reconnect budget exhausted: the PS stayed unreachable past
    ``reconnect_deadline_s``."""


def start_server(
    port: int = 0, *, loopback_only: bool = True, shard_id: int = 0,
    shard_count: int = 1, layout_version: int = 0,
    peer: tuple[str, int] | None = None, sync_wait_s: float = 0.0,
) -> int:
    """Start an in-process C++ PS server; returns the bound port.

    ``loopback_only=False`` binds all interfaces — required when workers on
    OTHER hosts dial this PS task (the protocol is unauthenticated, so only
    do this on a trusted cluster network, as with the reference's gRPC).

    (``shard_id``, ``shard_count``) is the server's shard identity (r9):
    which contiguous slice of the flat parameter vector it owns.  HELLO
    validates a shard-aware client's expectation against it, so a
    mis-wired dial fails loudly.  One process may host SEVERAL shard
    servers (the chief-hosted sharded topology and the shard bench).

    Replication (r12): ``layout_version`` joins the HELLO identity (the
    shard-topology epoch — mixed-epoch clients fail the dial loudly), and
    ``peer`` names this shard's peer replica: state-mutating ops forward
    to it, and the start blocks up to ``sync_wait_s`` pulling the peer's
    full state (REPL_SYNC) — adopting its STATE TOKEN — before serving."""
    host, pport = peer if peer is not None else ("", 0)
    p = native._load().ps_server_start_replicated(
        port, 1 if loopback_only else 0, shard_id, shard_count,
        int(layout_version), host.encode() if host else None, int(pport),
        int(sync_wait_s * 1000),
    )
    if p < 0:
        raise RuntimeError("ps_server_start failed")
    return p


def set_server_peer(port: int, peer: tuple[str, int]) -> bool:
    """Wire a running shard server to its peer replica (the in-process
    replicated topology binds ephemeral ports first, then pairs them)."""
    return bool(
        native._load().ps_server_set_peer(port, peer[0].encode(), peer[1])
    )


def resync_server(port: int, wait_s: float = 5.0) -> bool:
    """On-demand REPL_SYNC: the server at ``port`` pulls its peer's full
    state (adopting the peer's state token).  The in-process analog of the
    restarted-task start-time catch-up."""
    return bool(
        native._load().ps_server_resync_port(port, int(wait_s * 1000))
    )


def set_server_partitioned(port: int, on: bool) -> bool:
    """Inject a replication partition at the server at ``port``: its
    peer's repl connections are refused by policy and its own forwards
    fail — the ``partition`` fault kind's server-side primitive."""
    return bool(
        native._load().ps_server_set_partitioned(port, 1 if on else 0)
    )


def server_state_token(port: int) -> int:
    """A shard server's state-lineage token (-1 = no server there)."""
    return int(native._load().ps_server_state_token_port(port))


def server_diverged(port: int) -> int:
    """Whether the server at ``port`` latched replication divergence
    (1/0; -1 = no server there)."""
    return int(native._load().ps_server_diverged_port(port))


def server_live_conns(port: int) -> int:
    """Live client connections at the server at ``port`` (-1 = none
    there) — the orphaned-replica signal ``host_ps_task`` watches."""
    return int(native._load().ps_server_live_conns_port(port))


def set_server_draining(port: int, on: bool = True) -> bool:
    """Mark the server at ``port`` DRAINING (r15): a reshard retired its
    layout and the host is waiting out the last connections before exit —
    exported in STATS so a mid-transition cluster reads correctly in
    dtxtop."""
    return bool(
        native._load().ps_server_set_draining(port, 1 if on else 0)
    )


def stop_server(port: int | None = None) -> None:
    """Stop ALL in-process servers, or — ``port`` given — just the shard
    server bound there (the targeted-kill primitive for single-shard fault
    tests against in-process topologies)."""
    if port is None:
        native._load().ps_server_stop()
    else:
        native._load().ps_server_stop_port(port)


def server_incarnation(port: int | None = None) -> int:
    """A live server's incarnation id (-1 when none runs): the oldest
    server's by default, or the shard server bound at ``port``."""
    lib = native._load()
    if port is None:
        return int(lib.ps_server_incarnation())
    return int(lib.ps_server_incarnation_port(port))


def server_request_count(port: int | None = None) -> int:
    """Requests served (-1 when no server runs) — the trigger for
    ``die:after_reqs`` fault specs.  Default: the SUM across this process's
    live servers (with several local shards, the process's total traffic);
    ``port`` narrows to one shard server."""
    lib = native._load()
    if port is None:
        return int(lib.ps_server_requests())
    return int(lib.ps_server_requests_port(port))


class PSClient:
    """One TCP connection to the PS server; thread-safe via a lock.

    ``timeout_s``            connect timeout AND the default op deadline
                             (pre-r6 compatible: None = block forever).
    ``op_timeout_s``         per-op deadline; overrides ``timeout_s`` for
                             ops.  Blocking ops get this ON TOP of their
                             bounded server-side wait.
    ``reconnect_deadline_s`` > 0 enables recovery: on a transport failure
                             the client reconnects (exponential backoff,
                             giving up — ``PSDeadlineError`` — after this
                             many seconds of unreachability) and replays
                             the op.  0 = pre-r6 fail-fast behavior.
    ``worker_tag``           this client's worker id: non-None makes
                             accumulator applies / gradient pushes
                             dedup-tagged (replay-safe).  Plain applies on
                             a recovering client are refused instead of
                             risking a double apply.
    ``role``                 fault-plan role for DTX_FAULT_PLAN matching
                             (defaults to the process role).
    ``wire_dtype``           payload encoding on this connection: "f32"
                             (default; v1-compatible framing, no handshake
                             needed) or "bf16" (half the payload bytes both
                             ways; negotiated at connect via HELLO, so a
                             peer that can't speak wire v2 fails the
                             connection loudly instead of misparsing).
    ``expect_shard``         (shard_id, shard_count) this client expects of
                             the server it dials (r9 sharded PS).  Non-None
                             forces the HELLO handshake on every connect
                             (f32 included) and a server owning a DIFFERENT
                             shard fails the connection loudly — a
                             mis-wired dial must never silently serve the
                             wrong slice of the parameter vector.  None =
                             no expectation (pre-r9 framing, byte-identical
                             for f32).
    ``expect_layout``        the shard-topology EPOCH this client expects
                             (r12 layout version; 0 = no expectation).
                             Non-zero forces the handshake and a server on
                             a different epoch fails the dial loudly
                             naming both versions — the guard that makes
                             mixed-epoch clients impossible during a
                             (future) live reshard.
    ``addrs``                the full ordered replica address list for
                             this shard (r12; entry 0 is the primary —
                             ``host``/``port`` must equal it when both are
                             given).  With a backup present, recovery
                             ALTERNATES replicas and compares the shard's
                             STATE TOKEN on every reconnect: a token match
                             means the state survived (failover or synced
                             restart — NO reseed, by design zero chief
                             involvement); only when every replica's token
                             proves the state lost does the full
                             reincarnation path (object re-create +
                             ``on_reincarnation`` callbacks, i.e. chief
                             reseed) run as the last resort.  Ops issued
                             while connected to a backup replica inject
                             faults under the ``<role>_b`` client role.
    """

    #: Server-side wait per blocking-op round trip when the client has a
    #: deadline/recovery configured; each expiry just re-issues, so this
    #: only bounds how fast a dead peer is noticed.
    block_chunk_s = 2.0

    def __init__(
        self, host: str, port: int, *, timeout_s: float | None = None,
        op_timeout_s: float | None = None, reconnect_deadline_s: float = 0.0,
        backoff_s: float = 0.25, worker_tag: int | None = None,
        role: str | None = None, wire_dtype: str = "f32",
        expect_shard: tuple[int, int] | None = None,
        expect_layout: int = 0,
        addrs: list[tuple[str, int]] | None = None,
        control_ops_are_fault_points: bool = False,
        tenant: str = tenancy.DEFAULT_TENANT,
    ):
        if wire_dtype not in WIRE_DTYPES:
            raise ValueError(
                f"wire_dtype {wire_dtype!r} not in {sorted(WIRE_DTYPES)}"
            )
        # Multi-tenancy (r20): every object-key op this client issues is
        # qualified under ``t.<tenant>.`` at the single call() choke point
        # (tenancy.qualify — the default tenant is the identity, keeping
        # pre-tenant clients byte-identical on the wire).
        self.tenant = (
            tenant if tenant == tenancy.DEFAULT_TENANT
            else tenancy.check_tenant(tenant)
        )
        self._addrs = list(addrs) if addrs else [(host, port)]
        if (host, port) != self._addrs[0]:
            raise ValueError(
                f"(host, port) ({host}:{port}) must be addrs[0] "
                f"({self._addrs[0][0]}:{self._addrs[0][1]})"
            )
        self._cur = 0
        self._host, self._port = self._addrs[0]
        self._expect_shard = expect_shard
        self._expect_layout = int(expect_layout)
        self._connect_timeout = timeout_s
        self._op_timeout = op_timeout_s if op_timeout_s is not None else timeout_s
        self._reconnect_deadline = reconnect_deadline_s
        self._backoff = backoff_s
        self.worker_tag = worker_tag
        self.role = role if role is not None else faults.current_role()
        self.wire_dtype = wire_dtype
        self._wire_code = WIRE_DTYPES[wire_dtype]
        self._lock = threading.RLock()
        self._in_recovery = False
        self._ensures: list[tuple[int, str, int, int]] = []
        self._callbacks: list = []
        self._reconnect_callbacks: list = []
        # Per-REPLICA injectors (the backup leg is its own fault role,
        # ``<role>_b``, with its own logical-op counter) — created lazily
        # so single-address clients keep the zero-cost no-faults path.
        # ``control_ops_are_fault_points``: a DEDICATED control client
        # (the ``_lm`` membership legs) counts its lease/control ops in
        # the fault op index — that stream IS its logical traffic; every
        # other client skips control ops (faults.control_op_codes) so
        # plan indices never drift with scrape/heartbeat/epoch cadence.
        self._control_fault_points = control_ops_are_fault_points
        self._injectors: dict[int, faults.ClientFaultInjector | None] = {}
        self._injector = self._leg_injector(0)
        # Shared retry discipline (r18, parallel/retry.py): replays and
        # shed retries spend this token-bucket budget (refilled by
        # successes), so N clients recovering from one blip can never
        # tighten into a retry storm; exhaustion surfaces as the typed
        # PSDeadlineError plus a flight-recorder event.
        self._budget = retry.RetryBudget()
        self._sock: socket.socket | None = None
        self._negotiated = False  # peer confirmed v4: deadline stamps OK
        self._hdr = bytearray(12)  # reusable response-header buffer
        # Per-replica incarnations + the shard's state-lineage token (r12):
        # a reconnect that finds the SAME token — on any replica — proves
        # the shard's state survived and skips every rebuild/reseed step.
        # None token = server predates REPL_TOKEN (incarnation semantics).
        self._incarnations: dict[int, int] = {}
        self._state_token: int | None = None
        try:
            self._connect()
            # The baseline incarnation: reconnects compare against this to
            # tell a transient drop from a restarted (state-lost) server.
            # Bounded by the configured deadlines so a stalled server fails
            # the ctor instead of hanging it.
            inc, _ = self._attempt(
                _INCARNATION,
                deadline_s=self._op_timeout
                if self._op_timeout is not None
                else self._connect_timeout,
            )
            self._incarnations[self._cur] = inc
            if len(self._addrs) > 1:
                # Token semantics are a REPLICATED-topology feature; a
                # single-address client keeps the exact pre-r12 op
                # sequence (and incarnation-only recovery).
                self._read_state_token()
        except OSError:
            if self._reconnect_deadline <= 0:
                raise
            # Construction during a PS outage (e.g. mid supervised restart)
            # gets the same recovery budget as any op: retry with backoff;
            # the empty incarnation map makes the first contact a plain
            # first-connect (replays the empty ensure list, records ids).
            self._recover(time.monotonic() + self._reconnect_deadline)

    def _leg_injector(self, idx: int):
        """The fault injector for replica leg ``idx``: the bare client role
        on the primary, ``<role>_b`` on a backup — so plans can target the
        failover leg without firing on the healthy one."""
        if idx not in self._injectors:
            leg_role = self.role if idx == 0 else f"{self.role}_b"
            self._injectors[idx] = faults.client_injector(
                leg_role, count_control_ops=self._control_fault_points,
            )
        return self._injectors[idx]

    def _switch_replica(self, idx: int) -> None:
        self._sever()
        self._cur = idx
        self._host, self._port = self._addrs[idx]
        self._injector = self._leg_injector(idx)

    def _read_state_token(self) -> None:
        """Learn the shard's state token from the connected server (None
        when the server predates the op)."""
        tok, _ = self._attempt(
            _REPL_TOKEN, deadline_s=self._op_timeout or 10.0
        )
        self._state_token = None if tok < 0 else tok

    # -- transport ----------------------------------------------------------

    def _connect(self) -> None:
        sock = socket.create_connection(
            (self._host, self._port), timeout=self._connect_timeout
        )
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self._sock = sock
        if (
            self._wire_code != WIRE_DTYPES["f32"]
            or self._expect_shard is not None
            or self._expect_layout
        ):
            # Encoding differs from the v1 framing (HELLO per connection —
            # the server's dtype is per-connection state, negotiated BEFORE
            # any payload op can be misparsed) — or the caller expects a
            # specific SHARD of a sharded store, which the server must
            # confirm before any payload lands on the wrong slice.  Plain
            # f32 connections without a shard expectation skip it: their
            # framing is byte-identical to v1, so nothing can misparse and
            # the connect stays one round trip cheaper.
            self._negotiate()
            # The peer answered a v4 HELLO: deadline stamps (r18) are
            # safe on this connection.  An UN-negotiated plain-f32
            # connection stays v1-byte-identical — it may be talking to
            # a pre-v4 peer that would misparse the stamp.
            self._negotiated = True

    def _negotiate(self) -> None:
        """HELLO on the fresh socket.  Transport failures raise OSError
        (retryable, like any connect failure); a peer that answers the
        wrong version — or doesn't know the op — raises PSError, which is
        PERMANENT and must not be retried by the reconnect loop."""
        # HELLO carries no payload either way, so it frames identically
        # under every encoding — safe to send before the answer arrives.
        # The "ps" service announcement (r10) rides in b's high bits: the
        # native server masks them out (back-compatible), while a Python
        # service reached by mistake refuses with a status naming itself.
        sid, scount = self._expect_shard if self._expect_shard else (0, 0)
        status, _ = self._attempt(
            _HELLO, a=WIRE_VERSION,
            b=wire.pack_hello_b(
                self._wire_code, sid, scount, service="ps",
                layout_version=self._expect_layout,
            ),
            deadline_s=self._connect_timeout
            if self._connect_timeout is not None
            else 10.0,
        )
        if status == WIRE_VERSION:
            return
        self._sever()
        got = wire.unpack_wrong_service(status)
        if got is not None:
            # Checked BEFORE the shard decode: wrong-service statuses live
            # in a range a genuine shard-mismatch echo can never produce
            # (its packed identity always carries shard_count >= 1 in bits
            # 20+, putting it far below this band).
            raise PSError(
                f"wrong-service dial: {self._host}:{self._port} is "
                f"{wire.SERVICE_NAMES[got]} ({got!r}), not the native PS "
                "state service — check --ps_hosts against the running tasks"
            )
        if status <= wire.HELLO_SHARD_MISMATCH:
            got_id, got_n, got_v = wire.unpack_shard_mismatch(status)
            if self._expect_layout and got_v != (
                self._expect_layout & wire.HELLO_LAYOUT_MASK
            ):
                raise PSError(
                    f"layout-version mismatch: {self._host}:{self._port} "
                    f"serves shard layout EPOCH {got_v} but this client "
                    f"expected epoch {self._expect_layout} — a mixed-epoch "
                    "client must never scatter onto a resharded store; "
                    "restart the stale end on the current topology"
                )
            raise PSError(
                f"mis-wired shard dial: {self._host}:{self._port} owns shard "
                f"{got_id}/{got_n} but this client expected shard "
                f"{sid}/{scount} — check the --ps_hosts order/--ps_shards "
                "against the running PS tasks"
            )
        raise PSError(
            f"wire negotiation with {self._host}:{self._port} failed: "
            f"asked v{WIRE_VERSION}/{self.wire_dtype}, peer answered "
            f"{status} (pre-v2 server, or unsupported dtype) — both ends "
            "must speak wire v2 for a non-f32 encoding"
        )

    def _sever(self) -> None:
        sock, self._sock = self._sock, None
        self._negotiated = False
        if sock is not None:
            try:
                sock.close()
            except OSError:
                pass

    def close(self) -> None:
        # Also revoke the reconnect budget: an op issued after close (leaked
        # reference, teardown-ordered thread) must fail fast, not silently
        # resurrect a connection to the PS.
        self._reconnect_deadline = 0.0
        self._sever()

    def _encode_payload(self, payload: np.ndarray | None) -> np.ndarray | None:
        """The wire form of a payload: a contiguous f32 array (no copy when
        the caller's array already is one — the hot path) or its bf16 bit
        patterns (one vectorized conversion, the only data touch before the
        scatter/gather send)."""
        if payload is None:
            return None
        if self._wire_code == 1:
            return _f32_to_bf16(np.asarray(payload).reshape(-1))
        return np.ascontiguousarray(payload, np.float32).reshape(-1)

    def _send_frame(self, header: bytes, payload: np.ndarray | None) -> None:
        """Scatter/gather send: header + payload leave via ``sendmsg`` with
        a memoryview over the array — the payload bytes are never copied
        into a concatenated request buffer (wire.send_frame)."""
        wire.send_frame(self._sock, header, payload)

    def _recv_exact(self, view: memoryview) -> None:
        """Fill ``view`` from the socket via ``recv_into`` — no chunk
        accumulation (the old ``bytes +=`` loop was O(n²) in payload size),
        no staging copy: responses land directly in their final buffer
        (wire.recv_exact)."""
        wire.recv_exact(self._sock, view)

    def _attempt(
        self, op: int, name: str = "", a: int = 0, b: int = 0,
        payload: np.ndarray | None = None, *, deadline_s: float | None = None,
        out: np.ndarray | None = None, raw: bool = False,
    ) -> tuple[int, np.ndarray]:
        """One instrumented send/recv round trip (r13: per-op wall time and
        success/error counts land in the process ``ps_client/*`` telemetry
        family — one lock+add per op against cached instruments, cheap
        next to the socket round trip itself).  See ``_attempt_io``."""
        t0 = time.perf_counter()
        try:
            ret = self._attempt_io(
                op, name, a, b, payload, deadline_s=deadline_s, out=out,
                raw=raw,
            )
        except OSError:
            _OBS_ERRS.inc()
            raise
        _OBS_OPS.inc()
        _OBS_OP_MS.observe((time.perf_counter() - t0) * 1e3)
        return ret

    def _attempt_io(
        self, op: int, name: str = "", a: int = 0, b: int = 0,
        payload: np.ndarray | None = None, *, deadline_s: float | None = None,
        out: np.ndarray | None = None, raw: bool = False,
    ) -> tuple[int, np.ndarray]:
        """One send/recv round trip; severs the socket on ANY failure (the
        framing is broken mid-stream, so the connection is unusable).
        ``payload`` must already be wire-encoded (``_encode_payload``).
        ``out``: optional preallocated f32 destination — a response whose
        element count matches lands via ``recv_into`` DIRECTLY in it (the
        sharded gather's zero-staging path: each shard's slice of one
        output buffer); any other length falls back to a fresh array, so
        status-only answers (e.g. an unchanged-step pull) never clobber
        or misreport the caller's buffer.  ``raw``: the response payload is
        an UN-encoded byte blob counted in 4-byte units (STATS/REPL_SYNC
        shape) — returned as ``bytes``, never dtype-decoded."""
        if self._sock is None:
            raise ConnectionError("not connected")
        # Deadline propagation (r18): the caller's remaining per-op budget
        # rides in the frame header, so the server clamps blocking waits
        # to it and sheds work this client has already abandoned instead
        # of burning a thread on a dead request.  ONLY on a negotiated
        # (HELLO'd v4) connection — an un-negotiated plain-f32 socket may
        # be talking to a v1-framing peer that would misparse the stamp.
        header = wire.pack_request(
            op, name, a, b, 0 if payload is None else payload.size,
            deadline_ms=(
                0 if deadline_s is None or not self._negotiated
                else max(1, int(deadline_s * 1000))
            ),
        )
        try:
            self._sock.settimeout(deadline_s)
            self._send_frame(header, payload)
            _OBS_TX.inc(
                len(header) + (0 if payload is None else payload.nbytes)
            )
            hdr = memoryview(self._hdr)
            self._recv_exact(hdr)
            status, plen = struct.unpack("<qI", self._hdr)
            _OBS_RX.inc(
                12 + plen * (4 if raw else (2 if self._wire_code == 1 else 4))
            )
            if raw:
                blob = bytearray(plen * 4)
                if plen:
                    self._recv_exact(memoryview(blob))
                return status, bytes(blob)
            if status == wire.REPL_DIVERGED:
                # The replica refuses to accept a write it can no longer
                # replicate (its peer is alive but the link is down by
                # policy) — a PERMANENT loud failure, never retried: a
                # silent split-brain would diverge the two replicas'
                # state under every client that kept writing.  Fatal for
                # the run, so the flight recorder dumps NOW: the events
                # leading here (partitions, drops, failovers) are the
                # post-mortem (r13 dtxobs).
                faults.log_event(
                    "repl_diverged", role=self.role, host=self._host,
                    port=self._port, op_code=op,
                )
                telemetry.dump_flight_recorder("repl_diverged")
                raise PSError(
                    f"replication diverged: the PS at {self._host}:"
                    f"{self._port} refuses state-mutating ops because its "
                    "peer replica cannot mirror them (partitioned link, or "
                    "the peer restarted without syncing) — heal the link / "
                    "re-sync the lagging replica before resuming training"
                )
            if not plen:
                return status, np.empty((0,), np.float32)
            # Receive straight into the result array (f32) or its bf16
            # staging array (upconverted in one vectorized pass).  Freshly
            # allocated per response unless the caller supplied a matching
            # ``out`` — then the payload lands in the caller's buffer with
            # zero staging copies.
            if self._wire_code == 0:
                dst = out if out is not None and out.size == plen else None
                if dst is None:
                    dst = np.empty((plen,), np.float32)
                self._recv_exact(memoryview(dst.reshape(-1)).cast("B"))
                return status, dst
            raw = np.empty((plen,), np.uint16)
            self._recv_exact(memoryview(raw).cast("B"))
            if out is not None and out.size == plen:
                out.reshape(-1)[:] = _bf16_to_f32(raw)
                return status, out
            return status, _bf16_to_f32(raw)
        except OSError:
            self._sever()
            raise

    # -- recovery -----------------------------------------------------------

    def _qual(self, op: int, name: str) -> str:
        """Tenant-qualify an object key (r20): identity for the default
        tenant and for control/lease ops — only the object-key op families
        (tenancy.PS_SCOPED_OP_CODES) carry tenant-scoped names."""
        if self.tenant == tenancy.DEFAULT_TENANT:
            return name
        if op in tenancy.PS_SCOPED_OP_CODES:
            return tenancy.qualify(self.tenant, name)
        return name

    def _register_ensure(self, op: int, name: str, a: int, b: int) -> None:
        self._ensures.append((op, name, a, b))

    def ensure_object(self, op: int, name: str, a: int = 0, b: int = 0) -> int:
        """Issue a get-or-create op AND remember it, so a reincarnated
        server (restart lost every object) gets them re-created on
        reconnect.  Returns the status.  Only a SUCCESSFUL create is
        remembered — a rejected one (type/name clash) must not poison the
        reincarnation replay for the client's healthy objects.  The ensure
        list records the tenant-QUALIFIED name: the reincarnation replay
        goes through _attempt (below call()'s qualification point), so the
        stored name must already be the wire-level key."""
        status, _ = self.call(op, name, a, b)
        if status >= 0:
            self._register_ensure(op, self._qual(op, name), a, b)
        return status

    def on_reincarnation(self, fn) -> None:
        """Register a callback run (after object re-creation) whenever a
        reconnect lands on a NEW server incarnation — the chief re-seeds
        volatile state here (republish params, reset step, re-push
        tokens).  Callbacks may use this client; their ops run
        single-attempt (no nested recovery)."""
        self._callbacks.append(fn)

    def on_reconnect(self, fn) -> None:
        """Register a callback run on EVERY successful reconnect (same or
        new incarnation, before any reincarnation handling) — cache
        invalidation hooks: anything a client mirrors locally (e.g. the
        param-pull cache) must be re-validated against the server after a
        transport gap.  Must be cheap and must not issue remote ops."""
        self._reconnect_callbacks.append(fn)

    def _recover(self, t_end: float) -> None:
        """Reconnect with exponential backoff until ``t_end``; on success,
        detect state loss (token/incarnation) and rebuild only as the LAST
        resort.  With replicas configured (r12), attempts ALTERNATE the
        replica addresses — a dead primary fails over to its backup within
        one retry, with zero chief involvement when the backup's token
        proves the state intact."""
        attempt = 0
        lost: set[int] = set()
        lost_retries = 0
        immediate = False
        while True:
            if attempt and not immediate:
                # first attempt is immediate — the common drop is transient
                # with a healthy server; JITTERED backoff paces retries so
                # N clients recovering from one blip spread their
                # re-arrival instead of re-dialing in lockstep (r18).
                delay = retry.jittered(self._backoff, attempt - 1, cap_s=2.0)
                time.sleep(min(delay, max(0.0, t_end - time.monotonic())))
            immediate = False
            if time.monotonic() >= t_end:
                faults.log_event(
                    "reconnect_gave_up", role=self.role, host=self._host,
                    port=self._port, attempts=attempt,
                )
                # Budget exhausted = fatal for this client's caller: dump
                # the flight recorder so the outage window is attributable.
                telemetry.dump_flight_recorder("reconnect_gave_up")
                raise PSDeadlineError(
                    f"PS at {self._host}:{self._port} unreachable for "
                    f"{self._reconnect_deadline:.0f}s ({attempt} attempts)"
                )
            attempt += 1
            # Per-address circuit breaker (r18, process-wide): an address
            # that just failed ``threshold`` consecutive dials is OPEN —
            # skip the dial (fail over to the other replica, which has
            # its own breaker, or wait out part of the window) instead of
            # burning another connect timeout against a dead peer.
            breaker = retry.breaker_for((self._host, self._port))
            if not breaker.allow():
                if len(self._addrs) > 1:
                    self._switch_replica((self._cur + 1) % len(self._addrs))
                else:
                    breaker.wait_for_probe(t_end)
                    immediate = True  # the wait was this attempt's pacing
                continue
            try:
                self._connect()
            except OSError:
                breaker.on_failure()
                if len(self._addrs) > 1:
                    self._switch_replica((self._cur + 1) % len(self._addrs))
                continue
            breaker.on_success()
            try:
                # After several rounds stuck on state-lost replicas (the
                # OTHER replica stayed unreachable throughout), stop
                # waiting for a survivor that isn't coming and rebuild on
                # what we have — the both-replicas-dead last resort.
                self._post_reconnect(
                    attempt, lost, force_rebuild=lost_retries >= 3
                )
                return
            except _StateLost:
                lost_retries += 1
                # A replica not yet seen lost; with all of them lost, the
                # primary (see _post_reconnect).
                nxt = next(
                    (i for i in range(len(self._addrs)) if i not in lost), 0
                )
                self._switch_replica(nxt)
                immediate = True
                continue
            except (OSError, PSError):
                # PSError: a transport failure inside a reincarnation
                # callback (callbacks run single-attempt and wrap their
                # OSError) — same fault as a raw drop, same retry, same
                # deadline.
                self._sever()
                continue

    def _post_reconnect(
        self, attempts: int, lost: set[int] | None = None,
        force_rebuild: bool = False,
    ) -> None:
        deadline = self._op_timeout or 10.0
        inc, _ = self._attempt(_INCARNATION, deadline_s=deadline)
        token = None
        if len(self._addrs) > 1:  # token semantics are replicated-only
            tok, _ = self._attempt(_REPL_TOKEN, deadline_s=deadline)
            token = None if tok < 0 else tok  # -2 = pre-r12 server
        prev = self._incarnations.get(self._cur)
        changed = prev is not None and inc != prev
        self._incarnations[self._cur] = inc
        _OBS_RECONNECTS.inc()
        faults.log_event(
            "reconnected", role=self.role, attempts=attempts,
            incarnation_changed=changed, replica=self._cur,
        )
        for fn in list(self._reconnect_callbacks):
            fn()
        if token is not None and self._state_token is not None:
            if token == self._state_token:
                # The shard's state LINEAGE survived — on this replica
                # (transient drop, or a restart that REPL_SYNCed from the
                # survivor) or by failing over to its peer.  Nothing to
                # rebuild, nothing to reseed: the zero-stall path.
                if changed or self._cur != 0:
                    _OBS_FAILOVERS.inc()
                    faults.log_event(
                        "replica_state_intact", role=self.role,
                        replica=self._cur, incarnation_changed=changed,
                    )
                return
            if not force_rebuild and lost is not None:
                lost.add(self._cur)
                # With the state lost on EVERY replica, rebuild on the
                # primary and nowhere else.  Attempts alternate the
                # replicas from wherever each client stood when its dial
                # failed, so "the replica tried last" differs from client
                # to client: the chief then reseeds and pops on one
                # replica while a worker pushes to the other, for ever.
                if len(lost) < len(self._addrs) or self._cur != 0:
                    raise _StateLost()
        else:
            # Legacy (token-less) server, or first contact: incarnation
            # semantics, exactly the pre-r12 behavior.
            if not changed:
                if self._state_token is None:
                    self._state_token = token
                return
        # State lost on every replica (or a legacy server restarted):
        # re-create objects in creation order, then let the owner re-seed
        # volatile state — the chief-reseed last resort.
        self._in_recovery = True
        try:
            for op, name, a, b in list(self._ensures):
                status, _ = self._attempt(
                    op, name, a, b, deadline_s=self._op_timeout or 10.0
                )
                if status < 0:
                    raise ConnectionError(
                        f"object re-create op {op} {name!r} rejected ({status})"
                    )
            for fn in list(self._callbacks):
                fn()
        finally:
            self._in_recovery = False
        self._state_token = token
        _OBS_REBUILDS.inc()
        faults.log_event(
            "state_rebuilt", role=self.role, objects=len(self._ensures),
            callbacks=len(self._callbacks),
        )

    # -- ops ----------------------------------------------------------------

    def call(
        self, op: int, name: str = "", a: int = 0, b: int = 0,
        payload: np.ndarray | None = None, *, replay_safe: bool = True,
        server_wait_s: float = 0.0, fault_point: bool = True,
        out: np.ndarray | None = None, raw: bool = False,
        raw_payload: bool = False,
    ) -> tuple[int, np.ndarray]:
        """One request/response; recovers + replays on transport failure
        when recovery is enabled and the op is ``replay_safe`` (idempotent
        or dedup-tagged).  ``server_wait_s``: how long the server may
        legitimately block on this op — added to the op deadline so a
        bounded wait is never mistaken for a dead peer.  ``fault_point``:
        whether this call advances the fault-injection op counter — the
        chunked re-issues of one logical blocking op pass False so plan
        indices count LOGICAL ops, not timing-dependent chunks.
        (Control-plane ops are additionally skipped INSIDE the injector,
        from wire.CONTROL_OPS via faults.control_op_codes — no call site
        restates that set.)  ``out``:
        optional preallocated response destination (see ``_attempt``).
        ``raw_payload``: the payload is an UN-encoded byte blob already
        framed as 4-byte units (the RESHARD_BEGIN record shape) — sent
        verbatim, never dtype-converted, so a bf16 connection ships the
        same bytes as an f32 one."""
        # Tenant qualification (r20): the ONE place a PS object key gets
        # its ``t.<tenant>.`` prefix — every helper object (accumulator,
        # queues, param store) passes bare names through here.
        name = self._qual(op, name)
        # Encode once, outside the retry loop: a replay re-sends the same
        # wire bytes without re-converting (bf16) or re-checking layout.
        wire_payload = (
            payload if raw_payload else self._encode_payload(payload)
        )
        deadline = (
            self._op_timeout + server_wait_s
            if self._op_timeout is not None
            else None
        )
        with self._lock:
            if (
                fault_point
                and self._injector is not None
                and self._injector.before_op(op)
            ):
                self._sever()  # injected drop_conn: fail this op's transport
            t_end = None
            shed = retry.ShedRetry(self._budget, self._op_timeout)
            while True:
                if self._sock is not None:
                    try:
                        status, data = self._attempt(
                            op, name, a, b, wire_payload, deadline_s=deadline,
                            out=out, raw=raw,
                        )
                    except OSError as e:
                        if self._in_recovery or self._reconnect_deadline <= 0:
                            raise PSError(f"PS op {op} failed: {e!r}") from e
                        if not replay_safe:
                            raise PSError(
                                f"PS op {op} not replay-safe; connection lost "
                                f"mid-op: {e!r}"
                            ) from e
                        _OBS_CONN_LOST.inc()
                        faults.log_event(
                            "conn_lost", role=self.role, op_code=op,
                            error=type(e).__name__,
                        )
                    else:
                        hint = wire.retry_after_ms(status)
                        if hint is None:
                            # Every success funds future retries (the
                            # token-bucket budget, r18).
                            self._budget.on_success()
                            return status, data
                        # The server SHED this request (RETRY_LATER,
                        # r18 admission control): retry with jittered
                        # backoff THROUGH the budget, bounded by the op
                        # deadline — never at line rate
                        # (retry.ShedRetry, the one spelling).
                        if not shed.backoff(hint):
                            raise PSDeadlineError(
                                f"PS at {self._host}:{self._port} kept "
                                f"shedding op {op} (RETRY_LATER) past the "
                                "op deadline / retry budget — the server "
                                "is overloaded; back off and retry later"
                            )
                        continue
                elif self._in_recovery or self._reconnect_deadline <= 0:
                    raise PSError(f"PS op {op} failed: not connected")
                if t_end is None:
                    t_end = time.monotonic() + self._reconnect_deadline
                # A transport replay is a RETRY: it spends the shared
                # budget, so a storm of failing ops cannot re-dial and
                # replay unboundedly (budget exhaustion = the typed
                # deadline error, with the flight-recorder event the
                # budget logs).
                if not self._budget.try_spend():
                    raise PSDeadlineError(
                        f"PS at {self._host}:{self._port} retry budget "
                        f"exhausted replaying op {op} — refusing to feed "
                        "the retry storm"
                    )
                self._recover(t_end)

    def block_wait_s(self, t_end: float | None = None) -> float:
        """Server-side wait for the next blocking-op round trip: chunked
        (``block_chunk_s``) when this client has a deadline or recovery to
        honor, else 0 (= block forever, the pre-r6 wire behavior)."""
        chunk = (
            self.block_chunk_s
            if (self._op_timeout is not None or self._reconnect_deadline > 0)
            else 0.0
        )
        if t_end is None:
            return chunk
        remaining = max(0.05, t_end - time.monotonic())
        return min(chunk, remaining) if chunk else remaining

    def timed_blocking(
        self, op: int, name: str, make_ab, timeout_s: float | None = None
    ):
        """One LOGICAL blocking op issued as bounded server-side waits that
        are re-issued on expiry (-3) until data, cancellation, or
        ``timeout_s``.  ``make_ab(wait_ms) -> (a, b)`` builds the operands
        for each chunk.  Returns ``(status, payload)``, or ``(TIMED_OUT,
        None)`` when the caller deadline expires.  Only the first chunk is
        a fault-injection point — plan op indices count logical ops."""
        t_end = (
            time.monotonic() + timeout_s if timeout_s is not None else None
        )
        first = True
        while True:
            wait_s = self.block_wait_s(t_end)
            a, b = make_ab(int(wait_s * 1000))
            status, out = self.call(
                op, name, a, b, server_wait_s=wait_s, fault_point=first
            )
            first = False
            if status == -3:
                if t_end is not None and time.monotonic() >= t_end:
                    return TIMED_OUT, None
                continue
            return status, out

    def fail_fast(self) -> None:
        """Disable reconnect/recovery for all subsequent ops on this
        client.  Teardown-time best-effort signals (e.g. the chief's
        ``ps_shutdown`` push) must not spend the reconnect budget on a
        peer that may already be gone."""
        self._reconnect_deadline = 0.0

    def ping(self) -> None:
        status, _ = self.call(_PING)
        if status != 0:
            raise RuntimeError("PS server ping failed")

    def incarnation(self) -> int:
        status, _ = self.call(_INCARNATION)
        return status

    def stats(self) -> dict:
        """The server's whole counter table (r13 STATS): identity,
        incarnation/state token, request/connection counts, replication
        forward/sync/mirror counters and summed dedup/dropped counters —
        one JSON object per scrape, dtype-independent (the blob is raw
        bytes in 4-byte units, space-padded).  A pre-r13 server answers
        -2: surfaced as a loud PSError, never decoded as garbage."""
        status, blob = self.call(_STATS, raw=True)
        if status < 0 or not blob:
            raise PSError(
                f"PS at {self._host}:{self._port} does not answer STATS "
                f"(status {status}; pre-r13 server?)"
            )
        return json.loads(bytes(blob).decode())

    # -- membership leases (r14) --------------------------------------------

    def lease_acquire(self, name: str, ttl_s: float) -> int:
        """Acquire-or-renew the lease ``name`` (an opaque member string —
        see ``parallel.membership.pack_member``) for ``ttl_s`` seconds.
        Returns 1 when NEWLY acquired — a fresh member, or a re-acquire
        after the previous lease EXPIRED (the lapse signal a heartbeat
        watches for) — or 2 on a renewal of a live lease.  Replay-safe:
        a replayed acquire just renews again.  A pre-r14 server answers
        -2, surfaced as PSError so callers can degrade loudly."""
        status, _ = self.call(_LEASE_ACQUIRE, name, int(ttl_s * 1000))
        if status < 0:
            raise PSError(
                f"lease acquire {name!r} rejected ({status}); pre-r14 "
                "server, or a malformed member string"
            )
        return status

    def lease_release(self, name: str) -> bool:
        """Clean departure: drop the lease NOW instead of waiting out the
        TTL.  Idempotent; True when a live lease was released."""
        status, _ = self.call(_LEASE_RELEASE, name)
        if status < 0:
            raise PSError(f"lease release {name!r} rejected ({status})")
        return status == 1

    def lease_list(self) -> dict:
        """The coordinator's live-member registry: ``{"leases": [{"m":
        <member string>, "ttl_ms": ..., "age_ms": ..., "renewals": ...}],
        "expired_total": N}`` — expired entries already pruned (and
        counted) server-side.  Raw JSON blob like :meth:`stats`."""
        status, blob = self.call(_LEASE_LIST, raw=True)
        if status < 0 or not blob:
            raise PSError(
                f"PS at {self._host}:{self._port} does not answer "
                f"LEASE_LIST (status {status}; pre-r14 server?)"
            )
        return json.loads(bytes(blob).decode())

    # -- live resharding (r15) ----------------------------------------------

    def reshard_announce(self, version: int, blob: bytes) -> None:
        """Store ``blob`` as the coordinator's PENDING reshard record at
        epoch ``version`` (``parallel/reshard.py`` owns the schema).
        Idempotent — every joining shard task may announce the same
        record; refused for a version not above the committed one."""
        padded = blob + b" " * (-len(blob) % 4)
        status, _ = self.call(
            _RESHARD_BEGIN, "", version, raw_payload=True,
            payload=np.frombuffer(padded, np.uint8).view(np.float32),
        )
        if status < 0:
            raise PSError(
                f"reshard announce v{version} rejected ({status}): version "
                "not above the committed epoch, record oversized, or "
                "pre-r15 server"
            )

    def reshard_commit(self, version: int) -> None:
        """Promote the matching PENDING record to COMMITTED — the epoch
        flip every polling client converges to.  Idempotent when already
        committed at ``version``."""
        status, _ = self.call(_RESHARD_COMMIT, "", version)
        if status < 0:
            raise PSError(
                f"reshard commit v{version} rejected ({status}): no "
                "matching pending record (aborted, superseded, or pre-r15 "
                "server)"
            )

    def reshard_abort(self, version: int) -> bool:
        """Clear a matching PENDING record (the loud mid-transition
        bail-out); True when one was cleared."""
        status, _ = self.call(_RESHARD_ABORT, "", version)
        if status < 0:
            raise PSError(f"reshard abort v{version} rejected ({status})")
        return status == 1

    def reshard_poll(
        self, have_version: int = 0, *, pending: bool = False,
    ) -> tuple[int, bytes]:
        """The coordinator's reshard record: ``(version, blob)`` where the
        blob is non-empty only when ``version > have_version`` — the
        steady-state epoch poll is O(header), like an unchanged-step
        pull.  ``version`` 0 = no record.  A pre-r15 server answers -2,
        surfaced as ``(0, b"")`` so pollers degrade to the static
        topology silently (resharding simply never fires)."""
        status, blob = self.call(
            _RESHARD_GET, "", have_version, 1 if pending else 0, raw=True,
        )
        if status < 0:
            return 0, b""
        return status, bytes(blob).rstrip(b" ") if blob else b""

    def cancel_all(self) -> None:
        """Cancel blocked waiters on THIS client's tenant namespace: the
        request name is a key-prefix filter (r20) — empty for the default
        tenant (the whole space, the documented pre-tenant behavior), the
        ``t.<tenant>.`` prefix otherwise, so one tenant's teardown/reseed
        can never wake-and-fail another tenant's waiters."""
        self.call(_CANCEL_ALL, tenancy.tenant_prefix(self.tenant))


def _check(status: int, what: str) -> int:
    if status == -2:
        raise RuntimeError(f"PS server rejected {what} (bad object/request)")
    return status


# Wire packing of the (worker, seq) dedup tag — one definition, shared with
# the in-process ctypes wrappers (ps_server.cc layout, 15-bit worker).
_pack_tag = native._tag




class RemoteAccumulator:
    """API-compatible with native.GradientAccumulator, over the socket.

    On a client with a ``worker_tag``, applies are dedup-tagged: each
    logical apply gets the next per-object sequence number, retries of it
    replay the SAME number, and the server drops anything it has already
    processed — zero duplicate applications across reconnects."""

    def __init__(self, client: PSClient, name: str, num_elems: int):
        self._c, self._name, self._n = client, name, num_elems
        self._seq = 0
        _check(client.ensure_object(_ACC_GET, name, num_elems), "acc_get")
        if client.worker_tag is not None:
            # Announce this (possibly restarted) worker: the server forgets
            # the dead incarnation's sequences so our fresh 0-based stream
            # is not answered "duplicate".  Idempotent, replay-safe.
            _check(
                client.call(_ACC_RESET_WORKER, name, client.worker_tag)[0],
                "acc_reset_worker",
            )

    def apply(self, local_step: int, grad: np.ndarray) -> bool:
        if self._c.worker_tag is None:
            s, _ = self._c.call(
                _ACC_APPLY, self._name, local_step, payload=grad,
                replay_safe=False,
            )
            return _check(s, "acc_apply") == 1
        self._seq += 1
        s, _ = self._c.call(
            _ACC_APPLY_TAGGED, self._name, local_step,
            _pack_tag(self._c.worker_tag, self._seq), payload=grad,
        )
        # 1 = freshly accepted; 0 = stale-dropped; 2 = duplicate replay —
        # the first delivery's outcome (accepted OR dropped) is unknown, so
        # report False ("did not newly count"), matching
        # native.GradientAccumulator.apply_tagged.
        return _check(s, "acc_apply_tagged") == 1

    def take(self, num_required: int, timeout_s: float | None = None):
        """Blocking average; None when cancelled, ``TIMED_OUT`` when
        ``timeout_s`` expires.  Issued as bounded server-side waits so a
        dead PS surfaces between chunks and the reconnect path heals it."""
        s, out = self._c.timed_blocking(
            _ACC_TAKE, self._name, lambda w: (num_required, w), timeout_s
        )
        if s is TIMED_OUT:
            return TIMED_OUT
        return out if _check(s, "acc_take") >= 0 else None

    def set_global_step(self, step: int) -> None:
        _check(self._c.call(_ACC_SET_STEP, self._name, step)[0], "acc_set_step")

    @property
    def dropped(self) -> int:
        return _check(self._c.call(_ACC_DROPPED, self._name)[0], "acc_dropped")

    @property
    def deduped(self) -> int:
        return _check(self._c.call(_ACC_DEDUPED, self._name)[0], "acc_deduped")

    def cancel(self) -> None:
        self._c.cancel_all()


class RemoteTokenQueue:
    """API-compatible with native.TokenQueue."""

    def __init__(self, client: PSClient, name: str):
        self._c, self._name = client, name
        _check(client.ensure_object(_TQ_GET, name), "tq_get")

    def push(self, step: int, n: int = 1) -> None:
        _check(self._c.call(_TQ_PUSH, self._name, step, n)[0], "tq_push")

    def pop(self, timeout_s: float | None = None):
        """Blocking; token step, None when cancelled, ``TIMED_OUT`` when
        ``timeout_s`` expires first."""
        s, _ = self._c.timed_blocking(
            _TQ_POP, self._name, lambda w: (w, 0), timeout_s
        )
        if s is TIMED_OUT:
            return TIMED_OUT
        return s if s >= 0 else None

    def cancel(self) -> None:
        self._c.cancel_all()


class RemoteGradientQueue:
    """API-compatible with native.GradientQueue (tagged pushes on clients
    with a ``worker_tag`` — see RemoteAccumulator)."""

    def __init__(self, client: PSClient, name: str, num_elems: int, capacity: int = 16):
        self._c, self._name, self._n = client, name, num_elems
        self._seq = 0
        _check(client.ensure_object(_GQ_GET, name, num_elems, capacity), "gq_get")
        if client.worker_tag is not None:
            # See RemoteAccumulator: restarted-worker announcement.
            _check(
                client.call(_GQ_RESET_WORKER, name, client.worker_tag)[0],
                "gq_reset_worker",
            )

    def push(self, local_step: int, grad: np.ndarray) -> bool | None:
        """Tri-state like native.GradientQueue.push: True enqueued, False
        stale-dropped, None cancelled (termination signal)."""
        if self._c.worker_tag is None:
            s, _ = self._c.call(
                _GQ_PUSH, self._name, local_step, payload=grad,
                replay_safe=False,
            )
            return None if _check(s, "gq_push") < 0 else s == 1
        self._seq += 1
        tag = _pack_tag(self._c.worker_tag, self._seq)
        # Backpressure on a full queue becomes a dedup-safe ~2 s poll (the
        # server bounds its own space wait and answers -3).  Each re-issue
        # re-sends the payload, so the poll period is deliberately coarse;
        # the overall stall is bounded — a chief wedged this long is a job
        # failure, not backpressure.
        t_end = time.monotonic() + _PUSH_STALL_LIMIT_S
        first = True
        while True:
            s, _ = self._c.call(
                _GQ_PUSH_TAGGED, self._name, local_step, tag, payload=grad,
                server_wait_s=2.5, fault_point=first,
            )
            first = False
            if s == -3:
                if time.monotonic() >= t_end:
                    raise PSDeadlineError(
                        f"gradient queue {self._name!r} full for "
                        f"{_PUSH_STALL_LIMIT_S:.0f}s (chief stalled?)"
                    )
                continue
            _check(s, "gq_push_tagged")
            # 1 enqueued / 2 duplicate-of-enqueued -> True; 0 stale -> False.
            return None if s < 0 else s != 0

    def pop(self, timeout_s: float | None = None):
        """Blocking; (local_step, grad), None when cancelled+drained, or
        ``TIMED_OUT`` when ``timeout_s`` expires first."""
        s, out = self._c.timed_blocking(
            _GQ_POP, self._name, lambda w: (self._n, w), timeout_s
        )
        if s is TIMED_OUT:
            return TIMED_OUT
        return (s, out) if s >= 0 else None

    def set_min_step(self, step: int) -> None:
        _check(self._c.call(_GQ_SET_MIN, self._name, step)[0], "gq_set_min")

    @property
    def dropped(self) -> int:
        return _check(self._c.call(_GQ_DROPPED, self._name)[0], "gq_dropped")

    @property
    def deduped(self) -> int:
        return _check(self._c.call(_GQ_DEDUPED, self._name)[0], "gq_deduped")

    def cancel(self) -> None:
        self._c.cancel_all()


class RemoteParamStore:
    """Published (step, flat params) snapshot — the PS variable-hosting
    role; chief sets after every applied update, workers get before every
    gradient computation (SURVEY.md section 3.1 hot path).

    Versioned pulls (r7): ``get`` keeps a client-side (step, params) cache
    and issues ``PSTORE_GET_IF_NEWER`` with the cached step — when the
    published step hasn't advanced the server answers status-only (~12
    bytes) and the cached array is returned, so an unchanged-step pull
    costs O(header), not O(params).  The cache is invalidated on every
    reconnect (transport gap => local mirror unproven) and a reincarnated
    server re-fills it on the next pull.  Callers must treat the returned
    array as READ-ONLY: repeated unchanged-step gets share one buffer.
    ``cache_pulls=False`` restores the always-full-fetch behavior."""

    def __init__(
        self, client: PSClient, name: str, num_elems: int, *,
        cache_pulls: bool = True,
    ):
        self._c, self._name, self._n = client, name, num_elems
        self._cache_step = -1
        self._cache: np.ndarray | None = None
        self._cache_enabled = cache_pulls
        _check(client.ensure_object(_PSTORE_GET_OBJ, name, num_elems), "pstore_get_obj")
        if cache_pulls:
            client.on_reconnect(self.invalidate_cache)

    def invalidate_cache(self) -> None:
        self._cache_step, self._cache = -1, None

    def set(self, step: int, flat: np.ndarray) -> None:
        # Replay-safe: single-writer (the chief), so a replayed set can
        # never be reordered against a newer one on the same connection.
        _check(self._c.call(_PSTORE_SET, self._name, step, payload=flat)[0],
               "pstore_set")

    def _get_full(self) -> tuple[int, np.ndarray]:
        s, out = self._c.call(_PSTORE_GET, self._name)
        return _check(s, "pstore_get"), out

    def get(self) -> tuple[int, np.ndarray]:
        if not self._cache_enabled:
            return self._get_full()
        # Empty cache pulls with have_step=-1: a published store answers
        # with the full payload (same as a full get), an UNPUBLISHED one
        # answers status-only — so the poll loop waiting out a PS-restart
        # recovery window costs O(header) per probe, not a full zero-vector
        # ship per 50 ms from every worker connection.
        have = self._cache_step if self._cache is not None else -1
        s, out = self._c.call(_PSTORE_GET_IF_NEWER, self._name, have)
        if s == -2:
            # Pre-v2 server (op unknown): fall back to full pulls for the
            # life of this store rather than failing the caller.
            self._cache_enabled = False
            return self._get_full()
        _check(s, "pstore_get_if_newer")
        if out.size == 0:
            # The reconnect hook may have cleared the cache while this
            # very call was being replayed (_cache_step is then -1,
            # matching an empty store's step) — only a LIVE cache
            # satisfies the unchanged-step fast path.
            if s == self._cache_step and self._cache is not None:
                _OBS_PULL_HITS.inc()
                return s, self._cache
            if s < 0:
                # Never published: status-only, payload deliberately empty
                # (callers gate on step < 0 before touching the array).
                return s, out
            # Step moved without a payload (republished at a lower step,
            # e.g. a reseed the reconnect hook didn't see): distrust the
            # mirror and refetch in full.
            self.invalidate_cache()
            s, out = self._get_full()
        if s >= 0 and out.size:
            self._cache_step, self._cache = s, out
        return s, out
