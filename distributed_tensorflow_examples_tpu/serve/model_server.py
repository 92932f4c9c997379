"""Param-tracking model replica server: the online inference plane (r10).

After r9 the repo trains behind a resilient sharded parameter store but has
no process that answers a predict request.  The TensorFlow architecture
paper frames the PS pattern as the shared substrate for training AND
serving — parameter servers hand versioned params to any consumer — and
the tf.data-service PR (r8) showed the payoff of disaggregating a plane
onto the shared wire.  This module applies the same move to inference:

- :class:`ModelReplicaServer` — a replica speaking the shared
  ``parallel/wire.py`` framing under the ``msrv`` service tag.  It
  HOT-TRACKS training: a background refresher thread polls the (sharded)
  parameter store with ``PSTORE_GET_IF_NEWER`` (via
  ``ps_shard.ShardedParamStore`` / ``ps_service.RemoteParamStore``), so an
  unchanged model costs one O(header) round trip per shard and a changed
  one lands in a FRESH buffer the store never reuses — an in-flight batch
  holds its own ``(step, params)`` snapshot and can never tear.  Every
  predict response is stamped with the served ``model_step`` (the response
  status), so consumers can observe exactly which published update they
  were answered from.
- Dynamic micro-batching — requests from all connections coalesce through
  :class:`serve.batcher.DynamicBatcher` into one padded jitted apply
  (padding keeps the jit cache at ONE shape; row-independent models make
  the pad rows inert, so batched and unbatched outputs are byte-identical).
  A bounded queue answers an explicit OVERLOAD status past ``queue_depth``
  — admission control, so resilient clients back off instead of piling on.
- Fault posture — the replica process carries a fault role (``serve<i>``),
  ``die:after_reqs`` arms off the server's request counter, and the
  ``--job_name=serve`` task runs under the shared supervised-restart path
  (``train/ps_experiment._supervised_reexec``): a killed replica restarts,
  re-pulls the CURRENT params from the PS (zero coordination — the store
  is the rendezvous), and rejoins the client rotation.

Wire notes: frame layout / HELLO / zero-copy paths shared via
``parallel/wire.py``; payload lengths count BYTES (predict inputs/outputs
are mixed-dtype field dicts moved with the shared batch codec).  Op codes
are disjoint from both the PS range (1..27) and the data service's
(64..71), so a frame reaching the wrong service is refused, never
misinterpreted; the HELLO service identity makes even the refusal loud.
"""

from __future__ import annotations

import contextlib
import json
import logging
import os
import queue
import threading
import time
import typing
from collections import deque

import numpy as np

from ..parallel import ps_shard, server_core, tenancy, wire
from ..utils import compile_cache, faults, telemetry
from ..utils.metrics import LatencyRecorder, MetricsWriter
from . import batcher as batcher_lib

log = logging.getLogger("dtx.serve")

#: This wire's service identity (parallel/wire.py registry).
SERVICE = "msrv"
SERVICE_TAG = wire.SERVICE_TAGS[SERVICE]

# Op codes (SRV_*) — aliases into the ONE registry (wire.SRV_OPS), disjoint
# from the PS server's 1..27 and DSVC's 64..71 (dtxlint-enforced).
SRV_HELLO = wire.SRV_OPS["HELLO"]
SRV_PREDICT = wire.SRV_OPS["PREDICT"]
SRV_STATS = wire.SRV_OPS["STATS"]
SRV_SHUTDOWN = wire.SRV_OPS["SHUTDOWN"]
SRV_DECODE_OPEN = wire.SRV_OPS["DECODE_OPEN"]
SRV_DECODE_NEXT = wire.SRV_OPS["DECODE_NEXT"]
SRV_DECODE_CLOSE = wire.SRV_OPS["DECODE_CLOSE"]

#: Ops excluded from the request counter — derived from the one
#: control-plane registry (wire.CONTROL_OPS; dtxlint pins this site).
_SRV_CONTROL_OPS = frozenset(
    wire.SRV_OPS[n] for n in wire.CONTROL_OPS["msrv"]
)


def _tenant_of_request(op: int, name: str, a: int, b: int) -> str:
    """The server core's per-tenant admission attribution (r20): the
    tenant rides the ``name`` operand as a ``,t=<tenant>`` tag — absent
    (= the default tenant) on every untagged client's frames."""
    return tenancy.untag_name(name)[1]

# Response statuses (wire.SRV_STATUS aliases).  PREDICT success answers the
# served model_step (>= 0) as the status — the per-response staleness stamp
# costs zero extra bytes.
ERR = wire.SRV_STATUS["ERR"]
OVERLOAD = wire.SRV_STATUS["OVERLOAD"]
NO_MODEL = wire.SRV_STATUS["NO_MODEL"]
BAD_SESSION = wire.SRV_STATUS["BAD_SESSION"]
NO_DECODER = wire.SRV_STATUS["NO_DECODER"]

# The decode engine's share of the step thread's time, as leaf spans in the
# slot batcher's family (its ``name``, "decode").  They follow the batcher's
# ``decode/fill`` and precede its ``decode/emit``; none wraps another.
#: The wait for the chunk dispatched a call earlier, at the top of the next
#: call, once a chunk.  The engine adds to the span's ``/ns`` (one ``inc``,
#: ``_await_chunk``) the host's launch of the chunk and the time the chunk
#: had been on the device before the span was entered, so the sum over
#: ``/n`` stays what it was while one span held both: a chunk's launch and
#: its time on the device.
_SPAN_PREFILL = telemetry.span("decode/prefill")
_PREFILL_NS = telemetry.REGISTRY.counter("decode/prefill/ns")
#: The host's launch of a chunk (the runtime's ``PjitFunction(prefill_fn)``),
#: under the step in flight.
_SPAN_CHUNK_LAUNCH = telemetry.span("decode/chunk_launch")
#: The read of what the model counts on the device: a wait for everything
#: launched so far with nothing queued behind it, when ``stats()`` asked.
_SPAN_COUNTERS = telemetry.span("decode/counters")
#: Each row's token or its source, position and liveness, uploaded.
_SPAN_PREPARE = telemetry.span("decode/prepare")
#: Launching the jitted step (the runtime's ``PjitFunction(step_fn)``).
_SPAN_DISPATCH = telemetry.span("decode/dispatch")
#: Reading the selection of the step launched a call earlier: a wait on a
#: device that is mostly busy (with that step, then with the one just
#: launched), then ``[S]`` int32 to the host.
_SPAN_FETCH = telemetry.span("decode/fetch")
#: Each stepped session's token, count and ``done`` from what was read.
_SPAN_SELECT = telemetry.span("decode/select")
#: What the engine's calls took on the wall LESS their two waits on the
#: device (inside ``decode/fetch``, inside ``decode/prefill``): the engine's
#: own work, its spans' and the Python between them.  With ``decode/fill``
#: and ``decode/emit`` it is the host's loop, which has to fit under a step
#: for the device to set the pace.
_HOST_NS = telemetry.REGISTRY.counter("decode/host/ns")


#: The most prompt tokens one prefill chunk takes through the model (a
#: cache shorter than this makes it the cache's length).  Sessions that
#: decode wait one chunk longer for their next token whenever one runs, so
#: the size trades the first token of a prompt longer than a chunk against
#: how many token gaps carry a chunk.  Chosen on one v5e chip with
#: Cerebras-GPT-1.3B, 8 slots x 2048 (my chip runs, PR 25; PERF.md section
#: 6): a chunk reads the weights once whatever its size and takes 11.0 /
#: 12.6 / 15.6 ms at 128 / 256 / 512 beside a decode step of 23.4 ms; the
#: chat replay (prompts 16-768) needs 78 / 49 / 40 chunks a window, i.e. 6 /
#: 3.8 / 3.1 % of its steps carry one.
PREFILL_CHUNK = 512

#: The narrowest chunk the engine dispatches.  A chunk is as wide as the
#: tokens it carries: the engine compiles the chunk program at
#: ``PREFILL_CHUNK`` and at its halvings down to this floor
#: (:func:`chunk_widths`: 256 / 512) and dispatches the narrowest that
#: holds what its slot still owes, because a chunk bound by its products
#: and not by its weights takes as long as it is wide (Jamba2-3B 11.5 /
#: 14.1 / 23.8 ms at 128 / 256 / 512: PR 37's builder's chip runs) and every
#: decoding row waits it out.  Why 256 and not the lane tile, 128: every
#: width is one more program that each replica traces, lowers and loads
#: before its first answer, with the compile cache warm too - 3.0-6.4 s a
#: width for Jamba2-3B, whose start with 128 / 256 / 512 read 100-105 s
#: where one width reads 91-97 and two 94-99 (my chip runs, PR 40; PERF.md
#: section 6) - and the second halving buys a third of the first: 1.5-4.4
#: ms a chunk over the four families on record against 3.2-13.6 (PR 37's
#: builder's), and nothing at the 95th percentile of the token gaps, which
#: is a step and a 256-wide chunk with either.  Derived, not tuned to a
#: mix, and no setting: a ``PREFILL_CHUNK`` under twice the floor is the
#: one width.
PREFILL_FLOOR = 256


def chunk_widths(chunk: int) -> tuple[int, ...]:
    """The widths a chunk of at most ``chunk`` tokens is dispatched at,
    narrowest first: ``chunk`` and its halvings down to
    :data:`PREFILL_FLOOR` (an odd width is not halved)."""
    widths = [chunk]
    while widths[0] % 2 == 0 and widths[0] // 2 >= PREFILL_FLOOR:
        widths.insert(0, widths[0] // 2)
    return tuple(widths)


#: How long a ``DECODE_NEXT`` that finds nothing at its cursor is held for
#: an emission before it is answered empty and not done - what every empty
#: poll was answered at once before the replica held any.  A client looks at
#: its own deadline (``ServeClient.generate``'s ``deadline_s``) between two
#: polls, so this is how often it gets to; a tenth of a second is far under
#: the smallest ``op_timeout_s`` a client of this wire has (10 s) and under
#: ``session_idle_s`` (60 s), and long enough that a session still queued or
#: prefilling sends ten empty polls a second and not the hundred or two its
#: ``poll_s`` would.  No setting: nothing a deployment knows should move it.
DECODE_HOLD_S = 0.1


class _HeldPoll:
    """A ``DECODE_NEXT`` that found nothing, waiting with its session's
    ticket (``StreamTicket.when_ready``).  The ticket calls it from the
    thread that emits or ends the session - the step thread - and all it
    does there is put itself on the notifier's queue: the snapshot, the
    encoding and the reply are the notifier thread's."""

    __slots__ = ("conn", "sid", "ticket", "cursor", "due", "_hand_over")

    def __init__(self, hand_over, conn, sid: int, ticket, cursor: int):
        self._hand_over = hand_over
        self.conn = conn  # the request's reply handle
        self.sid = sid
        self.ticket = ticket  # None once answered (the notifier's mark)
        self.cursor = cursor
        self.due = time.monotonic() + DECODE_HOLD_S

    def __call__(self) -> None:
        self._hand_over(self)


def flat_param_spec(init_fn):
    """``(total_elems, unflatten)`` for the parameter STRUCTURE ``init_fn``
    builds — the shared ``ps_shard.flat_param_spec`` convention the
    training workers use (values always come from the param store; only
    shapes matter here)."""
    import jax

    template = init_fn(jax.random.key(0))
    if isinstance(template, tuple):  # init_fn returning (params, model_state)
        template = template[0]
    return ps_shard.flat_param_spec(template)


class _Flight(typing.NamedTuple):
    """One launched decode step whose selection the host has not read."""

    selection: object  # the step's ``[S]`` int32 on the device
    rows: list  # (ticket, row it emits from | None: emits nothing, done)
    held: int  # rows of sessions whose chunks were due
    rows_read: float  # cache positions the step's attention read, a slot
    params: object  # the served model's parameters it was launched with


class _DecodeEngine:
    """Stepped decode over a per-slot cache behind the sequence-slot
    batcher (r19).

    Model-agnostic: the model is what ``fns``, a ``models.decoding.
    DecodeFns``, says of it - ``init_cache(slots, max_len)`` (a per-slot
    cache pytree), ``step(params, cache, tokens[S], pos[S][, live[S]]) ->
    (logits [S, V], cache)``, one jitted apply that advances EVERY active
    session one position, ``prefill`` or ``None``, ``wants_live``, and
    ``step_rows_read`` / ``chunk_rows_read`` or ``None`` (a step, a chunk,
    is then taken to read all ``max_len`` rows of a slot: counters
    ``cache_rows_read``, ``prefill_rows_read``).  The engine owns the
    host-side session state (each session's position and counts), how a
    prompt reaches the cache and what is in flight, so batched decode is
    byte-identical to a session running alone: the slot array shape is
    FIXED, every row's math depends only on its own slot, and a session
    reads only what it wrote itself.

    Who picks the token.  The compiled step does: the engine jits ONE
    function, named ``step_fn``, that hands the model's ``step``
    ``where(from_host, tokens, prev)`` and returns ``(argmax(logits, -1)
    as int32 [S], cache)`` - greedy selection, the lowest index among
    equals as NumPy's has it, over the model's own float32 logits, which
    never leave the device.  ``prev`` is the step before's selection,
    still on the device (not donated: the host reads it after the next
    launch); ``from_host`` marks the rows whose token the host knows and
    the device does not - a prompt's token (a freshly seated row, a
    teacher-forced row, a held row) or token 0 (an empty row).  Everything
    else a step needs - positions, which rows are live, which sessions end -
    follows from COUNTS the host has without reading a token's value, so
    the engine launches step N+1 and only then reads step N's selection:
    the device runs N+1 while the host emits N and prepares N+2.  At most
    one step is in flight beyond the one being read (``ahead_steps``
    counts the launches made ahead).  A prefill chunk is one more member
    of the device's queue: it needs nothing from the host but its tokens,
    slot, offset and count, and the step behind it needs its cache, which
    the runtime orders (each program is donated the cache the one before
    it returns).  So a call that finds a chunk due dispatches the chunk
    BEHIND the step in flight, launches the next step BEHIND the chunk and
    only then reads the step in flight (``queued_chunks`` counts the chunks
    dispatched so: all but a parked engine's first and the one after a
    change of model).  The call after it first waits for that chunk, by the
    small array the chunk program returns beside the cache, before it
    dispatches anything: at most one chunk and two steps are ever on the
    device unread.  Nothing is launched across a change of the served
    model: a call that finds one while a step is in flight only collects;
    a call with no row left to step only collects too, so a session's last
    token is out before the loop parks.  The launch that is already out
    when a session's last token is read steps that session's row once past
    its end: an inert row (``idle_rows``), token 0 at position 0 and not
    live.

    What a row may do to its slot.  A row is LIVE when its session decodes
    in this step; an empty slot's row, the row of a seated session whose
    prompt chunks are still due and the row of a session stepped past its
    end are not.  Two kinds of model:

    - A model whose cache holds keys and values (``wants_live`` false) is
      not told: its rows that are not live compute inert rows, like the row
      batcher's pad rows - what such a row writes at its position the
      session's first real step writes again, and the attention mask
      confines each session to the positions it wrote itself.  A freed slot
      needs no cache reset.
    - A model whose cache holds a STATE that every step overwrites (a
      state-space layer: models/jamba.py) cannot compute an inert row: it
      would advance the state by a token that is not there.  Such a model
      says ``wants_live`` and promises: (a) a row that is not live leaves
      everything its slot owns unchanged; (b) a session starts from the
      zero state - the step at ``pos == 0`` and the chunk at ``offset ==
      0`` start there whatever the slot held, so a freed slot still needs
      no reset pass; (c) a chunk carries on from what the chunk before it
      left in the slot.  The engine uploads ``live`` beside tokens and
      positions for such a model and for no other.

    A model that supplies ``prefill(params, cache, tokens[C], slot,
    offset, n_valid) -> cache`` (it enters ONE slot's positions
    ``[offset, offset + n_valid)`` into that slot's cache and touches no
    other slot) has its prompts PREFILLED: before the decode step a call
    runs at most one chunk of at most ``PREFILL_CHUNK`` tokens, for the
    longest-seated session whose prompt is not yet cached, so every other
    session waits at most one step plus one chunk for its next token,
    whatever the prompt lengths or the burst.  The chunk is as wide as the
    tokens it carries: ``tokens[C]`` is the narrowest of
    :func:`chunk_widths` that holds them, padded with token 0 past
    ``n_valid``, so ``prefill`` is traced once a width - one ``jax.jit``,
    a compiled program a width, each run once on a chunk of no valid token
    before the first session is answered - and must take every one of them
    (counters ``prefill_width``, the tokens dispatched with the padding in,
    beside ``prefill_tokens``, the valid ones).  A session being prefilled
    holds its slot with a row that is not live; once all but its last
    prompt token are cached it is an ordinary decode row at ``pos = P - 1``
    and the next step emits its first token.  Without ``prefill`` the
    prompt is teacher-forced through the decode step, a token a step.

    What a model counts on the device.  A model whose cache tree has an
    entry ``counters`` - a dict of small int32 arrays that its step and its
    chunk add to, in place, on the device (models/longcat.py: which experts
    a step's tokens chose is known only there) - has them reported by
    ``stats()`` as ``model_<name>`` (the replica's ``decode_model_<name>``;
    an array as the sum of its elements), summed over everything launched
    since the engine started.  ``stats()``
    only ASKS: the read is made on the step thread, at the top of its next
    call, when it has no row left to step, or as it parks (``_parked``:
    rows their clients closed leave without a call, and ``stats()`` wakes
    a thread that is parked already), where that thread holds the cache and
    no program has been handed it - never from a handler's thread against
    a buffer that a launch may have donated meanwhile.  No step fetches anything
    for it; the asked-for read waits for the step in flight like any read
    of the cache would.  The device's int32 sums may wrap: the host keeps
    the totals and adds each read's difference modulo 2**32.  A cache
    without the entry is driven exactly as before and reports none.

    What the engine hands over.  The cache is DONATED to the step and to
    the chunk alike (the engine holds its only reference), so a program
    that writes a row in place costs that row and not a second cache.  A
    launch, a read or a chunk that raises - a chunk at its dispatch or at
    the wait for it - fails every active session and may have cost the
    engine its cache, so it is left a fresh cache, a fresh ``prev``,
    NOTHING in flight and no chunk outstanding - a freed slot needs no
    cache state.  An empty slot's row is stepped with token 0 at position
    0, not where its last session stood: a step that reads no further than
    its deepest row is held to the sessions that are seated.
    """

    def __init__(
        self, model_getter, fns, *, slots: int, max_len: int, max_sessions: int,
    ):
        import jax

        self._get_model = model_getter  # () -> (step, params) | None
        self._init_cache = fns.init_cache
        self._cache = fns.init_cache(slots, max_len)
        self._step_jit = jax.jit(_selecting(fns.step), donate_argnums=1)
        self._wants_live = fns.wants_live
        # How far into the cache a step reads: all of it, unless told.
        self._rows_read = fns.step_rows_read or (lambda pos, live, max_len: max_len)
        # What the engine holds for its slots (state, keys and values).
        self.state_bytes = sum(
            int(a.nbytes) for a in jax.tree.leaves(self._cache)
        )
        self.slots = int(slots)
        self.max_len = int(max_len)
        self._prefill_jit = (
            jax.jit(_echoing(fns.prefill), donate_argnums=1)
            if fns.prefill else None
        )
        self._chunk = min(PREFILL_CHUNK, self.max_len)
        self._widths = chunk_widths(self._chunk)
        # How far into the slot's cache a chunk reads: all of it, unless told.
        self._chunk_rows_read = fns.chunk_rows_read or (
            lambda offset, chunk, max_len: max_len
        )
        self._prefill_warm = False
        self.prefill_chunks = 0
        self.prefill_tokens = 0  # valid tokens; padding is not counted
        self.prefill_width = 0  # tokens dispatched: the chunks' widths
        # Chunks dispatched while a step launched earlier had not been read.
        self.queued_chunks = 0
        # The chunk on the device that the host has not waited for: what its
        # program echoes (the cache went on to the step behind it) and when
        # it began there.
        self._chunk_echo = None
        self._chunk_began_ns = 0
        self._chunk_launch_ns = 0
        # Slot-steps a seated session was not live: its chunks were due.
        self.held_rows = 0
        # Cache positions of each slot the steps' attention read, summed.
        self.cache_rows_read = 0
        # ... and of the chunk's slot the chunks' attention read, summed.
        self.prefill_rows_read = 0
        # Steps launched while the step before them had not been read, and
        # slot-steps of sessions stepped past their last token.
        self.ahead_steps = 0
        self.idle_rows = 0
        # Reads that found their step done: the host set that step's pace.
        self.reads_ready = 0
        # What the call under way has spent waiting on the device.
        self._blocked_ns = 0
        # The last launch's selection, on the device, and that launch while
        # the host has not read it.
        self._selection = self._no_selection()
        self._flight: _Flight | None = None
        # What the model counts on the device (class docstring): the host's
        # totals, the device's values at the last read, and the handshake.
        self._counts = isinstance(self._cache, dict) and "counters" in self._cache
        self.model_counters: dict = {}
        self._counters_seen: dict = {}
        self._counters_asked = threading.Event()
        self._counters_fresh = threading.Event()
        if self._counts:
            self._read_counters()
        self.batcher = batcher_lib.SlotBatcher(
            self._run_step, slots=self.slots, max_sessions=max_sessions,
            on_park=self._parked,
        )

    def _no_selection(self):
        import jax.numpy as jnp

        return jnp.zeros((self.slots,), jnp.int32)

    def open(self, prompt: np.ndarray, max_new_tokens: int):
        """Admit one greedy decode session; returns its StreamTicket.
        Raises ValueError on a prompt/budget the cache cannot hold, and
        ``batcher.Overloaded`` past the session bound."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        n = int(max_new_tokens)
        if prompt.size < 1:
            raise ValueError("decode needs a non-empty prompt")
        if n < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {n}")
        if prompt.size + n > self.max_len:
            raise ValueError(
                f"{prompt.size} prompt + {n} new tokens exceeds the "
                f"replica's decode_max_len={self.max_len}"
            )
        # Prompt tokens the prefill owes the cache before the slot decodes
        # (all but the last).
        prefill = prompt.size - 1 if self._prefill_jit else 0
        return self.batcher.open({
            "prompt": prompt, "prefill": prefill,
            "cached": 0,  # how many of them are in the cache
            # Where the session's next step stands: it starts where its
            # cached prompt ends - at position 0 feeding its first prompt
            # token, or (prefilled) at its last prompt token - and its last
            # step, which emits its n-th token, stands one short of ``end``.
            # The cache needs no reset (see the class docstring).
            "pos": prefill, "end": prompt.size - 1 + n,
        })

    def _read_counters(self) -> None:
        """Step thread only (or before it exists): fold what the device has
        counted since the last read into the host's totals."""
        import jax

        self._counters_asked.clear()  # one that comes during the read waits
        now = jax.device_get(self._cache["counters"])
        for name, value in now.items():
            value = np.asarray(value).astype(np.int64)
            seen = self._counters_seen.get(name, 0)
            self.model_counters[name] = (
                self.model_counters.get(name, 0) + int(((value - seen) % 2**32).sum())
            )
            self._counters_seen[name] = value
        self._counters_fresh.set()

    @contextlib.contextmanager
    def _cache_donated(self):
        """Around a program the cache is donated to, and around the read of
        what one selected: a failure may have cost the engine its cache and
        the selection the next launch would be fed, so it gets fresh ones
        and nothing stays in flight (the failure frees every slot, and a
        freed slot needs no cache state)."""
        try:
            yield
        except BaseException:
            self._flight = self._chunk_echo = None
            self._cache = None  # never two caches on the device
            self._cache = self._init_cache(self.slots, self.max_len)
            self._counters_seen = {}  # the fresh cache counts from zero
            self._selection = self._no_selection()
            raise

    def _prefill(self, params, slot: int, tokens, offset: int, n_valid: int,
                 width: int):
        """Dispatch one chunk of ``width`` tokens behind whatever is on the
        device and hand its cache on; returns what the program echoes, for
        the host to wait on.  Inside ``_cache_donated()``."""
        buf = np.zeros((width,), np.int32)
        buf[:n_valid] = tokens
        self._cache, echo = self._prefill_jit(
            params, self._cache, buf, np.int32(slot), np.int32(offset),
            np.int32(n_valid),
        )
        return echo

    def _await_chunk(self) -> None:
        """Top of a call, before anything is dispatched: wait for the chunk
        the call before dispatched.  The device is busy with it and then
        with the step queued behind it, so the wait idles nothing, and a
        chunk that failed on the device fails here.  The span holds the
        wait; the chunk had been running since ``_chunk_began_ns``, under
        the host's emit and fill, and that stretch and the chunk's launch
        are added to its sum."""
        echo, self._chunk_echo = self._chunk_echo, None
        if echo is None:
            return
        import jax

        _PREFILL_NS.inc(
            self._chunk_launch_ns + time.perf_counter_ns() - self._chunk_began_ns)
        with self._cache_donated(), _SPAN_PREFILL:
            jax.block_until_ready(echo)
        self._blocked_ns += _SPAN_PREFILL.last_ns

    def _chunk_due(self, slots) -> int | None:
        """The slot whose chunk runs next: the longest-seated session's
        whose prompt is not yet in the cache."""
        waiting = [
            (t.seated_ns, i) for i, t in enumerate(slots)
            if t is not None and t.state["cached"] < t.state["prefill"]
        ]
        return min(waiting)[1] if waiting else None

    def _prefill_one(self, params, slots, i: int | None) -> None:
        """At most one chunk, dispatched and not waited for: the next of
        slot ``i``'s prompt."""
        import jax

        with self._cache_donated():
            if not self._prefill_warm:
                # Every program exists before the first session is
                # answered, whatever its prompt's length: a chunk of no valid
                # token compiles the chunk program of its width and writes
                # nothing.
                for width in self._widths:
                    jax.block_until_ready(
                        self._prefill(params, 0, (), 0, 0, width))
                self._prefill_warm = True
            if i is None:
                return
            st = slots[i].state
            done = st["cached"]
            n = min(self._chunk, st["prefill"] - done)
            width = next(w for w in self._widths if w >= n)
            with _SPAN_CHUNK_LAUNCH:
                self._chunk_echo = self._prefill(
                    params, i, st["prompt"][done:done + n], done, n, width)
            # Booked onto ``decode/prefill/ns`` when the chunk is waited for.
            self._chunk_launch_ns = _SPAN_CHUNK_LAUNCH.last_ns
            # With nothing ahead of it the chunk begins now; behind a step
            # in flight, when that step's read returns (``_run_step``).
            self._chunk_began_ns = time.perf_counter_ns()
            st["cached"] = done + n
            self.prefill_chunks += 1
            self.prefill_tokens += n
            self.prefill_width += width
            self.prefill_rows_read += self._chunk_rows_read(
                done, width, self.max_len)

    def _parked(self) -> None:
        """The step thread, about to park: a ``stats()`` that asked for the
        counters is answered here too.  Sessions their clients closed leave
        without one more ``_call``, so the ask a call would have answered
        would else wait out its 2 s and read what an earlier ask left."""
        if self._counts and self._counters_asked.is_set():
            with self._cache_donated(), _SPAN_COUNTERS:
                self._read_counters()

    def _run_step(self, slots):
        """One call of the batcher's loop (``_call``), timed: what it took
        less what it waited on the device goes to ``decode/host/ns``."""
        t0 = time.perf_counter_ns()
        self._blocked_ns = 0
        results = self._call(slots)
        _HOST_NS.inc(time.perf_counter_ns() - t0 - self._blocked_ns)
        return results

    def _call(self, slots):
        """Wait for the chunk the call before dispatched, dispatch the chunk
        that is due, launch the step for ``slots`` behind it, THEN read the
        step launched by the call before and hand over its results -
        ``(ticket, emits, done)`` for each session that step stepped - or
        None when there is no such step yet."""
        self._await_chunk()
        model = self._get_model()
        flight = self._flight
        stepping = any(
            t is not None and t.state["pos"] < t.state["end"] for t in slots
        )
        if self._counts and (self._counters_asked.is_set() or not stepping):
            with self._cache_donated(), _SPAN_COUNTERS:
                self._read_counters()
        if flight is not None and (
            not stepping or model is None or model[1] is not flight.params
        ):
            self._flight = None
            return self._collect(flight)
        if model is None:
            raise _NoModel()
        _step, params = model
        chunk = None
        if self._prefill_jit is not None:
            chunk = self._chunk_due(slots)
            self._prefill_one(params, slots, chunk)
        self._flight = self._launch(params, slots)
        if flight is None:
            return None
        self.ahead_steps += 1
        results = self._collect(flight)
        if chunk is not None:
            self.queued_chunks += 1
            self._chunk_began_ns = time.perf_counter_ns()
        return results

    def _launch(self, params, slots) -> _Flight:
        import jax.numpy as jnp

        with _SPAN_PREPARE:
            # Fresh arrays every launch: the host goes on to the next
            # launch while this one's uploads may still be read.
            tokens = np.zeros((self.slots,), np.int32)
            pos = np.zeros((self.slots,), np.int32)
            from_host = np.ones((self.slots,), bool)
            live = np.zeros((self.slots,), bool)
            rows, held = [], 0
            for i, t in enumerate(slots):
                if t is None:
                    continue
                st = t.state
                p, prompt = st["pos"], st["prompt"]
                if p >= st["end"]:
                    # Its last token is asked for and not yet read, so the
                    # batcher still seats it: the row stays inert.
                    self.idle_rows += 1
                    continue
                pos[i] = p
                if p < len(prompt):
                    tokens[i] = prompt[p]  # seated, teacher-forced or held
                else:
                    from_host[i] = False  # the step before selected it
                if st["cached"] < st["prefill"]:
                    rows.append((t, None, False))  # held: its chunks are due
                    held += 1
                    continue
                live[i] = True
                st["pos"] = p + 1
                # The step that is fed the prompt's last token, and every
                # one after it, emits what it selects; the one that stands
                # one short of ``end`` is the session's last.
                rows.append(
                    (t, i if p + 1 >= len(prompt) else None, p + 1 == st["end"])
                )
            args = [jnp.asarray(tokens), jnp.asarray(from_host), jnp.asarray(pos)]
            if self._wants_live:
                args.append(jnp.asarray(live))
            rows_read = self._rows_read(pos, live, self.max_len)
        with self._cache_donated(), _SPAN_DISPATCH:
            self._selection, self._cache = self._step_jit(
                params, self._cache, self._selection, *args
            )
        return _Flight(self._selection, rows, held, rows_read, params)

    def _collect(self, flight: _Flight) -> list:
        with self._cache_donated():
            if flight.selection.is_ready():
                self.reads_ready += 1
            with _SPAN_FETCH:
                selected = np.asarray(flight.selection)
        self._blocked_ns += _SPAN_FETCH.last_ns
        with _SPAN_SELECT:
            results = [
                (t, [] if i is None else [int(selected[i])], done)
                for t, i, done in flight.rows
            ]
        # Counted where the batcher counts the step: when it has run.
        self.held_rows += flight.held
        self.cache_rows_read += flight.rows_read
        return results

    def stats(self) -> dict:
        s = self.batcher.stats()
        s["max_len"] = self.max_len
        s["prefill_chunks"] = self.prefill_chunks
        s["prefill_tokens"] = self.prefill_tokens
        s["prefill_width"] = self.prefill_width
        s["queued_chunks"] = self.queued_chunks
        s["held_rows"] = self.held_rows
        s["cache_rows_read"] = self.cache_rows_read
        s["prefill_rows_read"] = self.prefill_rows_read
        s["ahead_steps"] = self.ahead_steps
        s["idle_rows"] = self.idle_rows
        s["reads_ready"] = self.reads_ready
        s["state_bytes"] = self.state_bytes
        if self._counts:
            # Ask the step thread and give it a step and a chunk's time: it
            # answers from its next call, or from ``_parked`` where its last
            # rows were closed under it (woken, if it is parked already).
            self._counters_fresh.clear()
            self._counters_asked.set()
            if self.batcher.wake():
                self._counters_fresh.wait(2.0)
            s.update({f"model_{k}": v for k, v in self.model_counters.items()})
        return s

    def stop(self) -> None:
        import jax

        self.batcher.stop()
        flight, self._flight = self._flight, None
        echo, self._chunk_echo = self._chunk_echo, None
        if flight is not None or echo is not None:
            # The step thread is gone and its sessions failed: leave no
            # work on the device behind.
            try:
                jax.block_until_ready(
                    (echo, flight.selection if flight is not None else None))
            except Exception:  # noqa: BLE001 — a stop goes on to the end
                log.warning("what was on the device at stop failed",
                            exc_info=True)
        # A stopped engine holds nothing on the device (see the replica's
        # ``stop``).
        self._cache = self._selection = None


def _echoing(model_prefill):
    """The chunk program the engine compiles: ``model_prefill`` returning,
    beside the cache, ``n_valid`` as it was given.  The cache is donated on
    to the step launched behind the chunk; the echo is what the host can
    still wait on, and where a failed chunk surfaces.  Named ``prefill_fn``:
    the compiled program's name, ``jit_prefill_fn``, is how a device
    trace's readers find it."""

    def prefill_fn(params, cache, tokens, slot, offset, n_valid):
        return model_prefill(params, cache, tokens, slot, offset, n_valid), n_valid

    return prefill_fn


def _selecting(model_step):
    """The decode program the engine compiles: ``model_step`` fed each
    row's token from the host or from the step before, returning what it
    selects and not its logits.  Named ``step_fn``: the compiled program's
    name, ``jit_step_fn``, is how a device trace's readers find it."""
    import jax
    import jax.numpy as jnp

    def step_fn(params, cache, prev, tokens, from_host, pos, *live):
        logits, cache = model_step(
            params, cache, jnp.where(from_host, tokens, prev), pos, *live
        )
        # The selection is over the logits AS THE MODEL RETURNS THEM, in
        # their type: fused into the product that makes them, the compiler
        # compares that product's float32 sums and not their bfloat16
        # roundings, and picks another token wherever two of those tie
        # (most sessions of Cerebras-GPT-1.3B within some tens of tokens;
        # my chip runs, PR 30).  The barrier has them written out first.
        logits = jax.lax.optimization_barrier(logits)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache

    return step_fn


class ModelReplicaServer:
    """One serving replica: PS-tracking model + micro-batched predict.

    ``init_fn``       builds the parameter structure (shapes/treedef); the
                      VALUES are pulled from the parameter store.
    ``predict_fn``    ``predict_fn(params, inputs: dict) -> array | dict``;
                      must be row-wise in the leading dim (outputs row i
                      depend only on inputs row i) — that is what makes
                      padded batching exact and the scatter well-defined.
    ``ps_addrs``      the shard servers in shard order (``--ps_hosts``).
                      May be EMPTY in pin mode (a registry-only replica
                      needs no PS at all — membership then stays off).
    ``max_batch`` / ``max_wait_ms`` / ``queue_depth``
                      the micro-batcher knobs (serve/batcher.py).
    ``refresh_ms``    param-poll cadence; each poll is O(header) per shard
                      while the published step is unchanged.

    Registry pin mode (r19): with ``registry_dir`` + ``model_version``
    the replica serves an IMMUTABLE registry snapshot instead of
    hot-tracking the PS — the version loads once at construction, a
    lease-style PIN protects it from registry GC for the replica's
    lifetime (renewed by the refresher thread), and ``model_version``
    stamps the HELLO answer, every predict/decode response and STATS, so
    pools can route and account per version (canary vs stable).

    Decode serving (r19): ``decode_fns``, a ``models.decoding.DecodeFns``
    (what every served model's ``serve_decode_fns(cfg)`` gives) or the plain
    ``(init_cache_fn, step_fn[, prefill_fn])`` that is its first fields,
    adds the stepped KV-cache decode path — stateful sessions behind the
    sequence-slot batcher, streamed token responses over the
    DECODE_OPEN/NEXT/CLOSE wire (``serve.ServeClient.generate`` is the
    client side).  Its fields: ``init_cache``, ``step``, ``prefill`` (puts a
    seated prompt into the cache a chunk per forward pass instead of a token
    per decode step), ``wants_live`` (the step is told which rows may change
    what their slots own), ``step_rows_read`` and ``chunk_rows_read``
    (:class:`_DecodeEngine`).
    """

    def __init__(
        self, init_fn, predict_fn, ps_addrs, *, port: int = 0,
        loopback_only: bool = True, max_batch: int = 32,
        max_wait_ms: float = 5.0, queue_depth: int = 128,
        refresh_ms: float = 50.0, op_timeout_s: float | None = 10.0,
        reconnect_deadline_s: float = 60.0, role: str | None = None,
        metrics_dir: str | None = None, metrics_every: int = 100,
        membership: bool = True, lease_ttl_s: float = 10.0,
        advertise_addr: str | None = None, ps_replicas: int = 1,
        layout_version: int = 0, follow_reshard: bool = True,
        handler_workers: int = 8, queue_deadline_ms: float = 0.0,
        registry_dir: str | None = None, model_name: str = "default",
        model_version: int | None = None, pin_ttl_s: float = 30.0,
        decode_fns: tuple | None = None, decode_slots: int = 4,
        decode_max_len: int = 512, decode_max_sessions: int = 64,
        session_idle_s: float = 60.0,
        tenant: str = tenancy.DEFAULT_TENANT,
        tenant_quotas: dict | None = None,
    ):
        import jax

        from ..parallel import reshard
        from . import registry as registry_lib

        compile_cache.enable()
        telemetry.count_compiles()
        total, self._unflatten = flat_param_spec(init_fn)
        self._predict = jax.jit(predict_fn)
        self.role = role if role is not None else (
            faults.current_role() or "serve0"
        )
        # The tenant this replica serves FOR (r20): scopes its PS param
        # namespace (hot-tracking pulls the tenant's own ``params``
        # object), its registry model namespace and pin identity, and its
        # membership lease.  The default tenant changes nothing.
        self.tenant = (
            tenant if tenant == tenancy.DEFAULT_TENANT
            else tenancy.check_tenant(tenant)
        )
        self._op_timeout_s = op_timeout_s
        self._reconnect_deadline_s = reconnect_deadline_s
        # Registry pin (r19): a pinned replica serves one immutable
        # version for its whole lifetime; version 0 means hot-tracking.
        # The registry namespace is tenant-qualified (r20): tenant
        # ``runa``'s model ``m`` is the registry entry ``t.runa.m`` — two
        # tenants' models can share a bare name without sharing bytes.
        self.model_version = int(model_version or 0)
        self.model_name = tenancy.qualify(self.tenant, model_name)
        self._registry = (
            registry_lib.ModelRegistry(registry_dir) if registry_dir else None
        )
        self._pinned = self._registry is not None and self.model_version > 0
        if self.model_version > 0 and self._registry is None:
            raise ValueError(
                f"model_version={self.model_version} needs a registry_dir "
                "to load it from"
            )
        self._pin_ttl_s = max(5.0, float(pin_ttl_s))
        self._next_pin_renew = 0.0
        ps_addrs = list(ps_addrs or [])
        if not ps_addrs and not self._pinned:
            raise ValueError(
                "a hot-tracking replica needs ps_addrs (only a registry-"
                "pinned replica can run PS-free)"
            )
        if ps_addrs:
            self._group = ps_shard.ShardedPSClients(
                ps_addrs, role=self.role, op_timeout_s=op_timeout_s,
                reconnect_deadline_s=reconnect_deadline_s,
                replicas=ps_replicas, layout_version=layout_version,
                tenant=self.tenant,
            )
            self._layout = self._group.layout_for(total)
            self._pstore = ps_shard.ShardedParamStore(
                self._group, "params", self._layout
            )
        else:
            self._group = self._layout = self._pstore = None
            membership = False
        # Live resharding (r15): the refresher polls the coordinator for a
        # committed layout epoch (O(header) while unchanged) and swaps its
        # whole PS-side onto the new topology — a replica keeps
        # hot-tracking through an N→M reshard with zero restarts.  A
        # PINNED replica never follows: its params come from the registry,
        # and its PS legs (when present) serve membership only.
        self._reshards = 0
        self._follower = (
            reshard.EpochFollower(
                self._group.coordinator, layout_version,
                max(0.5, refresh_ms / 1e3),
            )
            if follow_reshard and self._group is not None and not self._pinned
            else None
        )
        self.max_batch = int(max_batch)
        self._refresh_s = max(refresh_ms, 1.0) / 1e3
        # The served model: an immutable (step, params) tuple swapped by
        # ONE reference assignment.  A changed pull lands in a fresh buffer
        # (the store's contract), so a batch holding the previous tuple is
        # never torn by the swap.
        self._model: tuple[int, object] | None = None
        if self._pinned:
            # Pin mode: the version loads ONCE, here — a replica that
            # cannot load its pinned version must fail its construction
            # loudly (the deploy controller's signal to not route to it),
            # never come up serving NO_MODEL forever.
            step, flat, _manifest = self._registry.load(
                self.model_name, self.model_version
            )
            self._model = (int(step), jax.device_put(self._unflatten(flat)))
            self._registry.pin(
                self.model_name, self.model_version, self.role,
                ttl_s=self._pin_ttl_s, tenant=self.tenant,
            )
            self._next_pin_renew = time.monotonic() + self._pin_ttl_s / 3
        self._incarnation = int.from_bytes(os.urandom(4), "little") | 1
        self._lock = threading.Lock()
        # The wedged-apply backstop (the 120 s bound the old blocking
        # path got from ticket.result): in-flight predict tickets are
        # tracked with a deadline and the refresher thread sweeps
        # overdue ones, resolving them with TimeoutError — the resolve
        # callback then answers a loud ERR and frees the connection.
        # Ticket resolution is idempotent, so a genuine late resolve
        # racing the sweep is harmless.  No extra thread, no per-request
        # timer: bounded threads stay bounded.
        self._ticket_deadline_s = 120.0
        self._pending_tickets: dict = {}  # ticket -> deadline (monotonic)
        self._predicts = 0
        self._refreshes = 0
        self._refresh_errors = 0
        self._overloads = 0
        self.latency = LatencyRecorder()
        self._writer = MetricsWriter(metrics_dir) if metrics_dir else None
        self._metrics_every = max(1, metrics_every)
        self._batcher = batcher_lib.DynamicBatcher(
            self._run_batch, max_batch=max_batch, max_wait_ms=max_wait_ms,
            queue_depth=queue_depth,
        )
        # Decode serving (r19): stateful sessions behind the sequence-slot
        # batcher.  Session ids are handed to clients as the DECODE_OPEN
        # status; the table maps them to stream tickets, and the refresher
        # sweeps sessions nobody polled for ``session_idle_s``.
        self._engine = None
        if decode_fns is not None:
            # Here, so a replica without a decoder loads no model.
            from ..models.decoding import DecodeFns

            self._engine = _DecodeEngine(
                lambda: self._model, DecodeFns(*decode_fns), slots=decode_slots,
                max_len=decode_max_len, max_sessions=decode_max_sessions,
            )
        self._session_idle_s = float(session_idle_s)
        self._sessions: dict[int, list] = {}  # sid -> [ticket, last_poll]
        self._next_sid = 1
        self._decode_opens = 0
        # The held poll: a DECODE_NEXT that finds nothing waits with its
        # ticket (at most ``DECODE_HOLD_S``) instead of being answered empty.
        # ``_fired`` takes the polls whose tickets called; ``_held`` keeps
        # every held poll in the order its hold runs out.  One thread, the
        # notifier, answers both, so a poll is answered once and the step
        # thread only ever puts on a queue.
        self._decode_polls = 0
        self._decode_polls_held = 0
        self._decode_polls_expired = 0
        self._fired: queue.SimpleQueue = queue.SimpleQueue()
        self._held: deque = deque()
        self._notifier = None
        if self._engine is not None:
            self._notifier = threading.Thread(
                target=self._notify_loop, daemon=True, name="msrv-notify"
            )
            self._notifier.start()
        self._stop = threading.Event()
        self.shutdown_requested = threading.Event()
        # The shared server runtime (r17): selector-driven I/O, bounded
        # handler pool, per-connection write buffering, HELLO routing and
        # the request counter live in parallel/server_core.py.  PREDICT
        # goes ASYNC through the batcher's resolve callback, so the pool
        # never parks a thread per in-flight predict — concurrency is
        # bounded by the batcher's admission control, not by threads.
        self._core = server_core.ServerCore(
            port=port, loopback_only=loopback_only, name="msrv",
            workers=handler_workers, tenant_quotas=tenant_quotas,
        )
        # Shed answers carry a backoff HINT (r18): roughly two batch
        # windows — the time a queue slot takes to free under load — so
        # pools back off for a meaningful beat instead of re-hammering.
        self._retry_after_ms = max(20, int(2 * max_wait_ms))
        self._core.add_service(server_core.Service(
            SERVICE, self._handle,
            control_ops=_SRV_CONTROL_OPS,
            tenant_of=_tenant_of_request,
            error_status=ERR,
            # PREDICT batches are the only request payloads; bound them
            # at the write-buffer bound rather than the frame ceiling.
            max_payload=256 << 20,
            # Admission policy (r18): a predict that sat in the dispatch
            # queue past this budget (or past the deadline its caller
            # stamped on the frame) is shed before a worker touches it.
            # 0 = client-stamped deadlines only.
            queue_deadline_s=(
                queue_deadline_ms / 1e3 if queue_deadline_ms else None
            ),
            retry_after_ms=self._retry_after_ms,
            # The msrv HELLO version word (r19): a dialing pool learns the
            # served registry version (0 = hot-tracking) at connect, before
            # routing a single predict — canary-weighted routing's
            # discovery half.
            hello_extra=lambda: wire.HELLO_VERSION_TAIL.pack(
                self.model_version
            ),
        ))
        self._core.start()
        self.port = self._core.port
        # Membership (r14): announce this replica — WITH its dialable
        # address — in the coordinator's lease registry, so an elastic
        # serve pool (and dtxtop) discovers dynamically-started replicas
        # from the registry instead of a static --serve_hosts list.
        self._heartbeat = None
        if membership:
            from ..parallel import membership as membership_lib

            self._heartbeat = membership_lib.LeaseHeartbeat(
                self._group.replica_addrs[0], self.role, kind="serve",
                addr=advertise_addr or f"127.0.0.1:{self.port}",
                ttl_s=lease_ttl_s, role=self.role,
                op_timeout_s=op_timeout_s,
                reconnect_deadline_s=reconnect_deadline_s,
                tenant=self.tenant,
            )
        self._refresher = threading.Thread(
            target=self._refresh_loop, daemon=True, name="msrv-refresh"
        )
        self._refresher.start()
        log.info(
            "model replica %s serving on port %d (%s, max_batch=%d, "
            "incarnation %d)",
            self.role, self.port,
            (
                f"pinned {self.model_name}/v{self.model_version}"
                if self._pinned
                else f"{self._group.num_shards} PS shard(s)"
            ),
            self.max_batch, self._incarnation,
        )

    # -- lifecycle -----------------------------------------------------------

    def request_count(self) -> int:
        """Requests handled so far — the ``die:after_reqs`` fault trigger
        for a serve task (same contract as the PS / data servers).  The
        counter lives in the server core, which excludes the control-plane
        ops (wire.CONTROL_OPS)."""
        return self._core.request_count()

    @property
    def model_step(self) -> int:
        m = self._model
        return -1 if m is None else m[0]

    def wait_for_model(self, timeout_s: float = 60.0) -> bool:
        """Block until the first published snapshot was pulled (True), or
        the timeout passes (False) — the warm-up gate hosting code may use
        before advertising the replica."""
        deadline = time.monotonic() + timeout_s
        while self._model is None:
            if time.monotonic() >= deadline:
                return False
            time.sleep(0.02)
        return True

    def stop(self) -> None:
        # Release the membership lease FIRST: discovery must drop this
        # replica from every pool rotation before the listener goes dark,
        # so a scale-down/stop never routes predicts at a dead port for
        # the thread-join window below (the zero-failed-requests drain
        # ordering autoscale.scale_down documents).
        if self._heartbeat is not None:
            self._heartbeat.close()
            self._heartbeat = None
        self._stop.set()
        # Held polls are in flight to the core: answer them with what their
        # streams hold now (no later poll is held, ``_stop`` is set), or the
        # drain below waits out their holds.
        with self._lock:
            tickets = [entry[0] for entry in self._sessions.values()]
        for t in tickets:
            t.release()
        # The core drains first (in-flight predicts resolve and their
        # buffered responses flush) and releases the port before
        # returning — the zero-dropped-requests half of a scale-down.
        self._core.stop()
        if self._notifier is not None:
            self._fired.put(None)
            self._notifier.join(timeout=5.0)
        self._refresher.join(timeout=5.0)
        self._batcher.stop()
        if self._engine is not None:
            self._engine.stop()
        # A stopped replica holds nothing on the device: its parameters and
        # its engine's cache go HERE, not with the garbage collector's next
        # pass - the replica, its core's handler and its engine refer to
        # each other, so dropping the last outside reference frees nothing
        # until the cycle is collected, and what runs next in the process
        # (a reference pass over the served tokens) finds the chip full of
        # a replica that is gone: 12.8 of 16 GB in half the runs of the
        # largest served model (my chip runs, PR 31).
        self._model = None
        if self._pinned:
            # Release the registry pin LAST: GC must not reclaim the
            # served version while in-flight work could still touch it.
            try:
                self._registry.unpin(
                    self.model_name, self.model_version, self.role,
                    tenant=self.tenant,
                )
            except Exception:  # noqa: BLE001 — unpin is best-effort cleanup
                log.warning("registry unpin failed", exc_info=True)
        if self._writer is not None:
            self._writer.close()
        if self._group is not None:
            self._group.close()

    # -- the param refresher (hot-tracking thread) ---------------------------

    def _swap_epoch(self, rec: dict) -> None:
        """Rebuild the PS-side onto a committed reshard record (refresher
        thread only — the predict path reads ``self._model``, an immutable
        tuple this swap never touches).  A failed rebuild keeps the
        current epoch and retries on the next poll."""
        old_version = self._layout.version
        if rec["num_elems"] != self._layout.num_elems:
            log.error(
                "serve %s: reshard v%d names %d elems, this replica "
                "serves %d — ignoring the record", self.role,
                rec["version"], rec["num_elems"], self._layout.num_elems,
            )
            return
        group = None
        try:
            group = ps_shard.ShardedPSClients.for_record(
                rec, role=self.role, op_timeout_s=self._op_timeout_s,
                reconnect_deadline_s=self._reconnect_deadline_s,
                tenant=self.tenant,
            )
            layout = group.layout_for(self._layout.num_elems)
            pstore = ps_shard.ShardedParamStore(group, "params", layout)
        except Exception as e:  # noqa: BLE001 — keep old epoch, retry
            if group is not None:
                group.close()
            self._follower.version = old_version
            faults.log_event(
                "serve_epoch_swap_failed", role=self.role,
                version=rec["version"], error=type(e).__name__,
            )
            return
        old_group = self._group
        self._group, self._layout, self._pstore = group, layout, pstore
        self._follower.rebind(group.coordinator, rec["version"])
        self._reshards += 1
        if self._heartbeat is not None:
            self._heartbeat.retarget(group.coordinator_replica_addrs)
        old_group.close()
        faults.log_event(
            "serve_epoch_swapped", role=self.role, version=rec["version"],
            shards=layout.num_shards,
        )

    def _sweep_stuck_tickets(self) -> None:
        """Resolve predict tickets past their deadline with TimeoutError
        (idempotent — a genuine resolve racing in later is a no-op): a
        wedged batch thread must not pin connections in_flight forever,
        which would leak them AND make every drain()/stop() burn its
        full timeout."""
        now = time.monotonic()
        with self._lock:
            stuck = [t for t, dl in self._pending_tickets.items() if now > dl]
        for t in stuck:
            t._resolve(error=TimeoutError(
                "batched apply did not complete in "
                f"{self._ticket_deadline_s:.0f}s (batch thread wedged?)"
            ))

    def _sweep_idle_sessions(self) -> None:
        """Cancel decode sessions nobody polled for ``session_idle_s`` —
        an abandoned client (crash, lost interest) must not hold a slot
        or its emission buffer forever.  DECODE_CLOSE is the polite path;
        this is the backstop."""
        if self._engine is None:
            return
        now = time.monotonic()
        with self._lock:
            stale = [
                sid for sid, (_t, last) in self._sessions.items()
                if now - last > self._session_idle_s
            ]
            tickets = [self._sessions.pop(sid)[0] for sid in stale]
        for t in tickets:
            t.cancel()

    def _refresh_loop(self) -> None:
        from ..parallel import ps_service

        while not self._stop.is_set():
            self._sweep_stuck_tickets()
            self._sweep_idle_sessions()
            if self._pinned:
                # Pin mode: no PS polling — the refresher's job is the
                # lease-style pin renewal (plus the sweeps above), so
                # registry GC can never reclaim a version this live
                # replica serves.
                now = time.monotonic()
                if now >= self._next_pin_renew:
                    self._next_pin_renew = now + self._pin_ttl_s / 3
                    try:
                        self._registry.pin(
                            self.model_name, self.model_version, self.role,
                            ttl_s=self._pin_ttl_s, tenant=self.tenant,
                        )
                    except Exception:  # noqa: BLE001 — retried next renew
                        self._refresh_errors += 1
                        faults.log_event(
                            "serve_pin_renew_failed", role=self.role,
                            version=self.model_version,
                        )
                self._stop.wait(max(self._refresh_s, 0.25))
                continue
            if self._follower is not None:
                rec = self._follower.poll()
                if rec is not None:
                    self._swap_epoch(rec)
            try:
                step, flat = self._pstore.get()
            except (ps_service.PSError, OSError) as e:
                # A PS outage past the client's own reconnect budget: keep
                # serving the LAST pulled model (stale-but-available beats
                # down) and keep polling.
                self._refresh_errors += 1
                faults.log_event(
                    "serve_refresh_error", role=self.role,
                    error=type(e).__name__,
                )
                self._stop.wait(min(1.0, self._refresh_s * 4))
                continue
            cur = self._model
            if step >= 0 and (cur is None or int(step) != cur[0]):
                # A CHANGED pull landed in a fresh buffer (the store never
                # hands back the previously returned one), so the views the
                # unflatten takes can outlive any number of later swaps.
                # device_put HERE, once per publish: the same snapshot is
                # reused across every apply until the next change, so the
                # batches must not each re-pay the host->device transfer.
                import jax

                self._model = (
                    int(step), jax.device_put(self._unflatten(flat))
                )
                self._refreshes += 1
            self._stop.wait(self._refresh_s)

    # -- the batched apply ---------------------------------------------------

    def _run_batch(self, items: list[dict]):
        """One padded jitted apply for a coalesced request list; returns
        ``(step, outputs_slice)`` per request.  Runs on the batch thread."""
        model = self._model
        if model is None:
            raise _NoModel()
        step, params = model
        proto = items[0]
        rows = [len(next(iter(it.values()))) for it in items]
        total = sum(rows)
        # Pad to the fixed max_batch shape so the jit cache holds ONE entry
        # per field signature; a lone oversized request runs at its own
        # (padded-to-itself) shape.
        padded = self.max_batch if total <= self.max_batch else total
        batch = {
            k: np.zeros((padded,) + np.asarray(v).shape[1:], np.asarray(v).dtype)
            for k, v in proto.items()
        }
        off = 0
        for it, r in zip(items, rows):
            for k in batch:
                batch[k][off : off + r] = it[k]
            off += r
        out = self._predict(params, batch)
        if not isinstance(out, dict):
            out = {"output": out}
        out_np = {k: np.asarray(v) for k, v in out.items()}
        results = []
        off = 0
        for r in rows:
            results.append(
                (step, {k: v[off : off + r] for k, v in out_np.items()})
            )
            off += r
        with self._lock:
            self._predicts += total
        return results

    # -- stats ---------------------------------------------------------------

    def stats(self) -> dict:
        b = self._batcher.stats()
        core = self._core.core_stats()
        with self._lock:
            s = {
                "service": SERVICE,
                "role": self.role,
                "incarnation": self._incarnation,
                "model_step": self.model_step,
                # The served registry version (r19): 0 = hot-tracking the
                # live run; > 0 = pinned to an immutable registry snapshot
                # (same stamp the HELLO word and every predict response
                # carry — dtxtop's per-version rollup keys off this).
                "model_version": self.model_version,
                "model_name": self.model_name,
                "tenant": self.tenant,
                "pinned": self._pinned,
                # The uniform runtime-accounting shape (r17): requests /
                # live_conns come from the shared server core, same
                # meaning on every service's STATS answer; the r18 shed
                # counters surface top-level with the same keys the
                # native PS exports.
                "requests": core["requests"],
                "live_conns": core["live_conns"],
                "shed_total": core["shed_total"],
                "queue_deadline_drops": core["queue_deadline_drops"],
                "core": core,
                # Per-tenant admission/accounting rows (r20) surface
                # top-level like the other two services', so dtxtop's
                # tenants section reads one shape everywhere.
                "tenants": core["tenants"],
                "predict_rows": self._predicts,
                "overloads": self._overloads,
                "refreshes": self._refreshes,
                "refresh_errors": self._refresh_errors,
                "ps_shards": (
                    self._group.num_shards if self._group is not None else 0
                ),
                "layout_epoch": (
                    self._layout.version if self._layout is not None else 0
                ),
                "reshards_followed": self._reshards,
                "decode_sessions_open": len(self._sessions),
                "decode_opens": self._decode_opens,
                # Every DECODE_NEXT handled; those that found nothing and
                # were held for an emission; those of them answered empty
                # when the hold (DECODE_HOLD_S) ran out.
                "decode_polls": self._decode_polls,
                "decode_polls_held": self._decode_polls_held,
                "decode_polls_expired": self._decode_polls_expired,
                "leased": bool(
                    self._heartbeat is not None and self._heartbeat.enabled
                ),
            }
        s.update({f"batcher_{k}": v for k, v in b.items()})
        if self._engine is not None:
            s.update({f"decode_{k}": v for k, v in self._engine.stats().items()})
        s.update(self.latency.percentile_scalars("serve"))
        # The replica process's client-side instruments ride along (r13):
        # its PS legs' reconnect/failover counters are the externally
        # visible half of "this replica kept tracking through the fault".
        s["registry"] = telemetry.snapshot()
        s["flight_events"] = len(telemetry.RECORDER)
        return s

    # -- the core handler ----------------------------------------------------
    # One registered handler on the shared server core (r17): the core
    # owns accept/read/write/HELLO/counting.  PREDICT is ASYNC — the
    # handler submits to the batcher and returns immediately; the
    # ticket's resolve callback (batch thread) queues the reply on the
    # connection, so a slow peer buffers bytes instead of wedging a
    # worker, and the bounded pool never caps the coalesced batch size.

    def _handle(self, conn, op: int, name: str, a: int, b: int, payload):
        if op == SRV_PREDICT:
            t0 = time.perf_counter()
            try:
                inputs = wire.decode_batch_bytes(payload)
            except (ValueError, TypeError, KeyError):
                return ERR, None
            return self._handle_predict(conn, inputs, t0)
        if op == SRV_DECODE_OPEN:
            return self._handle_decode_open(a, payload)
        if op == SRV_DECODE_NEXT:
            return self._handle_decode_next(conn, a, b)
        if op == SRV_DECODE_CLOSE:
            return self._handle_decode_close(a)
        if op == SRV_STATS:
            return 0, [json.dumps(self.stats()).encode()]
        if op == SRV_SHUTDOWN:
            self.shutdown_requested.set()
            return 0, None
        return ERR, None

    # -- decode sessions (r19) ----------------------------------------------

    def _stamp(self, out: dict) -> dict:
        """Every predict/decode response batch carries the served registry
        version next to its model_step (the status) — the per-response
        half of version observability (wire.SRV_VERSION_FIELD; clients
        strip it before handing outputs to the caller)."""
        out = dict(out)
        out[wire.SRV_VERSION_FIELD] = np.int64(self.model_version)
        return out

    def _handle_decode_open(self, max_new_tokens: int, payload):
        if self._engine is None:
            return NO_DECODER, None
        if self._model is None:
            return NO_MODEL, None
        try:
            inputs = wire.decode_batch_bytes(payload)
            prompt = np.asarray(inputs["prompt"])
        except (ValueError, TypeError, KeyError):
            return ERR, None
        try:
            ticket = self._engine.open(prompt, max_new_tokens)
        except ValueError:
            return ERR, None
        except batcher_lib.Overloaded:
            with self._lock:
                self._overloads += 1
            return wire.retry_later_status(self._retry_after_ms), None
        with self._lock:
            sid = self._next_sid
            self._next_sid += 1
            self._sessions[sid] = [ticket, time.monotonic()]
            self._decode_opens += 1
        return sid, None

    def _decode_answer(self, sid: int, ticket, cursor: int, hold: bool = False):
        """A poll's answer from what the stream holds now, ``(status,
        bufs)`` - or, with ``hold``, None where it holds neither a token at
        ``cursor`` nor an end to tell: the poll that is held instead."""
        try:
            tokens, done = ticket.snapshot(cursor)
        except _NoModel:
            return NO_MODEL, None
        except Exception:  # noqa: BLE001 — a failed step answers loudly
            log.error("decode session %d failed server-side", sid,
                      exc_info=True)
            with self._lock:
                self._sessions.pop(sid, None)
            return ERR, None
        if hold and not (tokens or done):
            return None
        out = self._stamp({
            "tokens": np.asarray(tokens, np.int32),
            "done": np.asarray([1 if done else 0], np.uint8),
        })
        return self.model_step, wire.encode_batch(out)

    def _handle_decode_next(self, conn, sid: int, cursor: int):
        """Tokens, an end or a failure are answered at once.  A poll that
        finds none of them is HELD: it waits with the session's ticket and
        is answered, by the notifier thread and with the frame built here,
        when the session emits or ends, when another poll for the session
        arrives, when the replica stops - or empty and not done, as before,
        once ``DECODE_HOLD_S`` has passed.  It returns ``ASYNC`` meanwhile,
        so it holds a reply slot of its connection and no pool worker."""
        with self._lock:
            self._decode_polls += 1
            entry = self._sessions.get(sid)
            if entry is not None:
                entry[1] = time.monotonic()
        if entry is None:
            return BAD_SESSION, None
        ticket = entry[0]
        answer = self._decode_answer(
            sid, ticket, cursor, hold=not self._stop.is_set())
        if answer is not None:
            return answer
        held = _HeldPoll(self._fired.put, conn, sid, ticket, cursor)
        with self._lock:
            self._decode_polls_held += 1
        self._held.append(held)
        ticket.when_ready(cursor, held)
        if self._stop.is_set():
            ticket.release()  # ``stop`` went through the sessions before it
        return server_core.ASYNC

    def _answer_held(self, held: _HeldPoll, expired: bool = False) -> None:
        """Notifier thread: the held poll's reply, and the session's stamp
        of activity (an answered poll is one, like one that arrives)."""
        ticket, held.ticket = held.ticket, None
        try:
            status, bufs = self._decode_answer(held.sid, ticket, held.cursor)
            with self._lock:
                self._decode_polls_expired += expired
                entry = self._sessions.get(held.sid)
                if entry is not None:
                    entry[1] = time.monotonic()
            held.conn.reply(status, bufs)
        except Exception:  # noqa: BLE001 — the notifier outlives any one poll
            log.error("held decode poll of session %d failed", held.sid,
                      exc_info=True)
            held.conn.reply(ERR, None)

    def _notify_loop(self) -> None:
        """Answer held polls off the step thread: those their tickets hand
        over (``_fired``) as they come, and those nothing came for when
        their hold runs out.  Holds run out in the order they began, so the
        oldest unanswered one says how long to wait for the queue."""
        held_polls = self._held
        while True:
            now = time.monotonic()
            while held_polls and (
                held_polls[0].ticket is None or held_polls[0].due <= now
            ):
                held = held_polls.popleft()
                if held.ticket is not None and held.ticket.forget(held):
                    self._answer_held(held, expired=True)
            wait = held_polls[0].due - now if held_polls else DECODE_HOLD_S
            try:
                held = self._fired.get(timeout=wait)
            except queue.Empty:
                continue
            if held is None:
                return
            self._answer_held(held)

    def _handle_decode_close(self, sid: int):
        with self._lock:
            entry = self._sessions.pop(sid, None)
        if entry is not None:
            entry[0].cancel()
        return 0, None  # idempotent: closing an unknown session is a no-op

    def _handle_predict(self, conn, inputs: dict, t0: float):
        if not inputs:
            return ERR, None
        lens = {len(np.asarray(v)) if np.asarray(v).ndim else -1
                for v in inputs.values()}
        if len(lens) != 1 or -1 in lens:
            # Every field must share one leading dim — the row unit the
            # batcher budgets and the scatter slices by.
            return ERR, None
        if self._model is None:
            return NO_MODEL, None
        # Requests coalesce only with SCHEMA-IDENTICAL neighbours (same
        # field names, trailing shapes and dtypes): one client sending a
        # mismatched request must never poison a well-formed concurrent
        # request's batch (it fails alone, in its own apply).
        schema = tuple(sorted(
            (k, np.asarray(v).shape[1:], str(np.asarray(v).dtype))
            for k, v in inputs.items()
        ))
        try:
            ticket = self._batcher.submit(inputs, rows=lens.pop(), key=schema)
        except batcher_lib.Overloaded:
            # r18: the batcher's admission refusal answers the typed
            # RETRY_LATER band — the shed carries its backoff hint in the
            # status, so resilient clients back off for a meaningful beat
            # instead of re-hammering the rotation (the legacy OVERLOAD
            # code point stays recognized client-side).
            with self._lock:
                self._overloads += 1
            return wire.retry_later_status(self._retry_after_ms), None

        def _resolved(value, error) -> None:
            with self._lock:
                self._pending_tickets.pop(ticket, None)
            if error is not None:
                if isinstance(error, _NoModel):
                    conn.reply(NO_MODEL, None)
                    return
                # An apply bug (or the batcher's stop-drain error, or
                # the wedged-apply timeout sweep) must surface as a LOUD
                # per-op error on the client, not a silent connection
                # close — WITH the traceback, since the client's typed
                # error message points operators at this log.
                log.error(
                    "batched predict failed server-side", exc_info=error
                )
                conn.reply(ERR, None)
                return
            step, out = value
            try:
                # Same invariant the core's worker guards on the sync
                # path: an output the wire cannot encode must answer a
                # loud ERR — an escape here would be swallowed by the
                # ticket's callback container with NO reply sent,
                # wedging the connection in_flight forever.  reply()
                # normalizes its buffers before queuing anything, so
                # the ERR after a failed attempt is the first frame.
                conn.reply(step, wire.encode_batch(self._stamp(out)))
            except Exception:
                log.error(
                    "predict reply failed (unserializable output?)",
                    exc_info=True,
                )
                conn.reply(ERR, None)
                return
            self.latency.record(time.perf_counter() - t0)
            if (
                self._writer is not None
                and self.latency.total % self._metrics_every == 0
            ):
                self._writer.scalars(
                    self.model_step, self.latency.percentile_scalars("serve")
                )

        with self._lock:
            self._pending_tickets[ticket] = (
                time.monotonic() + self._ticket_deadline_s
            )
        ticket.on_resolve(_resolved)
        return server_core.ASYNC


class _NoModel(RuntimeError):
    """Raised inside a batch whose replica has no pulled snapshot yet —
    mapped to the NO_MODEL status per request (warming replicas shed load
    explicitly, like overload)."""


# ----------------------------------------------------------------------------
# Task-role hosting (the runner's `serve` job)
# ----------------------------------------------------------------------------


def host_serve_task(
    *, init_fn, predict_fn, ps_addrs, port: int, loopback_only: bool = True,
    max_batch: int = 32, max_wait_ms: float = 5.0, queue_depth: int = 128,
    refresh_ms: float = 50.0, op_timeout_s: float | None = 10.0,
    reconnect_deadline_s: float = 60.0, metrics_dir: str | None = None,
    membership: bool = True, lease_ttl_s: float = 10.0,
    advertise_addr: str | None = None, ps_replicas: int = 1,
    layout_version: int = 0, queue_deadline_ms: float = 0.0,
    registry_dir: str | None = None, model_name: str = "default",
    model_version: int | None = None, decode_fns: tuple | None = None,
    decode_slots: int = 4, decode_max_len: int = 512,
    tenant: str = tenancy.DEFAULT_TENANT, tenant_quotas: dict | None = None,
) -> int:
    """Dedicated serve-task body (``--job_name=serve``): host one replica
    until a client signals SRV_SHUTDOWN (or the supervisor dies).  Arms
    ``die`` fault specs off the replica's request counter — the
    deterministic "kill replica i at request N" fault the serving recovery
    tests inject; a supervisor restart re-pulls the current params from the
    PS and rejoins the rotation with zero coordination.  With
    ``registry_dir`` + ``model_version`` (``--registry_dir`` /
    ``--serve_model_version``) the replica PINS that registry version
    instead of hot-tracking — a supervised restart re-loads the SAME
    version, so a rolling deploy's replica set keeps its meaning through
    kills."""
    server = ModelReplicaServer(
        init_fn, predict_fn, ps_addrs, port=port,
        loopback_only=loopback_only, max_batch=max_batch,
        max_wait_ms=max_wait_ms, queue_depth=queue_depth,
        refresh_ms=refresh_ms, op_timeout_s=op_timeout_s,
        reconnect_deadline_s=reconnect_deadline_s, metrics_dir=metrics_dir,
        membership=membership, lease_ttl_s=lease_ttl_s,
        advertise_addr=advertise_addr, ps_replicas=ps_replicas,
        layout_version=layout_version, queue_deadline_ms=queue_deadline_ms,
        registry_dir=registry_dir, model_name=model_name,
        model_version=model_version, decode_fns=decode_fns,
        decode_slots=decode_slots, decode_max_len=decode_max_len,
        tenant=tenant, tenant_quotas=tenant_quotas,
    )
    faults.arm_process_faults(
        request_count_fn=server.request_count,
        leave_fn=lambda: server.stop(),
    )
    if not server.wait_for_model(timeout_s=120.0):
        log.warning(
            "serve task: no published params after 120 s — serving NO_MODEL "
            "until the chief publishes"
        )
    log.info(
        "serve task on port %d (model_step=%d; blocking until shutdown)",
        server.port, server.model_step,
    )
    supervised = os.environ.get("DTX_SERVE_SUPERVISED") == "1"
    ppid0 = os.getppid()
    while not server.shutdown_requested.wait(timeout=2.0):
        if supervised and os.getppid() != ppid0:
            log.warning("serve task: supervisor died; exiting")
            break
    bound = server.port
    server.stop()
    return bound
