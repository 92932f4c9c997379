"""dtxobs core (r13): process-wide metrics registry + event flight recorder
+ (PR 24) the one span primitive.

Every role in the cluster (PS task, data server, serve replica, chief,
worker) accumulates its health into two process-wide singletons:

- :data:`REGISTRY` — a thread-safe metrics registry of named counters,
  gauges and BOUNDED histograms (ring of recent observations reduced to
  p50/p90/p99 at snapshot time, beside counts over fixed edges that never
  forget, so two snapshots give the distribution of what lay between
  them).  Instruments are cheap enough for the
  wire hot path (one small lock + an int add per event; percentile math
  is paid only by the scraper), and `snapshot()` flattens everything into
  one JSON-ready ``{name: number}`` table — the payload each service's
  ``STATS`` wire op answers, so one scraper (``tools/dtxtop.py``) can poll
  a live cluster with zero side channels.
- :data:`RECORDER` — a structured-event flight recorder: a bounded ring
  of typed events (connects, reconnects, failovers, reseeds, injected
  faults, divergence latches...).  ``utils/faults.log_event`` feeds every
  structured ``dtx.faults`` line into it, so the ring IS the recent fault/
  recovery history of the process; it is dumped to JSONL on demand and on
  fatal conditions (``REPL_DIVERGED`` latches, reconnect-budget
  exhaustion, injected deaths) so a post-mortem can attribute the failure
  to its cause without having had logging configured in advance.

:func:`span` puts one interval of host work on both records at once: an
event in the profiler's trace while a session runs (on the clock of the
device planes) and two registry counters, ``<name>/ns`` and ``<name>/n``,
always.  :func:`count_compiles` counts the process's XLA compilations the
same way (``jax/compiles``, ``jax/compile_ns``, a ``compile`` event).  The
module itself imports without JAX.

Naming convention: ``<family>/<metric>`` (``ps_client/reconnects``,
``ps_shard/pull_cache_hits``) — same family idea as
``utils.metrics.shard_scalars``, so dashboards glob one prefix per
subsystem.

The dump directory resolves from the ``DTX_OBS_EVENTS_DIR`` env var
(launchers export it from ``--obs_events_dir``); unset means on-fatal
dumps are skipped (explicit ``dump(path=...)`` always writes).
"""

from __future__ import annotations

import bisect
import collections
import functools
import json
import os
import threading
import time

#: Env var naming the flight-recorder dump directory (exported to every
#: cluster child by the launchers from ``--obs_events_dir``).
EVENTS_DIR_ENV = "DTX_OBS_EVENTS_DIR"


class Counter:
    """Monotone counter.  ``inc`` is thread-safe (Python int ``+=`` spans
    several bytecodes, so the GIL alone does not make it atomic)."""

    __slots__ = ("name", "_v", "_lock")

    def __init__(self, name: str):
        self.name = name
        self._v = 0
        self._lock = threading.Lock()

    def inc(self, n: int = 1) -> None:
        with self._lock:
            self._v += n

    @property
    def value(self) -> int:
        return self._v

    def _reset(self) -> None:
        with self._lock:
            self._v = 0


class Gauge:
    """Last-written value (queue depths, model steps, flags-as-metrics)."""

    __slots__ = ("name", "_v", "_lock")

    def __init__(self, name: str):
        self.name = name
        self._v = 0.0
        self._lock = threading.Lock()

    def set(self, v: float) -> None:
        with self._lock:
            self._v = float(v)

    @property
    def value(self) -> float:
        return self._v

    def _reset(self) -> None:
        with self._lock:
            self._v = 0.0


#: Upper edges of the buckets every histogram counts into: sixteen to an
#: octave, ``2**-10 .. 2**20`` (a microsecond to a quarter of an hour where
#: the unit is the millisecond), a last bucket without an upper edge behind
#: them.  Two values of one bucket lie within 4.5 % of each other, so a
#: percentile read back from the counts lies within 5 % of the exact one
#: wherever in its bucket it is put.  Fixed, and the same for every
#: histogram: the difference of two snapshots needs no edge negotiated.
_EDGES = tuple(2.0 ** (k / 16) for k in range(-160, 321))
#: How a snapshot spells each edge (``<name>/le/<edge>``); ``float()`` reads
#: it back to six digits.
_EDGE_KEYS = tuple(f"{e:.6g}" for e in _EDGES) + ("inf",)
#: The bucket of a value: the first edge at or above it.
_bucket_of = functools.partial(bisect.bisect_left, _EDGES)


class Histogram:
    """Two records of one stream of observations.

    A bounded ring of the most recent ``capacity`` -> count/p50/p90/p99/max:
    what a person reads off one scrape.  And a count per bucket of
    :data:`_EDGES` that is never reset by time: :meth:`cumulative` gives, at
    every edge whose bucket holds something, how many observations EVER made
    were at or under it, so the difference of two scrapes is the
    distribution of exactly the observations between them - what a scraper
    that wants a window's percentile takes.

    ``observe`` is O(1) under a lock (a bisection over the edges outside
    it); the percentile reduction (a sort of at most ``capacity`` floats)
    runs only in :meth:`snapshot` — scrape cost lives with the scraper, not
    the hot path."""

    __slots__ = ("name", "_cap", "_buf", "_n", "_buckets", "_lock")

    def __init__(self, name: str, capacity: int = 512):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.name = name
        self._cap = int(capacity)
        self._buf: list[float] = [0.0] * self._cap
        self._n = 0  # total ever observed; ring index is _n % _cap
        self._buckets = [0] * len(_EDGE_KEYS)
        self._lock = threading.Lock()

    def observe(self, v: float) -> None:
        # Spelled out, not ``observe_many((v,))``: the wire's hot path
        # observes one value a call.
        v = float(v)
        i = _bucket_of(v)
        with self._lock:
            self._buf[self._n % self._cap] = v
            self._n += 1
            self._buckets[i] += 1

    def observe_many(self, values) -> None:
        """Every value of ``values``, under the lock taken once."""
        values = list(map(float, values))
        placed = list(map(_bucket_of, values))
        buf, cap, buckets = self._buf, self._cap, self._buckets
        with self._lock:
            n = self._n
            for v, i in zip(values, placed):
                buf[n % cap] = v
                n += 1
                buckets[i] += 1
            self._n = n

    @property
    def count(self) -> int:
        return self._n

    def snapshot(self) -> dict[str, float]:
        """``{count, p50, p90, p99, max}`` over the retained window (zeros
        when nothing has been observed — scrapers still see the keys)."""
        with self._lock:
            m = min(self._n, self._cap)
            window = sorted(self._buf[:m])
            n = self._n
        if not window:
            return {"count": 0, "p50": 0.0, "p90": 0.0, "p99": 0.0, "max": 0.0}

        def pct(p: float) -> float:
            # Nearest-rank on the sorted window: cheap, monotone, and
            # exact at the edges (p99 of a small window is its max).
            i = min(len(window) - 1, max(0, round(p / 100 * (len(window) - 1))))
            return window[i]

        return {
            "count": n,
            "p50": pct(50),
            "p90": pct(90),
            "p99": pct(99),
            "max": window[-1],
        }

    def cumulative(self) -> dict[str, int]:
        """``{edge: observations ever made that were <= edge}`` at each edge
        whose own bucket is not empty (an edge left out reads what the edge
        before it reads; the last one present reads ``count``)."""
        with self._lock:
            buckets = list(self._buckets)
        out, total = {}, 0
        for key, b in zip(_EDGE_KEYS, buckets):
            if b:
                total += b
                out[key] = total
        return out

    def _reset(self) -> None:
        with self._lock:
            self._n = 0
            self._buckets[:] = [0] * len(_EDGE_KEYS)  # in place: see observe_many


class MetricsRegistry:
    """Get-or-create instrument table.  Instrument handles are stable for
    the process lifetime (hot paths cache them at module scope), so
    :meth:`reset` ZEROES values instead of dropping instruments — a cached
    handle keeps counting into the table the next snapshot reads."""

    def __init__(self):
        self._lock = threading.Lock()
        self._counters: dict[str, Counter] = {}
        self._gauges: dict[str, Gauge] = {}
        self._hists: dict[str, Histogram] = {}

    def counter(self, name: str) -> Counter:
        with self._lock:
            c = self._counters.get(name)
            if c is None:
                c = self._counters[name] = Counter(name)
            return c

    def gauge(self, name: str) -> Gauge:
        with self._lock:
            g = self._gauges.get(name)
            if g is None:
                g = self._gauges[name] = Gauge(name)
            return g

    def histogram(self, name: str, capacity: int = 512) -> Histogram:
        with self._lock:
            h = self._hists.get(name)
            if h is None:
                h = self._hists[name] = Histogram(name, capacity)
            return h

    # Convenience one-shot spellings (cold paths that don't cache handles).
    def inc(self, name: str, n: int = 1) -> None:
        self.counter(name).inc(n)

    def set_gauge(self, name: str, v: float) -> None:
        self.gauge(name).set(v)

    def observe(self, name: str, v: float) -> None:
        self.histogram(name).observe(v)

    def snapshot(self) -> dict[str, float]:
        """One flat JSON-ready table: counters and gauges verbatim,
        histograms flattened as ``<name>_count/_p50/_p90/_p99/_max`` over
        their rings and ``<name>/le/<edge>`` over their buckets
        (:meth:`Histogram.cumulative`)."""
        with self._lock:
            counters = list(self._counters.values())
            gauges = list(self._gauges.values())
            hists = list(self._hists.values())
        out: dict[str, float] = {}
        for c in counters:
            out[c.name] = c.value
        for g in gauges:
            out[g.name] = g.value
        for h in hists:
            for k, v in h.snapshot().items():
                out[f"{h.name}_{k}"] = v
            for edge, v in h.cumulative().items():
                out[f"{h.name}/le/{edge}"] = v
        return out

    def reset(self) -> None:
        """Zero every instrument (test isolation; handles stay valid)."""
        with self._lock:
            instruments = (
                list(self._counters.values())
                + list(self._gauges.values())
                + list(self._hists.values())
            )
        for i in instruments:
            i._reset()


#: The process-wide registry every role instruments onto.
REGISTRY = MetricsRegistry()


class FlightRecorder:
    """Bounded ring of structured events, dumped to JSONL on demand.

    ``record`` is the single write path (``faults.log_event`` calls it for
    every ``dtx.faults`` line, so injected faults and recovery actions are
    captured even when nothing is watching).  ``dump`` writes one JSONL
    file — a ``dump`` header line carrying the reason, then every retained
    event oldest-first — to an explicit path or into the
    ``DTX_OBS_EVENTS_DIR`` directory; with neither configured it is a
    no-op returning None, so fatal-path hooks are always safe to call."""

    def __init__(self, capacity: int = 4096):
        self._events: collections.deque = collections.deque(maxlen=capacity)
        self._lock = threading.Lock()
        self._dumps = 0

    def record(self, event: str, **fields) -> None:
        entry = {"ts": time.time(), "event": str(event), **fields}
        with self._lock:
            self._events.append(entry)

    def __len__(self) -> int:
        with self._lock:
            return len(self._events)

    def events(self) -> list[dict]:
        with self._lock:
            return list(self._events)

    @property
    def dumps(self) -> int:
        return self._dumps

    def clear(self) -> None:
        with self._lock:
            self._events.clear()

    def dump(self, path: str | None = None, *, reason: str = "") -> str | None:
        if path is None:
            d = os.environ.get(EVENTS_DIR_ENV, "")
            if not d:
                return None
            role = os.environ.get("DTX_FAULT_ROLE", "") or "proc"
            path = os.path.join(d, f"flight-{role}-{os.getpid()}.jsonl")
        events = self.events()
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        with open(path, "w") as f:
            f.write(json.dumps(
                {
                    "ts": time.time(), "event": "dump", "reason": reason,
                    "pid": os.getpid(), "retained": len(events),
                },
                default=str,
            ) + "\n")
            for e in events:
                f.write(json.dumps(e, default=str) + "\n")
        with self._lock:
            self._dumps += 1
        return path


#: The process-wide flight recorder.
RECORDER = FlightRecorder()


# ---------------------------------------------------------------------------
# Spans: one interval on two records — the profiler's trace (when a session
# is running) and the registry's running sums (always)
# ---------------------------------------------------------------------------

#: ``jax.profiler.TraceAnnotation``, resolved at the first span entered:
#: this module must import without JAX (``tools/tsan_driver.py``).
_annotation = None


def _resolve_annotation():
    global _annotation
    from jax.profiler import TraceAnnotation

    _annotation = TraceAnnotation
    return TraceAnnotation


class Span:
    """A named interval of host work.  Entering it opens a
    ``jax.profiler.TraceAnnotation(name)`` — nothing without a profiler
    session, and with one an event on the host thread's line, on the
    clock the device planes share — and leaving it adds the elapsed
    ``time.perf_counter_ns()`` to counter ``<name>/ns`` and 1 to
    ``<name>/n``.  The trace is the list of events, the counters are their
    sums; nothing else is kept but :attr:`last_ns`, and nothing turns a
    span off.

    One handle serves any number of threads (the open interval is
    per-thread), but not two nested entries on one thread: spans are
    leaves — they follow each other and never wrap another, because the
    trace's gap labeller gives a gap to the event that covers most of it
    and an enclosing span would take every label."""

    __slots__ = ("name", "_ns", "_n", "_open")

    def __init__(self, name: str):
        self.name = name
        self._ns = REGISTRY.counter(f"{name}/ns")
        self._n = REGISTRY.counter(f"{name}/n")
        self._open = threading.local()

    def __enter__(self) -> "Span":
        a = (_annotation or _resolve_annotation())(self.name)
        a.__enter__()
        self._open.annotation = a
        self._open.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        self._open.dt = dt = time.perf_counter_ns() - self._open.t0
        self._open.annotation.__exit__(*exc)
        self._ns.inc(dt)
        self._n.inc()

    @property
    def last_ns(self) -> int:
        """What the interval this thread left last added to ``<name>/ns``:
        for a caller that books the same nanoseconds somewhere else too."""
        return self._open.dt


#: ``telemetry.span(name)`` is how call sites spell it: resolve the handle
#: once per site (a module constant, an attribute set in ``__init__``) and
#: enter it with ``with`` each time round.
span = Span


#: The runtime's event for one program built (a persistent-cache load
#: included: either way a new shape reached the compiler's front door).
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compile_listener_lock = threading.Lock()
_compile_listener_on = False


def count_compiles() -> None:
    """Count every XLA compilation of this process from here on: counters
    ``jax/compiles`` and ``jax/compile_ns``, and a ``compile`` event in
    the flight recorder saying when.  Idempotent; called where a replica
    or a training run starts, so "did anything compile in the steady
    state" is a count read from ``STATS``, not an inference."""
    global _compile_listener_on
    with _compile_listener_lock:
        if _compile_listener_on:
            return
        import jax.monitoring

        compiles = REGISTRY.counter("jax/compiles")
        compile_ns = REGISTRY.counter("jax/compile_ns")

        def _on_duration(event: str, seconds: float, **_kw) -> None:
            if event == _COMPILE_EVENT:
                compiles.inc()
                compile_ns.inc(int(seconds * 1e9))
                record_event("compile", seconds=seconds)

        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _compile_listener_on = True


def record_event(event: str, **fields) -> None:
    """Module-level spelling of ``RECORDER.record`` (instrumentation
    sites read better without the singleton plumbing)."""
    RECORDER.record(event, **fields)


def dump_flight_recorder(reason: str, path: str | None = None) -> str | None:
    """Best-effort fatal-path dump: record the reason as its own event,
    then dump the ring.  Never raises — the caller is already on an error
    path and must not trade its diagnostic for an IO failure."""
    try:
        RECORDER.record("fatal", reason=reason)
        return RECORDER.dump(path, reason=reason)
    except Exception:
        return None


def snapshot() -> dict[str, float]:
    """The process registry's flat table (module-level convenience for the
    services' STATS handlers)."""
    return REGISTRY.snapshot()
