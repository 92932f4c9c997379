"""Multi-host bootstrap: the TPU-native distributed runtime layer.

Replaces the reference's control plane (SURVEY.md section 2b D2/D9/D10):
``tf.train.Server`` starting gRPC master/worker services per process, and
``TFConfigClusterResolver`` reading the ``TF_CONFIG`` env JSON.  On TPU the
control plane is JAX's coordination service (``jax.distributed.initialize``
over DCN); the data plane is XLA collectives over ICI and never touches this
module.  What remains host-side:

- cluster resolution: explicit args > ``TF_CONFIG`` (accepted for CLI/env
  compatibility with reference launchers) > TPU-pod auto-detection (on Cloud
  TPU ``jax.distributed.initialize()`` discovers everything itself),
- process identity helpers (``is_chief`` = process 0, the analog of
  ``task_index == 0`` chief election),
- a cross-host barrier (``sync_global_devices``), the ``wait_for_session``
  analog used around checkpoint save/restore fences.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os

import jax

log = logging.getLogger("dtx.dist")


@dataclasses.dataclass(frozen=True)
class ClusterConfig:
    """Resolved multi-host identity (the ClusterSpec + task tuple analog)."""

    coordinator_address: str | None  # host:port of process 0
    num_processes: int | None
    process_id: int | None
    source: str  # "args" | "tf_config" | "auto"
    task_type: str | None = None  # TF_CONFIG task type ("worker", "ps", ...)

    @property
    def is_ps_task(self) -> bool:
        """True for TF_CONFIG roles with no seat in the SPMD world (ps,
        evaluator): the process should exit cleanly, like the legacy
        ``--job_name=ps`` path (SURVEY.md section 5.6)."""
        return self.task_type in ("ps", "evaluator")


def resolve_cluster(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> ClusterConfig:
    """Explicit args win; else ``TF_CONFIG`` (TFConfigClusterResolver analog,
    SURVEY.md D9); else leave everything None for TPU-pod auto-detection."""
    if coordinator_address or num_processes is not None or process_id is not None:
        return ClusterConfig(coordinator_address, num_processes, process_id, "args")

    tf_config = os.environ.get("TF_CONFIG")
    if tf_config:
        try:
            cfg = json.loads(tf_config)
            cluster = cfg.get("cluster", {})
            task = cfg.get("task", {})
            workers = list(cluster.get("chief", [])) + list(cluster.get("worker", []))
            if cluster.get("ps"):
                log.warning(
                    "TF_CONFIG lists %d ps tasks: parameter servers are "
                    "obsolete on TPU (variables are mesh-sharded); counting "
                    "only chief/worker tasks as processes.",
                    len(cluster["ps"]),
                )
            task_type = task.get("type")
            index = int(task.get("index", 0))
            if task_type == "worker" and "chief" in cluster:
                index += len(cluster["chief"])
            if workers:
                if task_type not in (None, "chief", "worker"):
                    # ps/evaluator tasks hold no SPMD process id — giving them
                    # one would collide with a real worker's seat.
                    return ClusterConfig(
                        workers[0], len(workers), None, "tf_config", task_type
                    )
                # Coordinator port: reuse the first task's port on its host.
                return ClusterConfig(
                    workers[0], len(workers), index, "tf_config", task_type
                )
        except (ValueError, KeyError) as e:
            log.warning("ignoring malformed TF_CONFIG: %s", e)
    return ClusterConfig(None, None, None, "auto")


_initialized = False


def initialize(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> ClusterConfig:
    """Start (or join) the coordination service.  Idempotent; single-process
    runs (no cluster info anywhere, 1 host) skip initialization entirely so
    examples work unchanged on one chip."""
    global _initialized
    cfg = resolve_cluster(coordinator_address, num_processes, process_id)
    if _initialized:
        return cfg
    if cfg.is_ps_task:
        log.warning(
            "TF_CONFIG task type %r has no role under SPMD; not joining the "
            "coordination service (caller should exit 0).",
            cfg.task_type,
        )
        return cfg
    if cfg.source == "auto" and not _on_multihost_tpu():
        return cfg  # plain single-process run
    # NOTE: must run before any other JAX call — touching the backend first
    # (even jax.process_count()) would make initialize() raise.
    jax.distributed.initialize(
        coordinator_address=cfg.coordinator_address,
        num_processes=cfg.num_processes,
        process_id=cfg.process_id,
    )
    _initialized = True
    log.info(
        "distributed runtime up: process %d/%d (source=%s)",
        jax.process_index(),
        jax.process_count(),
        cfg.source,
    )
    return cfg


def _on_multihost_tpu() -> bool:
    """True when Cloud-TPU env vars indicate a MULTI-host pod slice whose
    topology ``jax.distributed.initialize()`` can self-discover.  A single
    hostname (e.g. ``TPU_WORKER_HOSTNAMES=localhost`` on one-host setups) is
    not a cluster."""
    if os.environ.get("MEGASCALE_COORDINATOR_ADDRESS"):
        return True
    hostnames = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    return len([h for h in hostnames.split(",") if h.strip()]) > 1


def process_index() -> int:
    return jax.process_index()


def process_count() -> int:
    return jax.process_count()


def is_chief() -> bool:
    """Process 0 — the reference's ``task_index == 0`` chief (SURVEY.md T1).
    Under SPMD the chief's only special duties are host-side: writing metrics
    and directing non-sharded checkpoint metadata."""
    return jax.process_index() == 0


def barrier(name: str = "barrier") -> None:
    """Cross-host sync point (the ``SessionManager.wait_for_session`` analog:
    everyone reaches ``name`` before anyone proceeds)."""
    from jax.experimental import multihost_utils

    multihost_utils.sync_global_devices(name)


# ----------------------------------------------------------------------------
# Failure detection: peer-heartbeat watchdog (SURVEY.md section 5.3)
# ----------------------------------------------------------------------------

#: Exit code a process uses when the watchdog declares a peer dead.  The
#: supervisor (utils.supervisor) treats any nonzero exit as "restart me";
#: a distinct code makes the cause greppable in task logs.
EXIT_PEER_LOST = 83

_watchdog_thread = None
_watchdog_stop = None


def start_watchdog(
    *,
    interval_s: float = 2.0,
    grace_s: float = 10.0,
    startup_grace_s: float = 120.0,
    on_failure=None,
    _client=None,
    _idx=None,
    _count=None,
):
    """Detect dead peers and fail FAST instead of hanging in a collective.

    The recovery model is the reference's (SURVEY.md section 5.3): crash-
    restart, not elastic.  A restarted worker cannot rejoin a live
    coordination service (the service and all XLA collectives are formed
    over a fixed process set), so the correct behavior when any peer dies
    is: every surviving process exits promptly (``EXIT_PEER_LOST``), the
    per-task supervisor (``utils.supervisor``) relaunches the whole job with
    the same TF_CONFIG, the coordination service re-forms, and training
    auto-resumes from the last checkpoint (TrainSession auto-restore).
    Without this, survivors block forever in the next all-reduce — the gloo/
    ICI collective has no peer-death signal of its own.

    Mechanism: every process overwrites ``dtx/hb/<idx>`` in the coordination
    service's KV store with a local sequence number every ``interval_s``; a
    monitor thread samples all peers every ``grace_s`` and declares any peer
    whose counter stopped advancing dead.  Threads are daemons: a clean exit
    0 needs no teardown.

    A peer whose heartbeat value is ``"done"`` departed CLEANLY (it called
    ``stop_watchdog()``, as ``Experiment.finish`` does) and is never
    declared dead — without this, end-of-job skew between workers larger
    than ``grace_s`` would kill survivors mid-final-checkpoint.  A peer that
    NEVER publishes a first beat within ``startup_grace_s`` (it died between
    joining the coordination service and its first beat, e.g. an init-time
    OOM) is declared dead too — first-beat silence must not be an unbounded
    blind spot.

    ``on_failure(dead: list[int])`` overrides the default ``os._exit``.
    Returns True if started (multi-process with a live client), else False.
    ``_client``/``_idx``/``_count`` are test seams (fake KV client).
    """
    global _watchdog_thread, _watchdog_stop
    import threading
    import time as _time

    if _watchdog_thread is not None:
        return True
    client = (
        _client
        if _client is not None
        else jax._src.distributed.global_state.client
    )
    if client is None:
        return False
    idx = jax.process_index() if _idx is None else _idx
    count = jax.process_count() if _count is None else _count
    if count < 2:
        return False
    if grace_s < 3 * interval_s:
        # A grace below ~3 beats would declare live peers dead whenever two
        # monitor samples land inside one beat interval.
        log.warning(
            "watchdog: grace_s=%.1f < 3x interval_s=%.1f; clamping to %.1f",
            grace_s, interval_s, 3 * interval_s,
        )
        grace_s = 3 * interval_s
    stop = threading.Event()

    def _beat():
        seq = 0
        misses = 0
        while not stop.is_set():
            seq += 1
            try:
                client.key_value_set(f"dtx/hb/{idx}", str(seq), allow_overwrite=True)
                misses = 0
            except Exception as e:
                # NEVER stop beating while the process lives: a silently
                # frozen heartbeat makes every peer declare us dead and
                # kills a healthy job.  A service outage longer than the
                # peers' grace does that anyway — but then the supervisor
                # restart is at least the designed response.  (At clean
                # shutdown the stop event ends this loop; at process exit
                # the daemon thread dies with it.)
                misses += 1
                if misses <= 3 or misses % 30 == 0:
                    log.warning(
                        "watchdog: heartbeat publish failed %dx (%s); retrying",
                        misses, e,
                    )
            stop.wait(interval_s)

    def _fail(dead: list[int]):
        log.critical(
            "watchdog: peer heartbeat lost for process(es) %s; exiting %d "
            "for supervisor restart (a dead peer cannot rejoin a live "
            "coordination service — the whole job restarts and auto-resumes "
            "from the last checkpoint).",
            dead,
            EXIT_PEER_LOST,
        )
        os._exit(EXIT_PEER_LOST)

    fail = on_failure or _fail

    def _monitor():
        last: dict[int, str] = {}
        t0 = _time.monotonic()
        misses = 0
        while not stop.is_set():
            stop.wait(grace_s)
            if stop.is_set():
                return
            try:
                pairs = dict(client.key_value_dir_get("dtx/hb/"))
                misses = 0
            except Exception as e:
                # Retry transient KV errors — exiting here would silently
                # disable failure detection for the rest of the run.  Three
                # consecutive failures = service gone (shutdown teardown).
                misses += 1
                if misses >= 3:
                    log.warning(
                        "watchdog: coordination service unreachable 3x (%s); "
                        "monitor disabled", e,
                    )
                    return
                continue
            now = {p: pairs.get(f"dtx/hb/{p}") for p in range(count) if p != idx}
            dead = [
                p
                for p, seq in now.items()
                if seq != "done"
                and (
                    (seq is not None and last.get(p) == seq)
                    or (seq is None and _time.monotonic() - t0 > startup_grace_s)
                )
            ]
            if dead:
                fail(dead)
                return
            last.update({p: s for p, s in now.items() if s is not None})

    _watchdog_stop = stop
    _watchdog_thread = threading.Thread(target=_monitor, daemon=True, name="dtx-watchdog")
    threading.Thread(target=_beat, daemon=True, name="dtx-heartbeat").start()
    _watchdog_thread.start()
    log.info(
        "watchdog up: %d peers, beat %.1fs, grace %.1fs", count - 1, interval_s, grace_s
    )
    return True


def stop_watchdog(*, _client=None, _idx=None) -> None:
    """Stop heartbeating and announce a CLEAN departure to the peers (they
    must not treat this process's silence as a crash).  ``_client``/``_idx``
    are the same test seams as start_watchdog's."""
    global _watchdog_thread, _watchdog_stop
    if _watchdog_stop is not None:
        _watchdog_stop.set()
        client = (
            _client
            if _client is not None
            else jax._src.distributed.global_state.client
        )
        if client is not None:
            try:
                idx = jax.process_index() if _idx is None else _idx
                client.key_value_set(f"dtx/hb/{idx}", "done", allow_overwrite=True)
            except Exception:
                pass  # service already torn down
    _watchdog_thread = None
    _watchdog_stop = None
