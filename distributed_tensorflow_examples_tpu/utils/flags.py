"""Flag/CLI layer: absl-flags based, preserving the reference CLIs.

Contract (SURVEY.md section 5.6, BASELINE.json:5): every example keeps its
existing CLI.  The reference scripts take TF-1 cluster flags
(``--ps_hosts/--worker_hosts/--job_name/--task_index``); on TPU the cluster is
a mesh, so those flags are *accepted and mapped*:

- ``--ps_hosts``/``--worker_hosts``/``--job_name``/``--task_index`` are
  parsed, logged, and translated: the worker count informs a requested data-
  parallel size when ``--mesh`` is unset; PS hosts map to nothing (the PS role
  is absorbed by mesh-sharded variables) and a notice explains that.
- New-style control: ``--mesh "data=8,model=2"``, ``--coordinator`` etc.
"""

from __future__ import annotations

import logging

from absl import flags

log = logging.getLogger("dtx.flags")

FLAGS = flags.FLAGS


def _define(kind, name, default, help_str):
    """Define unless an identical-named flag exists (absl.logging already owns
    --log_dir; the reference CLI reuses that name, so we adopt it)."""
    if name in flags.FLAGS:
        return
    getattr(flags, f"DEFINE_{kind}")(name, default, help_str)


def define_training_flags(default_batch_size: int = 128, default_steps: int = 1000):
    """The shared surface every example exposes (ref flag set, SURVEY.md L5).
    Idempotent (``_define``) so bench drivers/tests may import several example
    modules into one process."""
    _define("integer", "batch_size", default_batch_size, "GLOBAL batch size.")
    _define("integer", "train_steps", default_steps, "Stop after this many steps.")
    _define("string", "data_dir", None, "Dataset directory (synthetic if absent).")
    _define("string", "log_dir", None, "Checkpoints + metrics directory.")
    _define("float", "learning_rate", 0.01, "Base learning rate.")
    _define(
        "integer",
        "warmup_steps",
        0,
        "Linear learning-rate warmup from 0 to --learning_rate over this "
        "many optimizer updates (0 = none).  Training-quality knob for "
        "workloads whose early gradients are outsized relative to the "
        "init scale — the async cifar10 path defaults it on (see the "
        "example) so stale first applies cannot collapse the relu stack.",
    )
    _define("integer", "seed", 0, "Global RNG seed (determinism knob).")
    _define(
        "integer", "log_every_steps", 100, "Metric logging cadence (LoggingTensorHook analog)."
    )
    _define(
        "integer", "checkpoint_every_steps", 1000, "CheckpointSaverHook save cadence."
    )
    _define(
        "integer", "unroll", 1, "Steps fused per dispatch (lax.scan multi-step trains)."
    )
    _define(
        "integer",
        "grad_accum",
        1,
        "Gradient-accumulation microbatches per step: activation memory of "
        "batch/k at full-batch numerics (one optimizer update).",
    )
    _define(
        "string",
        "mesh",
        "",
        'Mesh spec, e.g. "data=8,model=2"; empty = all devices on the data axis.',
    )
    _define("bool", "profile", False, "Capture a jax.profiler trace window.")
    _define(
        "string",
        "obs_events_dir",
        "",
        "Observability (r13 dtxobs): directory where each cluster task "
        "dumps its structured-event flight recorder (one "
        "flight-<role>-<pid>.jsonl per process) on fatal conditions — "
        "replication divergence, reconnect-budget exhaustion, injected "
        "deaths.  Exported to child tasks via DTX_OBS_EVENTS_DIR.  Empty "
        "= on-fatal dumps are skipped (live scraping via the STATS ops / "
        "tools/dtxtop.py works regardless).",
    )
    _define(
        "string",
        "platform",
        "",
        'Force the JAX platform (e.g. "cpu") from inside the CLI — the '
        "flag spelling of the JAX_PLATFORMS environment variable.",
    )
    _define(
        "bool",
        "zero_opt",
        False,
        "ZeRO-1 optimizer-state sharding: shard replicated optimizer slots "
        "over the data axis (reduce-scatter grads, sharded update, "
        "all-gather params — identical numerics, 1/dp the optimizer HBM).",
    )
    _define(
        "bool",
        "watchdog",
        True,
        "Multi-process peer-heartbeat watchdog: exit fast (code 83) when a "
        "peer dies instead of hanging in the next collective, so a "
        "supervisor can restart the job (crash-restart recovery).",
    )
    _define(
        "float",
        "watchdog_grace_secs",
        10.0,
        "Heartbeat staleness after which a peer is declared dead.",
    )
    _define(
        "bool",
        "deterministic",
        False,
        "Run-to-run determinism (enable_op_determinism analog): partitionable "
        "threefry + highest matmul precision.",
    )


def define_legacy_cluster_flags():
    """TF-1 PS/worker cluster flags: accepted for CLI compatibility, mapped to
    mesh topology (SURVEY.md D1/D9 -> mesh)."""
    _define("string", "ps_hosts", "", "(legacy) comma-separated PS host:port list.")
    _define(
        "string", "worker_hosts", "", "(legacy) comma-separated worker host:port list."
    )
    _define("string", "job_name", "", '(legacy) "ps" or "worker".')
    _define("integer", "task_index", 0, "(legacy) task index within the job.")
    _define(
        "bool", "sync_replicas", True, "(legacy) SyncReplicasOptimizer on/off -> sync/async DP."
    )
    _define(
        "bool",
        "ps_emulation",
        False,
        "Run the PS-emulation trainer even in sync mode: token-gated "
        "SyncReplicasOptimizer semantics (accumulate/drop-stale/chief-apply/"
        "token-dequeue) via the native accumulator service (D5).",
    )
    _define(
        "integer",
        "ps_tasks",
        -1,
        "Cross-process PS launch: number of dedicated --job_name=ps "
        "processes in the cluster (-1 = one per --ps_hosts entry, the "
        "reference convention; 0 = no PS task, the chief hosts the state "
        "service in-process).",
    )
    _define(
        "bool",
        "ps_listen_all",
        False,
        "Bind the (unauthenticated) PS state service on ALL interfaces so "
        "workers on other hosts can reach it.  Off = loopback only.  "
        "Required whenever the task's --ps_hosts entry is not a literal "
        "loopback address — network exposure must be an explicit operator "
        "decision, never inferred from hostname spelling (ADVICE r4).",
    )
    _define(
        "integer",
        "ps_shards",
        -1,
        "Sharded parameter store (r9): partition the flat param/gradient "
        "vector over this many PS servers (contiguous ShardLayout slices; "
        "pulls/pushes scatter/gather in parallel, one connection per "
        "shard).  -1 = one shard per --ps_hosts entry (the reference's "
        "replica_device_setter convention); must not exceed the host "
        "count.  1 = the single-server r7 wire, byte-identical.",
    )
    _define(
        "integer",
        "ps_replicas",
        1,
        "PS shard replication (r12): servers holding EACH shard.  2 gives "
        "every shard a primary/backup pair — --ps_hosts then lists "
        "shards*2 entries, the first half primaries, the second half "
        "backups (task i serves shard i%%shards, replica i//shards).  "
        "Primaries forward state-mutating ops to their backup; a client "
        "whose primary dies (or restarts empty) fails over to the backup "
        "with ZERO chief involvement (state-token checked), and a "
        "restarted replica catches up from the survivor via REPL_SYNC "
        "before serving.  1 = the unreplicated pre-r12 wire.",
    )
    _define(
        "integer",
        "ps_layout_version",
        0,
        "PS shard-layout EPOCH (r12): carried in the HELLO shard-identity "
        "word by every server and client of the topology, so a client "
        "from a different epoch (e.g. a stale task surviving a reshard) "
        "fails its dial loudly naming both versions instead of silently "
        "scattering onto the wrong partition.  0 = unversioned.",
    )
    _define(
        "string",
        "ps_reshard_to",
        "",
        "Live PS resharding (r15): makes a --job_name=ps task a JOINER of "
        "a layout-epoch transition.  Format 'V:host:port,host:port,...' — "
        "V is the NEW epoch (> --ps_layout_version) and the list is the "
        "new topology (this task serves entry --task_index).  The joiner "
        "assembles its slice of the flat parameter vector from the OLD "
        "topology (--ps_hosts/--ps_shards/--ps_layout_version) over "
        "slice-ranged REPL_SYNC, announces the transition as the "
        "coordinator's pending record, and heartbeats a 'ps'-kind lease; "
        "the running chief verifies every joiner, republishes current "
        "params, commits the epoch, every client swaps (in-flight pushes "
        "stay at-most-once via epoch-scoped dedup tags), and the old "
        "tasks drain and exit 0.  Empty = a normal (non-joiner) PS task.  "
        "See RUNBOOK 'Live resharding'.",
    )
    _define(
        "integer",
        "ps_restarts",
        3,
        "Cross-process PS launch: run the --job_name=ps task under "
        "utils.supervisor.supervise() with this restart budget, so a PS "
        "crash is healed by PS restart + client reconnect (partial "
        "recovery) instead of the whole-job crash-restart path.  0 "
        "disables supervision (a PS crash then fails the job once the "
        "clients' reconnect budget runs out).",
    )
    _define(
        "string",
        "ps_wire_dtype",
        "f32",
        "Cross-process PS wire encoding: f32 (exact) or bf16 (half the "
        "param/grad bytes; PS stores f32 and converts at the socket "
        "boundary — a bandwidth knob for real networks, negotiated at "
        "connect so mismatched peers fail loudly).  See RUNBOOK 'PS "
        "transport tuning' for when bf16 is accuracy-safe.",
    )
    _define(
        "bool",
        "ps_prefetch",
        True,
        "Async cross-process workers: double-buffer param pulls on a "
        "dedicated background connection so the next step's pull overlaps "
        "the current step's gradient compute (adds at most one step of "
        "parameter staleness; sync mode never prefetches).",
    )
    _define(
        "string",
        "data_service_hosts",
        "",
        "Disaggregated data service: host:port list where --job_name="
        "data_service tasks listen (entry [task_index] is this task's bind "
        "address).  Training workers reach the service via "
        "--data_dir=dsvc://host:port; the task serves the shard files under "
        "its own --data_dir.  Exposure rules follow --ps_listen_all; the "
        "task restarts under --ps_restarts like the PS task.",
    )
    _define(
        "string",
        "serve_hosts",
        "",
        "Online inference plane (r10): host:port list where --job_name="
        "serve model replicas listen (entry [task_index] is this task's "
        "bind address).  Each replica hot-tracks the (sharded) parameter "
        "store at --ps_hosts with versioned pulls and serves micro-batched "
        "predictions under the msrv service tag; clients load-balance "
        "round-robin over the full list (serve.ServePool).  Exposure rules "
        "follow --ps_listen_all; the task restarts under --ps_restarts "
        "like the PS and data-service tasks.",
    )
    _define(
        "integer",
        "serve_max_batch",
        32,
        "Serving replicas: max rows coalesced into one jitted apply "
        "(the dynamic micro-batcher's row budget).",
    )
    _define(
        "float",
        "serve_max_wait_ms",
        5.0,
        "Serving replicas: how long a non-full micro-batch waits for more "
        "requests after its first one arrived — the latency spent buying "
        "coalescing.",
    )
    _define(
        "integer",
        "serve_queue_depth",
        128,
        "Serving replicas: max in-system predict requests before the "
        "replica answers an explicit OVERLOAD status (admission control; "
        "resilient clients rotate/back off instead of piling on).",
    )
    _define(
        "float",
        "serve_queue_deadline_ms",
        0.0,
        "Serving replicas: queue-deadline budget (r18 admission control) — "
        "a predict that waited in the replica's dispatch queue past this "
        "budget is shed with a typed RETRY_LATER answer before a worker "
        "touches it (the caller has abandoned or is about to abandon it). "
        "0 = no server-side policy; only deadlines the CLIENTS stamp on "
        "their frames apply.",
    )
    _define(
        "float",
        "serve_refresh_ms",
        50.0,
        "Serving replicas: parameter-store poll cadence.  Each poll is one "
        "O(header) round trip per shard while the published step is "
        "unchanged (PSTORE_GET_IF_NEWER), so tight cadences stay cheap.",
    )
    _define(
        "string",
        "registry_dir",
        "",
        "Model registry root (r19, serve/registry.py): a directory of "
        "immutable (model_name, version) flat-param snapshots with "
        "fsync'd atomic manifests and lease-style pins.  Training CLIs "
        "PUBLISH their final params here as a new version; a "
        "--job_name=serve replica given --serve_model_version PINS one "
        "version from here instead of hot-tracking the PS (registry GC "
        "never deletes a version a live replica has pinned).  Empty = no "
        "registry (the pre-r19 hot-tracking-only serve plane).",
    )
    _define(
        "integer",
        "serve_model_version",
        0,
        "Serving replicas (r19): pin this registry version from "
        "--registry_dir and serve it IMMUTABLY — the version stamps the "
        "msrv HELLO word, every predict/decode response and STATS, so "
        "pools route and account per version (canary vs stable) and "
        "rolling deploys flip a live pool with zero failed requests.  0 "
        "= hot-track the live training run off the PS (the r10 "
        "behavior).",
    )
    _define(
        "bool",
        "membership_leases",
        True,
        "Elastic membership (r14): async workers and serve replicas "
        "heartbeat a lease on the coordinator PS shard, so the chief, the "
        "data service and tools/dtxtop.py learn the LIVE member set from "
        "the registry instead of static --worker_hosts — a worker can "
        "join or leave mid-run with no restart of anything else, and an "
        "expired lease reassigns the member's in-flight splits "
        "immediately.  Degrades loudly to the static posture against a "
        "pre-r14 PS.  Off = no lease traffic (the pre-r14 wire).",
    )
    _define(
        "float",
        "lease_ttl_s",
        10.0,
        "Membership lease TTL in seconds: a member whose heartbeats stop "
        "for this long is treated as departed (lease pruned, splits "
        "reassigned).  Heartbeats renew at ttl/3, so two missed beats "
        "still keep the lease alive.",
    )
    _define(
        "string",
        "tenant",
        "default",
        "Multi-tenancy (r20): the tenant this task belongs to.  Every PS "
        "object the run creates lives under the 't.<tenant>.' key "
        "namespace, its membership leases / data-service job / served "
        "model are tenant-scoped, and the shared servers account and "
        "admission-control its traffic per tenant — several runs share "
        "one PS/data/serve plane without ever touching each other's "
        "state.  'default' = untagged (byte-identical pre-r20 wire).  "
        "See RUNBOOK 'Multi-tenancy'.",
    )
    _define(
        "string",
        "tenant_quotas",
        "",
        "Multi-tenancy (r20), SERVER tasks (ps/data_service/serve): "
        "per-tenant weighted-fair dispatch weights and quota caps, "
        "'tenant=weight[:max_inflight[:max_dispatch]],...' (e.g. "
        "'runa=3,runb=1:64:8').  Dispatch capacity is divided "
        "weight-proportionally under contention (stride scheduling); a "
        "tenant past a hard cap gets typed RETRY_LATER answers while "
        "other tenants flow.  Unlisted tenants get weight 1, no caps.  "
        "Empty = every tenant weight 1, uncapped.",
    )
    _define(
        "integer",
        "replicas_to_aggregate",
        0,
        "(legacy, sync_replicas) gradients to aggregate per update; 0 = "
        "number of workers.",
    )
    _define(
        "integer",
        "max_staleness",
        0,
        "(async mode) drop gradients older than this many applied steps; "
        "0 = unbounded (the reference's async behavior).",
    )


def is_cross_process_ps(FLAGS) -> bool:
    """True when the CLI requests the reference's one-process-per-task PS
    launch (SURVEY.md sections 3.1/3.2): a PS-emulation mode is selected,
    a PS service address is given, and this process was assigned a task
    role.  In that topology ``--ps_hosts`` is MEANINGFUL — it is where the
    native state service (native/ps_server.cc) listens.  The
    ``data_service`` job is a task of the same launch pattern: a dedicated
    input-worker process serving batches (data/data_service.py) — it needs
    only ``--data_service_hosts``, not a PS service.  The ``serve`` job
    (r10) is a model replica of the inference plane: it needs BOTH a bind
    address (``--serve_hosts``) and the PS topology it pulls params from."""
    if getattr(FLAGS, "job_name", "") == "data_service":
        return bool(getattr(FLAGS, "data_service_hosts", ""))
    if getattr(FLAGS, "job_name", "") == "serve":
        return bool(getattr(FLAGS, "serve_hosts", "")) and bool(
            getattr(FLAGS, "ps_hosts", "")
        )
    return (
        getattr(FLAGS, "job_name", "") in ("chief", "worker", "ps")
        and bool(getattr(FLAGS, "ps_hosts", ""))
        and (getattr(FLAGS, "ps_emulation", False) or not getattr(FLAGS, "sync_replicas", True))
    )


def parse_hostports(spec: str, flag: str = "--ps_hosts") -> list[tuple[str, int]]:
    """Validate a comma-separated ``host:port`` list into addr tuples.
    Malformed entries (empty, missing/non-numeric port, duplicates) fail
    the launch loudly — a typo'd shard list must never silently collapse
    onto fewer servers than the operator asked for."""
    addrs: list[tuple[str, int]] = []
    for entry in spec.split(","):
        entry = entry.strip()
        host, sep, port_s = entry.rpartition(":")
        if not entry or not sep or not host or not port_s.isdigit():
            raise ValueError(
                f"{flag} entry {entry!r} is not host:port (full list: {spec!r})"
            )
        addr = (host, int(port_s))
        if addr in addrs:
            raise ValueError(f"{flag} lists {entry!r} twice ({spec!r})")
        addrs.append(addr)
    return addrs


def ps_shard_topology(FLAGS) -> tuple[list[tuple[str, int]], int, int]:
    """The validated PS shard topology: the FULL ``--ps_hosts`` address
    list plus the resolved shard count (``--ps_shards``; -1 = one shard
    per host) and replica count (``--ps_replicas``, r12).  Shard i's
    PRIMARY is ``addrs[i]`` and replica r of shard i is
    ``addrs[r*shards + i]`` (replica-major) — the ONE place the
    host-order/shard-id correspondence is defined (r9 fix: the pre-r9
    path warned and silently used ``ps_hosts[0]`` only)."""
    addrs = parse_hostports(FLAGS.ps_hosts)
    raw = getattr(FLAGS, "ps_shards", -1)
    n = -1 if raw is None else int(raw)
    r = int(getattr(FLAGS, "ps_replicas", 1) or 1)
    if r not in (1, 2):
        raise ValueError(
            f"--ps_replicas={r} unsupported (1 = unreplicated, 2 = "
            "primary/backup pairs; deeper chains are not implemented)"
        )
    if n < 0:
        if len(addrs) % r:
            raise ValueError(
                f"--ps_replicas={r} does not tile {len(addrs)} --ps_hosts "
                "entries (need shards*replicas hosts)"
            )
        n = len(addrs) // r
    if n == 0 or n * r > len(addrs):
        raise ValueError(
            f"--ps_shards={n} x --ps_replicas={r} invalid for {len(addrs)} "
            f"--ps_hosts entries (need shards*replicas <= {len(addrs)}, "
            "or -1 shards for one shard per host)"
        )
    return addrs, n, r


def parse_reshard_to(spec: str) -> tuple[int, list[tuple[str, int]]]:
    """Validate a ``--ps_reshard_to`` spec: ``V:host:port,host:port,...``
    into ``(new_version, new_addrs)``.  Malformed specs fail the launch
    loudly — a typo'd target topology must never half-join a transition."""
    version_s, sep, hosts = spec.partition(":")
    if not sep or not version_s.isdigit() or int(version_s) <= 0:
        raise ValueError(
            f"--ps_reshard_to {spec!r} must be 'V:host:port,...' with a "
            "positive integer epoch V"
        )
    return int(version_s), parse_hostports(hosts, "--ps_reshard_to")


def resolve_legacy_cluster(FLAGS) -> dict:
    """Interpret legacy cluster flags against the mesh world; returns info for
    the example to log.  A process launched as a PS task has no role in SPMD:
    we exit 0 immediately (the analog of ``server.join()`` never being
    needed) — UNLESS cross-process PS emulation is active, where the PS
    task hosts the native state service for real (is_cross_process_ps).

    Also applies ``--platform`` (must run before first backend use)."""
    if getattr(FLAGS, "platform", ""):
        import jax

        jax.config.update("jax_platforms", FLAGS.platform)
    info = {}
    cross = is_cross_process_ps(FLAGS)
    # Any PS-emulation mode (cross-process OR the single-process thread
    # emulation): --ps_hosts is meaningful topology, never "obsolete".
    emulation = cross or (
        getattr(FLAGS, "ps_emulation", False)
        or not getattr(FLAGS, "sync_replicas", True)
    )
    if getattr(FLAGS, "ps_hosts", ""):
        if emulation:
            # Validate and surface the FULL list (r9 fix: this path used
            # to log entry [0] only, hiding a sharded topology's servers).
            addrs, n_shards, n_replicas = ps_shard_topology(FLAGS)
            info["ps_hosts"] = [f"{h}:{p}" for h, p in addrs]
            info["ps_shards"] = n_shards
            info["ps_replicas"] = n_replicas
            log.info(
                "--ps_hosts given with PS emulation: %d host(s), %d "
                "shard(s) x %d replica(s) — the native state service "
                "serves shard i%%%d, replica i//%d at entry i: %s.",
                len(addrs), n_shards, n_replicas, n_shards, n_shards,
                ",".join(info["ps_hosts"][: n_shards * n_replicas]),
            )
        else:
            info["ps_hosts"] = FLAGS.ps_hosts.split(",")
            log.warning(
                "--ps_hosts given: parameter servers are obsolete on TPU — "
                "variables are mesh-sharded in HBM (replica_device_setter -> "
                "sharding rules). Ignoring %d PS hosts.",
                len(info["ps_hosts"]),
            )
    if getattr(FLAGS, "worker_hosts", ""):
        info["worker_hosts"] = FLAGS.worker_hosts.split(",")
        log.info(
            "--worker_hosts given (%d workers): %s",
            len(info["worker_hosts"]),
            "cross-process PS emulation — one worker process per entry"
            if cross
            else "on TPU the equivalent data-parallel degree comes from the "
            "mesh; launch one process per host with jax.distributed (see "
            "parallel.dist).",
        )
    info["is_legacy_ps_process"] = (
        getattr(FLAGS, "job_name", "") == "ps" and not cross
    )
    return info
