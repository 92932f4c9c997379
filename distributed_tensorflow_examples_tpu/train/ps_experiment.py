"""CLI-level runner for the PS-emulation modes (SURVEY.md D5, section 3.1/3.2).

One shared path so every example honors ``--sync_replicas`` uniformly
(round-1 review: only cifar10_cnn did, and the token-gated ``sync_replicas``
mode — W1's actual SyncReplicasOptimizer semantics — was reachable only from
tests):

- ``--sync_replicas=false``           -> async mode (W2: each worker's
  gradient applies immediately, in arrival order).
- ``--ps_emulation --sync_replicas``  -> token-gated sync_replicas mode (W1:
  accumulate ``--replicas_to_aggregate`` grads, drop stale, chief applies,
  workers proceed on tokens).

Both run on ``parallel.async_ps.AsyncPSTrainer`` (native C++ accumulator /
token-queue / gradient-queue services) with checkpoint/resume under
``--log_dir`` and print the same scrapable FINAL line as ``Experiment``.

Note on model_state: the emulation keeps non-parameter state (e.g. BatchNorm
statistics) at its initial value — the reference's async-PS scripts hosted
only *variables* on PS tasks; workloads with running statistics (W3) are not
PS workloads in the reference either.
"""

from __future__ import annotations

import dataclasses
import logging
import os
import sys
import time
from typing import Any, Callable, Iterator

import numpy as np

log = logging.getLogger("dtx.ps_experiment")


def worker_count(FLAGS) -> int:
    """Emulated worker count from the legacy cluster flags (the ONE place
    this is computed — CLIs that shard data per worker must use it too)."""
    return max(2, len(FLAGS.worker_hosts.split(",")) if FLAGS.worker_hosts else 2)


def run_ps_emulation(
    *,
    init_fn: Callable,
    loss_fn: Callable,
    optimizer,
    batches_for_worker: Callable[[int, int, int], Iterator[dict]],
    FLAGS,
    mode: str,
    eval_fn: Callable[[Any], dict[str, float]] | None = None,
    model_state: Any = None,
    predict_fn: Callable | None = None,
) -> Any:
    """Run W1/W2 PS-emulation training; returns final params.

    ``batches_for_worker(worker_id, local_batch_size, n_workers)`` yields
    that worker's local batches (its data shard; the count is passed so data
    sharding can never diverge from the thread count); ``eval_fn(params)``
    computes final metrics for the FINAL line.  ``predict_fn(params,
    inputs)`` is the row-wise inference apply a ``--job_name=serve``
    replica (r10) would serve — only that task role needs it.

    With ``--job_name=ps|chief|worker`` and ``--ps_hosts`` (the reference's
    one-process-per-task launch, SURVEY.md sections 3.1/3.2) this process
    runs ONLY its task's role over the native socket service instead of the
    in-process thread emulation — see :func:`run_ps_cluster_task`.
    """
    import jax

    from ..parallel.async_ps import AsyncPSConfig, AsyncPSTrainer
    from ..utils.flags import is_cross_process_ps

    if is_cross_process_ps(FLAGS):
        return run_ps_cluster_task(
            init_fn=init_fn,
            loss_fn=loss_fn,
            optimizer=optimizer,
            batches_for_worker=batches_for_worker,
            FLAGS=FLAGS,
            mode=mode,
            eval_fn=eval_fn,
            model_state=model_state,
            predict_fn=predict_fn,
        )

    n_workers = worker_count(FLAGS)
    r2a = getattr(FLAGS, "replicas_to_aggregate", 0) or n_workers
    if getattr(FLAGS, "grad_accum", 1) > 1:
        log.warning(
            "--grad_accum=%d is ignored in PS-emulation mode (per-worker "
            "gradients apply individually; accumulation is a mesh-trainer "
            "feature)", FLAGS.grad_accum,
        )
    log.info(
        "PS emulation mode=%s: %d workers%s (native accumulator/token "
        "services; semantics notes in parallel.async_ps)",
        mode,
        n_workers,
        f", replicas_to_aggregate={r2a}" if mode == "sync_replicas" else "",
    )
    acfg = _ps_cfg(FLAGS, mode, n_workers)
    params = init_fn(jax.random.key(FLAGS.seed))
    if isinstance(params, tuple):  # init_fn returning (params, model_state)
        params, model_state = params
    trainer = AsyncPSTrainer(
        acfg,
        loss_fn,
        optimizer,
        params,
        model_state=model_state,
        rng=jax.random.key(FLAGS.seed),
    )
    local_bs = max(1, FLAGS.batch_size // n_workers)
    t0 = time.perf_counter()
    final_params = trainer.run(
        [
            iter(batches_for_worker(w, local_bs, n_workers))
            for w in range(n_workers)
        ]
    )
    dt = time.perf_counter() - t0  # training window only (eval excluded)

    metrics = eval_fn(final_params) if eval_fn is not None else {}
    sps = trainer.global_step / dt if dt > 0 else 0.0
    losses = [l for (_, _, l) in trainer.history] or [float("nan")]
    _print_final(
        step=trainer.global_step, dt=dt, local_bs=local_bs, mode=mode,
        metrics=metrics,
        # Sync mode consumes replicas_to_aggregate worker batches per
        # applied step — count them all, not just the chief's one
        # (ADVICE r5: the old definition undercounted by ~n_workers).
        eps_per_chip=sps * local_bs * (r2a if mode == "sync_replicas" else 1)
        / max(1, len(jax.devices())),
        extra={
            "stale_dropped": trainer.total_dropped,
            "first_loss": f"{losses[0]:.4f}",
            "last_loss": f"{losses[-1]:.4f}",
        },
    )
    return final_params


def _print_final(
    *, step: int, dt: float, local_bs: int, mode: str,
    metrics: dict, extra: dict, eps_per_chip: float | None = None,
):
    """The ONE scrapable FINAL line both PS paths (thread emulation and
    cross-process cluster) print — same fields, same order."""
    sps = step / dt if dt > 0 else 0.0
    if eps_per_chip is None:
        eps_per_chip = sps * local_bs
    parts = [
        f"FINAL step={step}",
        f"steps_per_sec={sps:.1f}",
        f"examples_per_sec_per_chip={eps_per_chip:.0f}",
        f"mode={mode}",
    ]
    for k, v in extra.items():
        parts.append(f"{k}={v}")
    for k, v in metrics.items():
        parts.append(f"{k}={v:.4f}" if isinstance(v, float) else f"{k}={v}")
    print(" ".join(parts))


def _ps_cfg(FLAGS, mode: str, n_workers: int):
    from ..parallel.async_ps import AsyncPSConfig

    r2a = getattr(FLAGS, "replicas_to_aggregate", 0) or n_workers
    return AsyncPSConfig(
        num_workers=n_workers,
        mode=mode,
        replicas_to_aggregate=r2a if mode == "sync_replicas" else None,
        max_staleness=getattr(FLAGS, "max_staleness", None) or None,
        # --deterministic: async applies keep their stale-params semantics
        # but run on the fixed round-robin schedule (reproducible runs —
        # and a retry-free CLI acceptance gate).
        fixed_interleave=bool(getattr(FLAGS, "deterministic", False)),
        train_steps=FLAGS.train_steps,
        ckpt_dir=os.path.join(FLAGS.log_dir, "ps_ckpt") if FLAGS.log_dir else None,
        checkpoint_every=FLAGS.checkpoint_every_steps,
        # r7 transport knobs (getattr: embedded callers' FLAGS namespaces
        # predate them).  PSClient validates the dtype, so a typo'd
        # --ps_wire_dtype fails the launch loudly.
        ps_wire_dtype=getattr(FLAGS, "ps_wire_dtype", "f32") or "f32",
        ps_prefetch=bool(getattr(FLAGS, "ps_prefetch", True)),
        # r14 elasticity knobs (getattr for embedded callers, as above).
        membership_leases=bool(getattr(FLAGS, "membership_leases", True)),
        lease_ttl_s=float(getattr(FLAGS, "lease_ttl_s", 10.0) or 10.0),
        # r20 multi-tenancy: the run's tenant namespace (getattr for
        # embedded callers).  tenancy.check_tenant inside the clients
        # rejects a typo'd --tenant loudly at dial time.
        tenant=getattr(FLAGS, "tenant", "default") or "default",
    )


def _tenant_quotas(FLAGS):
    """--tenant_quotas parsed to the ServerCore quota table (r20), or None.
    A malformed spec fails the SERVER launch loudly here — never silently
    serving with fairness off."""
    from ..parallel import tenancy

    spec = getattr(FLAGS, "tenant_quotas", "") or ""
    return tenancy.parse_quotas(spec) if spec else None


def _resolve_listen_all(FLAGS, host: str, flag: str = "--ps_hosts") -> bool:
    """Network exposure is an explicit operator decision (--ps_listen_all),
    never inferred from how the hostname is spelled: '::1' or a
    loopback-resolving FQDN must not silently bind INADDR_ANY, and a
    non-loopback entry without the flag is a launch error, not a silent
    network-wide bind of an unauthenticated service (ADVICE r4).  Applies
    to EVERY service-hosting path: the dedicated PS task, the chief-hosted
    (--ps_tasks=0) service, the data-service task, and the serve replicas
    (``flag`` names the host list the entry came from)."""
    listen_all = bool(getattr(FLAGS, "ps_listen_all", False))
    if not listen_all and host not in ("127.0.0.1", "localhost"):
        raise ValueError(
            f"{flag} entry {host!r} is not a literal loopback "
            "address; serving other hosts needs the unauthenticated "
            "state service bound on all interfaces — opt in explicitly "
            "with --ps_listen_all (trusted networks only)"
        )
    if listen_all:
        log.warning(
            "--ps_listen_all: PS state service binding ALL interfaces "
            "(UNAUTHENTICATED — trusted networks only)"
        )
    return listen_all


def _probe_ps(host: str, port: int, deadline_s: float) -> bool:
    """True when a PS service answers PING at host:port within the window."""
    from ..parallel import ps_service

    t_end = time.time() + deadline_s
    while time.time() < t_end:
        try:
            c = ps_service.PSClient(host, port, timeout_s=2.0)
            c.ping()
            c.close()
            return True
        except (OSError, ps_service.PSError):
            # PSError covers a PS that accepts the connection but drops it
            # mid-ping (e.g. mid-restart under the supervisor) — keep
            # polling, exactly like a refused connection.
            time.sleep(0.2)
    return False


def _supervised_reexec(FLAGS, *, child_env_flag: str) -> int | None:
    """Re-exec this launch under ``utils.supervisor.supervise()`` — the
    service-task crash-heal path shared by the ``ps`` and ``data_service``
    roles.  Returns the supervisor's exit code when THIS process acted as
    the supervisor (the caller exits with it), or None when the caller
    should host the service itself: supervision disabled, a
    non-re-executable launcher, or this process IS the supervised child
    (``child_env_flag`` set).  A fault-INJECTED death is healed by
    stripping the fired ``die`` spec from the restarted child's plan."""
    from ..utils import faults

    restarts = int(getattr(FLAGS, "ps_restarts", 0) or 0)
    launcher = os.path.abspath(sys.argv[0]) if sys.argv else ""
    if restarts > 0 and not (launcher.endswith(".py") and os.path.isfile(launcher)):
        # Supervision re-execs the launch script; a programmatic or
        # embedded caller whose argv does not reproduce this config
        # would supervise the WRONG thing — host unsupervised instead.
        log.warning(
            "--ps_restarts=%d: launcher %r is not a re-executable "
            "script; hosting the service unsupervised (a crash falls "
            "back to whole-job restart)", restarts, sys.argv[:1],
        )
        restarts = 0
    if restarts <= 0 or os.environ.get(child_env_flag) == "1":
        return None
    from ..utils import supervisor

    env = dict(os.environ)
    env[child_env_flag] = "1"

    def heal_fault_plan(env: dict, attempt: int, returncode: int) -> dict:
        # A fault-INJECTED death must not re-fire in the healing
        # incarnation (the plan is inherited through the env);
        # organic crashes keep the plan untouched.
        if returncode == faults.FAULT_EXIT_CODE and env.get("DTX_FAULT_PLAN"):
            env["DTX_FAULT_PLAN"] = faults.plan_without(
                env["DTX_FAULT_PLAN"], "die", faults.current_role()
            )
            faults.log_event(
                "supervisor_healed_plan", role=faults.current_role(),
                attempt=attempt,
            )
        return env

    return supervisor.supervise(
        [sys.executable, os.path.abspath(sys.argv[0]), *sys.argv[1:]],
        max_restarts=restarts,
        env=env,
        mutate_env=heal_fault_plan,
    )


def run_ps_cluster_task(
    *, init_fn, loss_fn, optimizer, batches_for_worker, FLAGS, mode, eval_fn=None,
    model_state=None, predict_fn=None,
):
    """One task of the reference's multi-process PS cluster (its defining
    launch pattern — one process per ``--job_name``/``--task_index``,
    SURVEY.md sections 3.1/3.2), over the native socket service:

    - ``ps``:     hosts the C++ state service at ``--ps_hosts[task_index]``
                  until the chief signals shutdown (``server.join()`` role).
                  Task i owns SHARD i of the flat parameter vector (r9,
                  ``parallel/ps_shard.ShardLayout`` over ``--ps_shards``
                  servers; -1 = one per host — the reference's
                  ``replica_device_setter`` spreading): param pulls,
                  publishes and gradient pushes scatter/gather over every
                  shard in parallel, while step tokens and the shutdown
                  signal stay on shard 0 (the coordinator).
    - ``chief``:  aggregation/apply/publish loop (``RemotePSChief``).
                  Topology is DETERMINISTIC, not probed: with
                  ``--ps_tasks=0`` the chief hosts every shard server
                  in-process (3-process minimum launch); otherwise
                  dedicated PS tasks are expected at ``ps_hosts[0:N]`` and
                  waited for (120 s each).
    - ``worker``: gradient computation against the published snapshots
                  (``remote_worker_loop``), data-sharded by ``task_index``.
    - ``data_service`` (r8): dedicated input worker — serves decoded,
                  batched shards from its ``--data_dir`` at
                  ``--data_service_hosts[task_index]``; training workers
                  consume via ``--data_dir=dsvc://host:port``
                  (``data/data_service.py``).  Needs no PS service.
    - ``serve`` (r10): online inference replica — hot-tracks the (sharded)
                  parameter store with versioned pulls and serves
                  micro-batched predictions at
                  ``--serve_hosts[task_index]`` under the ``msrv`` service
                  tag (``serve/model_server.py``; needs ``predict_fn``).
                  Clients load-balance over the full list
                  (``serve.ServePool``).  Restarts under ``--ps_restarts``
                  like the other service tasks: a killed replica re-pulls
                  the current params from the PS and rejoins with zero
                  coordination.

    Fault posture (r6): each task gets a fault role (``ps0``, ``chief0``,
    ``worker<i>``, ``data_service0``) for ``DTX_FAULT_PLAN`` matching, and the PS task runs
    under ``utils.supervisor.supervise()`` (``--ps_restarts``), so a PS
    crash is healed by PS restart + client reconnect/reseed instead of the
    whole-job crash-restart path — see RUNBOOK.md "Fault injection &
    recovery".

    Launch recipe: RUNBOOK.md "Cross-process PS".
    """
    import jax

    from ..parallel import async_ps
    from ..utils import compile_cache, faults, telemetry

    n_workers = worker_count(FLAGS)
    local_bs = max(1, FLAGS.batch_size // n_workers)
    job = FLAGS.job_name
    if not faults.current_role():
        faults.set_role(f"{job}{FLAGS.task_index}")
    # Observability (r13 dtxobs): export the flight-recorder dump directory
    # to this task AND everything it spawns (supervised re-execs inherit
    # the environment), so every role of the cluster dumps its event ring
    # to one place on fatal conditions.  Env wins when both are set — the
    # launcher may already have threaded it through.
    obs_dir = getattr(FLAGS, "obs_events_dir", "") or ""
    if obs_dir and not os.environ.get(telemetry.EVENTS_DIR_ENV):
        os.environ[telemetry.EVENTS_DIR_ENV] = obs_dir

    if job == "data_service":
        # Disaggregated input worker (r8): serves ready batches from this
        # task's --data_dir shards to training workers that resolve
        # --data_dir=dsvc://host:port (data/data_service.py).  Same
        # supervised-restart contract as the PS task — a killed data server
        # comes back on the same port and the clients re-claim their
        # in-flight splits mid-epoch.  Needs no PS service of its own.
        from ..data import data_service as dsvc_lib

        ds_hosts = getattr(FLAGS, "data_service_hosts", "") or ""
        if not ds_hosts:
            raise ValueError(
                "--job_name=data_service needs --data_service_hosts "
                "(host:port this task binds)"
            )
        ds_entries = ds_hosts.split(",")
        my_host, my_port = ds_entries[
            min(FLAGS.task_index, len(ds_entries) - 1)
        ].rsplit(":", 1)
        listen_all = _resolve_listen_all(FLAGS, my_host, "--data_service_hosts")
        rc = _supervised_reexec(FLAGS, child_env_flag="DTX_DSVC_SUPERVISED")
        if rc is not None:
            if rc != 0:
                raise SystemExit(rc)
            return None
        # Elasticity (r14): when the launch carries a PS topology, watch
        # the coordinator shard's lease registry so a departed worker's
        # splits reassign on the membership signal, not the liveness
        # window.
        lease_addrs = None
        if getattr(FLAGS, "ps_hosts", "") and bool(
            getattr(FLAGS, "membership_leases", True)
        ):
            from ..parallel.membership import coordinator_addrs
            from ..utils.flags import ps_shard_topology

            entries, n_shards, n_replicas = ps_shard_topology(FLAGS)
            lease_addrs = coordinator_addrs(entries, n_shards, n_replicas)
        bound = dsvc_lib.host_data_service_task(
            FLAGS.data_dir, int(my_port), batch_size=local_bs,
            seed=FLAGS.seed, loopback_only=not listen_all,
            ps_addrs=lease_addrs,
            ps_layout_version=int(
                getattr(FLAGS, "ps_layout_version", 0) or 0
            ),
            tenant_quotas=_tenant_quotas(FLAGS),
        )
        print(f"DSVC_DONE port={bound}")
        return None

    from ..utils.flags import ps_shard_topology

    entries, n_shards, n_replicas = ps_shard_topology(FLAGS)
    # The sharded-store topology (r9): shard i's PRIMARY server is
    # entries[i]; every client scatters/gathers over all of them in
    # parallel.  Shard 0 doubles as the coordinator (tokens, shutdown
    # signal).  Replication (r12): replica r of shard i is
    # entries[r*n_shards + i] — clients carry the full per-shard replica
    # list and fail over inside their own recovery loop.
    shard_addrs = entries[: n_shards * n_replicas]
    primary_addrs = entries[:n_shards]
    layout_version = int(getattr(FLAGS, "ps_layout_version", 0) or 0)
    host, port = shard_addrs[0]

    if job == "serve":
        # Online inference replica (r10): hot-track the parameter store
        # these same shard servers host and serve micro-batched
        # predictions.  Same supervised-restart contract as the PS and
        # data-service tasks — a killed replica comes back on the same
        # port, re-pulls the CURRENT params from the PS (the store is the
        # rendezvous; zero coordination) and rejoins the client rotation.
        from .. import serve as serve_pkg
        from ..utils.flags import parse_hostports

        if predict_fn is None:
            raise ValueError(
                "--job_name=serve needs a predict_fn (the row-wise "
                "inference apply) passed through run_ps_emulation / "
                "run_ps_cluster_task"
            )
        sv_hosts = getattr(FLAGS, "serve_hosts", "") or ""
        if not sv_hosts:
            raise ValueError(
                "--job_name=serve needs --serve_hosts (host:port this "
                "replica binds)"
            )
        sv_entries = parse_hostports(sv_hosts, "--serve_hosts")
        my_host, my_port = sv_entries[
            min(FLAGS.task_index, len(sv_entries) - 1)
        ]
        listen_all = _resolve_listen_all(FLAGS, my_host, "--serve_hosts")
        rc = _supervised_reexec(FLAGS, child_env_flag="DTX_SERVE_SUPERVISED")
        if rc is not None:
            if rc != 0:
                raise SystemExit(rc)
            return None
        for sh, sp in primary_addrs:
            if not _probe_ps(sh, sp, 120.0):
                raise ConnectionError(
                    f"no PS service at {sh}:{sp} after 120 s (the serve "
                    "replica pulls its params from there)"
                )
        # Registry pin mode (r19): --registry_dir + --serve_model_version
        # serve an immutable registry version instead of hot-tracking;
        # the PS legs stay up for membership leases, so rolling deploys
        # ride the same discovery as the elastic pool.
        bound = serve_pkg.host_serve_task(
            registry_dir=getattr(FLAGS, "registry_dir", "") or None,
            model_version=(
                int(getattr(FLAGS, "serve_model_version", 0) or 0) or None
            ),
            init_fn=init_fn,
            predict_fn=predict_fn,
            # Full replica-major list (r15): the replica's PS legs get the
            # same failover the training clients have, and its refresher
            # follows committed layout epochs from the same topology.
            ps_addrs=shard_addrs,
            ps_replicas=n_replicas,
            layout_version=layout_version,
            port=int(my_port),
            loopback_only=not listen_all,
            max_batch=int(getattr(FLAGS, "serve_max_batch", 32)),
            max_wait_ms=float(getattr(FLAGS, "serve_max_wait_ms", 5.0)),
            queue_depth=int(getattr(FLAGS, "serve_queue_depth", 128)),
            queue_deadline_ms=float(
                getattr(FLAGS, "serve_queue_deadline_ms", 0.0)
            ),
            refresh_ms=float(getattr(FLAGS, "serve_refresh_ms", 50.0)),
            membership=bool(getattr(FLAGS, "membership_leases", True)),
            lease_ttl_s=float(getattr(FLAGS, "lease_ttl_s", 10.0) or 10.0),
            advertise_addr=f"{my_host}:{my_port}",
            metrics_dir=(
                os.path.join(FLAGS.log_dir, f"serve{FLAGS.task_index}")
                if getattr(FLAGS, "log_dir", None)
                else None
            ),
            # r20: the replica serves ITS tenant's model namespace (PS
            # params + registry pins + lease all tenant-scoped) while the
            # quota table admission-controls every tenant that dials it.
            tenant=getattr(FLAGS, "tenant", "default") or "default",
            tenant_quotas=_tenant_quotas(FLAGS),
        )
        print(f"SERVE_DONE port={bound}")
        return None

    acfg = _ps_cfg(FLAGS, mode, n_workers)
    if acfg.fixed_interleave:
        # Real processes free-run — there is no scheduler to fix their
        # interleaving, so --deterministic must not silently promise a
        # reproducible trajectory here (it still pins seeds/precision).
        log.warning(
            "--deterministic: the fixed async interleave applies only to "
            "the single-process thread emulation; cross-process cluster "
            "ordering remains arrival-order nondeterministic."
        )
        acfg = dataclasses.replace(acfg, fixed_interleave=False)
    chief_hosts_service = FLAGS.ps_tasks == 0

    if job == "ps":
        if chief_hosts_service:
            raise ValueError(
                "--job_name=ps contradicts --ps_tasks=0 (chief hosts the "
                "service); launch without the PS task or drop --ps_tasks=0"
            )
        from ..parallel.membership import coordinator_addrs as _coord_addrs

        reshard_spec = getattr(FLAGS, "ps_reshard_to", "") or ""
        if reshard_spec:
            # Live-reshard JOINER (r15): this task serves shard
            # --task_index of the TARGET topology named by
            # --ps_reshard_to, assembling its slice from the OLD topology
            # (--ps_hosts / --ps_shards / --ps_layout_version) before it
            # carries data.  See RUNBOOK "Live resharding".
            from ..utils.flags import parse_reshard_to

            new_version, new_entries = parse_reshard_to(reshard_spec)
            if new_version <= layout_version:
                raise ValueError(
                    f"--ps_reshard_to epoch {new_version} must exceed the "
                    f"old --ps_layout_version {layout_version}"
                )
            tid = FLAGS.task_index
            if tid >= len(new_entries):
                raise ValueError(
                    f"--task_index={tid} exceeds the {len(new_entries)}-"
                    "entry --ps_reshard_to topology"
                )
            my_host, my_port = new_entries[tid]
            listen_all = _resolve_listen_all(
                FLAGS, my_host, "--ps_reshard_to"
            )
            rc = _supervised_reexec(FLAGS, child_env_flag="DTX_PS_SUPERVISED")
            if rc is not None:
                if rc != 0:
                    raise SystemExit(rc)
                return None
            bound = async_ps.host_ps_task(
                int(my_port), loopback_only=not listen_all,
                shard_id=tid, shard_count=len(new_entries),
                layout_version=new_version,
                coordinator_addrs=[new_entries[0]],
                lease_ttl_s=float(getattr(FLAGS, "lease_ttl_s", 10.0) or 10.0),
                reshard_from={
                    "addrs": shard_addrs,
                    "shards": n_shards,
                    "replicas": n_replicas,
                    "version": layout_version,
                    "new_addrs": new_entries,
                },
            )
            print(f"PS_DONE port={bound}")
            return None
        tid = min(FLAGS.task_index, len(entries) - 1)
        my_host, my_port = entries[tid]
        listen_all = _resolve_listen_all(FLAGS, my_host)
        # Host in a supervised CHILD (--ps_restarts): a PS crash (injected
        # or organic) is healed by a fresh incarnation on the same port,
        # which the chief/worker clients reconnect into — partial recovery
        # instead of whole-job crash-restart.  With sharding, ONE shard's
        # crash is healed this way while the other shards serve on.
        rc = _supervised_reexec(FLAGS, child_env_flag="DTX_PS_SUPERVISED")
        if rc is not None:
            if rc != 0:
                raise SystemExit(rc)
            return None
        if tid >= n_shards * n_replicas:
            # Launch-script parity: extra PS tasks beyond the shard/replica
            # grid are accepted but own no slice — host an
            # unsharded-identity service nothing will dial.
            log.warning(
                "PS task %d exceeds --ps_shards=%d x --ps_replicas=%d: no "
                "shard assigned (idle; shrink --ps_hosts or raise "
                "--ps_shards)", tid, n_shards, n_replicas,
            )
            bound = async_ps.host_ps_task(
                int(my_port), loopback_only=not listen_all
            )
        else:
            # Task i serves shard i % shards, replica i // shards — the
            # inverse of ps_shard.replica_major's addrs[r*shards + s]
            # grouping (the ONE replica-major definition).  Its PEER is
            # the other replica of the same shard; a restart catches up
            # from it (REPL_SYNC) before serving — the primary waits only
            # briefly (its peer may be waiting on US at a cold start),
            # the backup generously (its primary is booting too).
            s_id, r_id = tid % n_shards, tid // n_shards
            peer = None
            peer_role = ""
            sync_wait_s = 0.0
            if n_replicas == 2:
                from ..parallel.ps_shard import replica_major

                pair = replica_major(
                    list(range(n_shards * n_replicas)), n_shards, n_replicas
                )[s_id]
                peer_tid = pair[(r_id + 1) % 2]
                peer = entries[peer_tid]
                peer_role = f"ps{peer_tid}"
                sync_wait_s = 2.0 if r_id == 0 else 45.0
            bound = async_ps.host_ps_task(
                int(my_port), loopback_only=not listen_all,
                shard_id=s_id, shard_count=n_shards,
                layout_version=layout_version, peer=peer,
                peer_role=peer_role, sync_wait_s=sync_wait_s,
                # The coordinator's registry backs the idle-pair self-exit
                # (RUNBOOK 4e) and the drain/epoch reads.
                coordinator_addrs=_coord_addrs(
                    entries, n_shards, n_replicas
                ),
            )
        print(f"PS_DONE port={bound}")
        return None

    if job == "chief":
        faults.arm_process_faults()
        compile_cache.enable()
        params = init_fn(jax.random.key(FLAGS.seed))
        if isinstance(params, tuple):
            params, model_state = params
        if not chief_hosts_service:
            for sh, sp in shard_addrs:
                if not _probe_ps(sh, sp, 120.0):
                    raise ConnectionError(
                        f"no PS task answered at {sh}:{sp} after 120 s "
                        "(launch every --job_name=ps shard process first, "
                        "or pass --ps_tasks=0 to host the service in the "
                        "chief)"
                    )
        log.info(
            "PS cluster chief: mode=%s %d workers, %d shard(s) x %d "
            "replica(s) at %s (%s)",
            mode, n_workers, n_shards, n_replicas,
            ",".join(f"{h}:{p}" for h, p in shard_addrs),
            "hosted in-process" if chief_hosts_service else "external PS tasks",
        )
        # Scrapable platform record: whether the chief genuinely ran on the
        # chip (not a silent CPU fallback).
        print(f"CHIEF_PLATFORM={jax.devices()[0].platform}", flush=True)
        trainer = async_ps.RemotePSChief(
            acfg, loss_fn, optimizer, params,
            model_state=model_state,
            rng=jax.random.key(FLAGS.seed),
            ps_replicas=n_replicas,
            layout_version=layout_version,
            **(
                # Chief-hosted service (one in-process server per shard
                # replica): same explicit-exposure contract as the
                # dedicated PS task (code-review r5), checked per host.
                {
                    "ports": [p for _, p in shard_addrs],
                    "listen_all": any(
                        _resolve_listen_all(FLAGS, h) for h, _ in shard_addrs
                    ),
                }
                if chief_hosts_service
                else {"ps_addrs": shard_addrs}
            ),
        )
        t0 = time.perf_counter()
        final_params = trainer.run_chief()
        dt = time.perf_counter() - t0
        metrics = eval_fn(final_params) if eval_fn is not None else {}
        # Same examples_per_sec_per_chip DEFINITION as the thread-emulation
        # path: divide by the chief's device count (ADVICE r4 — one scrapable
        # field name must not carry two definitions across the PS modes), and
        # count all replicas_to_aggregate worker batches per sync step
        # (ADVICE r5).
        sps = trainer.global_step / dt if dt > 0 else 0.0
        r2a = (
            (acfg.replicas_to_aggregate or n_workers)
            if mode == "sync_replicas"
            else 1
        )
        _print_final(
            step=trainer.global_step, dt=dt, local_bs=local_bs,
            mode=f"{mode}_cluster", metrics=metrics,
            eps_per_chip=sps * local_bs * r2a / max(1, len(jax.devices())),
            extra={"workers": n_workers, "stale_dropped": trainer.total_dropped},
        )
        return final_params

    # job == "worker"
    faults.arm_process_faults()
    wid = FLAGS.task_index
    for sh, sp in shard_addrs:
        if not _probe_ps(sh, sp, 120.0):
            raise ConnectionError(f"no PS service at {sh}:{sp} after 120 s")

    def struct_init(rng):
        p = init_fn(rng)
        return p[0] if isinstance(p, tuple) else p

    n = async_ps.remote_worker_loop(
        host, port, wid,
        cfg=acfg,
        loss_fn=loss_fn,
        init_fn=struct_init,
        batches=iter(batches_for_worker(wid, local_bs, n_workers)),
        model_state=model_state,
        rng=jax.random.key(FLAGS.seed),
        addrs=shard_addrs,
        ps_replicas=n_replicas,
        layout_version=layout_version,
        # Per-shard pull/push wall-time scalars (shard-imbalance signal).
        metrics_dir=(
            os.path.join(FLAGS.log_dir, f"worker{wid}") if FLAGS.log_dir else None
        ),
        metrics_every=max(1, getattr(FLAGS, "log_every_steps", 20) or 20),
    )
    print(f"WORKER_DONE task={wid} contributed={n}")
    return None


def array_eval_fn(apply_logits: Callable, test: dict[str, np.ndarray], batch_size: int):
    """Standard accuracy eval over array test splits for the FINAL line."""
    import jax

    from ..models import layers

    @jax.jit
    def _acc(p, b):
        return layers.accuracy(apply_logits(p, b), b["label"])

    def eval_fn(params):
        n = len(test["label"])
        ebs = min(batch_size, n)
        accs = [
            float(_acc(params, {k: v[i : i + ebs] for k, v in test.items()}))
            for i in range(0, (n // ebs) * ebs, ebs)
        ]
        return {"test_accuracy": float(np.mean(accs))}

    return eval_fn
