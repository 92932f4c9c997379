"""Experiment runner: flags -> mesh -> sharded state -> session, shared by
every example CLI (SURVEY.md section 7: "one small framework, five thin
example CLIs on top" — inverting the reference's copy-per-script structure).

Wraps the full L0-L3 wiring that each reference script re-implements by hand:
mesh build, distributed bootstrap, sharded-state init, jitted step build,
hook stack (stop/steps-per-sec/logging/summary/checkpoint/profiler), infeed,
and the managed run loop.
"""

from __future__ import annotations

import logging
import os
from typing import Any, Callable, Iterable

import jax
import optax
from jax.sharding import Mesh, PartitionSpec

from ..data import pipeline as pipeline_lib
from ..parallel import MeshSpec, build_mesh, dist
from ..utils import compile_cache, telemetry
from ..utils.metrics import MetricsWriter
from . import hooks as hooks_lib
from .checkpoint import CheckpointManager
from .loop import TrainSession
from .state import create_sharded_state
from .step import build_eval_step, build_train_step

log = logging.getLogger("dtx.runner")


class Experiment:
    """One configured training run.

    Args mirror what every reference script assembles around its model:
    ``init_fn(rng) -> params | (params, model_state)``, the framework-standard
    ``loss_fn``, an optax optimizer, and sharding rules.
    """

    def __init__(
        self,
        *,
        init_fn: Callable,
        loss_fn: Callable | None,
        optimizer: optax.GradientTransformation,
        rules=(),
        flags,
        mesh: Mesh | None = None,
        extra_hooks: Iterable[hooks_lib.Hook] = (),
        loss_fn_factory: Callable | None = None,
        batch_spec: PartitionSpec | None = None,
    ):
        self.flags = flags
        if getattr(flags, "deterministic", False):
            from ..utils import determinism

            determinism.enable()
        cluster = dist.initialize()
        if cluster.is_ps_task:
            # TF_CONFIG launchers may still start ps/evaluator processes;
            # they hold no SPMD seat — exiting here prevents a duplicate
            # training job from corrupting the real workers' log_dir.
            print(
                f"TF_CONFIG task type {cluster.task_type!r}: parameter "
                "servers are not needed on TPU; exiting 0."
            )
            raise SystemExit(0)
        compile_cache.enable()
        telemetry.count_compiles()
        if getattr(flags, "watchdog", True):
            # Multi-process fail-fast (no-op single-process): a dead peer
            # must crash the job promptly so the per-task supervisor can
            # restart it — see utils.supervisor for the recovery story.
            dist.start_watchdog(grace_s=getattr(flags, "watchdog_grace_secs", 10.0))
        self.mesh = mesh if mesh is not None else build_mesh(MeshSpec.parse(flags.mesh))
        log.info("mesh: %s over %d devices", dict(self.mesh.shape), self.mesh.size)
        if loss_fn is None:
            # Mesh-dependent losses (ring attention needs the mesh object).
            if loss_fn_factory is None:
                raise ValueError("pass loss_fn or loss_fn_factory")
            loss_fn = loss_fn_factory(self.mesh)
        self.batch_spec = batch_spec
        self.optimizer = optimizer
        self.state, self.shardings = create_sharded_state(
            init_fn,
            optimizer,
            jax.random.key(flags.seed),
            mesh=self.mesh,
            rules=rules,
            zero_opt_sharding=getattr(flags, "zero_opt", False),
        )
        self.step_fn = build_train_step(
            loss_fn,
            optimizer,
            mesh=self.mesh,
            state_shardings=self.shardings,
            unroll=flags.unroll,
            batch_spec=batch_spec,
            grad_accum=getattr(flags, "grad_accum", 1),
        )
        self._loss_fn = loss_fn
        self.log_dir = flags.log_dir or None
        self.writer = MetricsWriter(self.log_dir if dist.is_chief() else None)
        self.ckpt = None
        if self.log_dir:
            self.ckpt = CheckpointManager(
                os.path.join(self.log_dir, "ckpt"), save_interval_steps=1
            )
        self.hooks = [
            hooks_lib.StopAtStepHook(flags.train_steps),
            hooks_lib.StepCounterHook(
                every_steps=flags.log_every_steps, batch_size=flags.batch_size
            ),
            hooks_lib.LoggingHook(every_steps=flags.log_every_steps),
            hooks_lib.SummaryHook(self.writer, every_steps=flags.log_every_steps),
        ]
        if self.ckpt is not None:
            self.hooks.append(
                hooks_lib.CheckpointHook(
                    self.ckpt, every_steps=flags.checkpoint_every_steps
                )
            )
            # Preemption (SIGTERM) -> final checkpoint + clean stop; resume
            # is the ordinary auto-restore (SURVEY.md section 5.3).
            from .preemption import PreemptionCheckpointHook

            self.hooks.append(PreemptionCheckpointHook(self.ckpt))
        if getattr(flags, "profile", False) and self.log_dir:
            self.hooks.append(hooks_lib.ProfilerHook(self.log_dir))
        self.hooks.extend(extra_hooks)
        self.session = TrainSession(
            self.step_fn,
            self.state,
            hooks=self.hooks,
            checkpoint_manager=self.ckpt,
            steps_per_call=flags.unroll,
        )

    def batches(self, local_iter, *, unrolled: bool = True):
        """Wrap a per-host local-batch iterator into prefetched global device
        batches (stacking for unroll when configured)."""
        spec = self.batch_spec
        it = local_iter if hasattr(local_iter, "__next__") else iter(local_iter)
        if unrolled and self.flags.unroll > 1:
            it = pipeline_lib.stack_for_unroll(it, self.flags.unroll)
            base = spec if spec is not None else PartitionSpec("data")
            spec = PartitionSpec(None, *base)
        return pipeline_lib.prefetch_to_mesh(it, self.mesh, spec=spec)

    def run(self, local_iter) -> Any:
        """Managed run over the given local-batch iterator; returns final state."""
        final = self.session.run(self.batches(local_iter))
        self.state = final
        return final

    def evaluate(
        self,
        arrays: dict,
        *,
        eval_fn: Callable | None = None,
        batch_size: int | None = None,
    ) -> dict[str, float]:
        """Sharded full-split eval; averages metrics over complete batches."""
        if eval_fn is None:
            _loss = self._loss_fn

            def eval_fn(params, mstate, batch):
                return _loss(params, mstate, batch, jax.random.key(0))[1][1]

        step = build_eval_step(
            eval_fn,
            mesh=self.mesh,
            state_shardings=self.shardings,
            batch_spec=self.batch_spec,
        )
        n = len(next(iter(arrays.values())))
        dp = self.mesh.shape.get("data", 1)
        ebs = min(batch_size or self.flags.batch_size, n // dp * dp)
        # Round down to a multiple of the data-axis size: a --batch_size not
        # divisible by dp (e.g. 100 on an 8-way mesh) must not crash eval
        # after training completed.
        ebs = (ebs // dp) * dp
        if ebs <= 0:
            return {}
        sums: dict[str, float] = {}
        count = 0
        for i in range(0, (n // ebs) * ebs, ebs):
            b = {k: v[i : i + ebs] for k, v in arrays.items()}
            m = step(
                self.state,
                pipeline_lib.as_global(b, self.mesh, spec=self.batch_spec),
            )
            for k, v in m.items():
                sums[k] = sums.get(k, 0.0) + float(v)
            count += 1
        return {k: v / count for k, v in sums.items()}

    def finish(self, **final_metrics) -> None:
        """Print the FINAL line (the contract tests/bench scrape) and close."""
        parts = [f"FINAL step={self.session.step}"]
        # Always present (0.0 when the run was shorter than the counter
        # cadence) — scrapers key on these fields.
        sps = self.session.records.get("steps_per_sec") or 0.0
        parts.append(f"steps_per_sec={sps:.1f}")
        eps = self.session.records.get("examples_per_sec_per_chip") or 0.0
        parts.append(f"examples_per_sec_per_chip={eps:.0f}")
        for k, v in final_metrics.items():
            parts.append(f"{k}={v:.4f}" if isinstance(v, float) else f"{k}={v}")
        print(" ".join(parts))
        self.writer.close()
        if self.ckpt is not None:
            self.ckpt.close()
        # Announce clean departure: peers' watchdogs must not read this
        # process's end-of-job silence as a crash (finish-time skew between
        # workers can exceed the heartbeat grace).
        dist.stop_watchdog()
