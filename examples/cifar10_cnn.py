"""W2: CIFAR-10 CNN — the reference's async parameter-server workload.

Reference config (SURVEY.md section 2a W2, BASELINE.json:8): "CIFAR-10 CNN,
async SGD parameter-server" — each worker applies gradients to PS-hosted
variables immediately, no aggregation (call stack: SURVEY.md section 3.2).

TPU-native shape: SPMD is synchronous by construction, so this CLI runs sync
data-parallel by default; ``--sync_replicas=false`` selects the async-PS
*emulation* mode (per-island sync + staleness-bounded cross-island applies —
``parallel.async_ps``; semantics divergence documented there).

Run: python examples/cifar10_cnn.py --batch_size=256 --train_steps=1000
"""

import logging
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from absl import app, flags

from distributed_tensorflow_examples_tpu import data, models, train
from distributed_tensorflow_examples_tpu.utils.flags import (
    define_legacy_cluster_flags,
    define_training_flags,
    resolve_legacy_cluster,
)

define_training_flags(default_batch_size=128, default_steps=1000)
define_legacy_cluster_flags()

FLAGS = flags.FLAGS


def main(argv):
    del argv
    logging.basicConfig(level=logging.INFO, format="%(message)s")
    import optax

    info = resolve_legacy_cluster(FLAGS)
    if info["is_legacy_ps_process"]:
        print("job_name=ps: parameter servers are not needed on TPU; exiting 0.")
        return

    # Out-of-core: shard-*.dtxr chunks stream through the NATIVE C++ loader,
    # shard-*.npz through the Python pipeline, else in-RAM (SURVEY.md T7);
    # source selection + eval-shard holdout shared in data.streams.
    src = data.streams.resolve_image_source(
        FLAGS.data_dir,
        fallback=lambda: data.datasets.cifar10(FLAGS.data_dir, seed=FLAGS.seed),
        seed=FLAGS.seed,
        num_classes=10,
        name="cifar10",
        tenant=getattr(FLAGS, "tenant", "default") or "default",
    )
    ds = src.ds

    def worker_stream(w, bs, n_workers):
        """Per-emulated-worker data shard (worker w plays host w)."""
        return data.streams.train_iter(
            src, batch_size=bs, seed=FLAGS.seed, worker=w,
            n_workers=n_workers,
            tenant=getattr(FLAGS, "tenant", "default") or "default",
        )

    cfg = models.cnn.Config()
    if not FLAGS.sync_replicas or FLAGS.ps_emulation:
        # W2's true shape: async SGD, each (emulated) worker applying grads
        # immediately to the host-hosted variables, coordinated by the native
        # accumulator/token service; --ps_emulation keeps the token-gated
        # sync mode available here too (parallel.async_ps has the semantics).
        import optax as _optax

        mode = "sync_replicas" if FLAGS.sync_replicas else "async"
        # Short LR warmup (default 20 applies): the first async applies
        # land on stale params at full magnitude; a linear ramp keeps them
        # from collapsing the relu stack onto the uniform plateau.  The
        # stack is stable at lr 0.01 (the e2e gate's flag; 200 applies
        # reach accuracy 0.67-0.98 over seeds 0-2) and on the edge at 0.05.
        warmup = FLAGS.warmup_steps if FLAGS.warmup_steps > 0 else 20
        lr = _optax.linear_schedule(
            FLAGS.learning_rate / 10.0, FLAGS.learning_rate, warmup
        )
        train.run_ps_emulation(
            init_fn=lambda rng: models.cnn.init(cfg, rng),
            loss_fn=models.cnn.loss_fn(cfg),
            optimizer=_optax.sgd(lr),
            batches_for_worker=worker_stream,
            FLAGS=FLAGS,
            mode=mode,
            eval_fn=train.array_eval_fn(
                lambda p, b: models.cnn.apply(cfg, p, b["image"]),
                ds.test,
                FLAGS.batch_size,
            ),
            # Row-wise inference apply for --job_name=serve replicas (r10).
            predict_fn=lambda p, b: models.cnn.apply(cfg, p, b["image"]),
        )
        return

    exp = train.Experiment(
        init_fn=lambda rng: models.cnn.init(cfg, rng),
        loss_fn=models.cnn.loss_fn(cfg),
        optimizer=optax.sgd(FLAGS.learning_rate),
        rules=models.cnn.SHARDING_RULES,
        flags=FLAGS,
    )
    exp.run(
        data.streams.train_iter(
            src, batch_size=FLAGS.batch_size, seed=FLAGS.seed,
            tenant=getattr(FLAGS, "tenant", "default") or "default",
        )
    )
    metrics = exp.evaluate(ds.test)
    exp.finish(test_accuracy=metrics.get("accuracy", 0.0))


if __name__ == "__main__":
    app.run(main)
