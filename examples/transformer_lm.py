"""Transformer LM: the framework's growth-path example (no reference analog).

The five reference workloads predate attention (SURVEY.md section 5.7); this
CLI exists to exercise what the reference never could — the long-context and
model-parallel axes of the framework:

- ``--mesh "data=2,seq=2,model=2"``: data x sequence x tensor(Megatron)
  parallelism in one run — ring attention by default, or
  ``--attention=ulysses`` for all-to-all CP (r4),
- ``--attention flash``: the Pallas flash kernel (O(block) VMEM — sequence
  length bounded by HBM, not by the [T, T] score matrix),
- the same TrainSession/hooks/checkpoint/preemption machinery as the five
  parity examples.

Run: python examples/transformer_lm.py --batch_size=8 --seq_len=512 \
         --train_steps=500 --attention=flash
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

define_training_flags(default_batch_size=8, default_steps=500)
define_legacy_cluster_flags()
flags.DEFINE_integer("vocab_size", 8192, "Vocabulary size.")
flags.DEFINE_integer("dim", 256, "Model width.")
flags.DEFINE_integer("n_layers", 4, "Decoder blocks.")
flags.DEFINE_integer("n_heads", 8, "Attention heads.")
flags.DEFINE_integer("seq_len", 512, "Sequence length.")
flags.DEFINE_enum(
    "attention", "auto", ["auto", "xla", "flash", "ulysses"],
    "Attention impl: auto/xla/flash select the per-chip kernel (and the "
    "ring impl under a seq-sharded mesh); ulysses = all-to-all CP instead "
    "of the ring (local heads per TP shard must be a multiple of the seq "
    "shard count).",
)
flags.DEFINE_float("clip_norm", 1.0, "Global-norm gradient clip.")
flags.DEFINE_bool(
    "remat", False, "Rematerialise blocks in backward (fits bigger batches)."
)
flags.DEFINE_integer(
    "loss_chunks",
    0,
    ">1 chunks the LM head + cross-entropy over the sequence (the [B,T,V] "
    "logits never materialise — fits bigger batches/longer context; "
    "identical numerics).  Requires seq_len %% loss_chunks == 0.",
)
flags.DEFINE_integer(
    "sample_tokens",
    0,
    ">0: after training, greedy-decode this many tokens from a corpus "
    "prompt via the KV-cache inference path and log the token ids.",
)
flags.DEFINE_integer(
    "pipeline_stages",
    1,
    ">1 runs the block stack under the GPipe schedule over the mesh 'pipe' "
    'axis (pass a matching --mesh, e.g. "data=2,pipe=4"); must divide '
    "--n_layers.",
)
flags.DEFINE_integer("microbatches", 4, "GPipe microbatches per step.")
flags.DEFINE_integer(
    "moe_experts",
    0,
    ">0 swaps every block's MLP for a mixture-of-experts FFN sharded over "
    'the mesh "expert" axis (pass e.g. --mesh "data=2,expert=4"); '
    "top-2 routing, Switch aux loss.",
)
flags.DEFINE_float("moe_capacity_factor", 1.25, "Expert capacity factor.")
flags.DEFINE_integer(
    "moe_group_size",
    1024,
    "GShard routing-group size G (dispatch FLOPs/token ~ G; capacity is "
    "per-group) — the dispatch-share knob (Config.moe_group_size).",
)

FLAGS = flags.FLAGS


def _cfg_from_flags():
    return models.transformer.Config(
        vocab_size=FLAGS.vocab_size,
        dim=FLAGS.dim,
        n_layers=FLAGS.n_layers,
        n_heads=FLAGS.n_heads,
        max_seq_len=FLAGS.seq_len,
        attention=FLAGS.attention,
        pipeline_stages=FLAGS.pipeline_stages,
        microbatches=FLAGS.microbatches,
        moe_experts=FLAGS.moe_experts,
        moe_capacity_factor=FLAGS.moe_capacity_factor,
        moe_group_size=FLAGS.moe_group_size,
        remat=FLAGS.remat,
        loss_chunks=FLAGS.loss_chunks,
    )


def _serve_task(cfg):
    """``--job_name=serve`` (r19): host one registry-PINNED transformer
    replica — stepped KV-cache decode through the sequence-slot batcher
    (streamed tokens over DECODE_OPEN/NEXT/CLOSE) plus the row-wise
    logits predict path.  Registry-only: no PS cluster needed — publish
    a trained version with ``--registry_dir`` first, then::

        python examples/transformer_lm.py --job_name=serve \
            --registry_dir=/models --serve_model_version=1 \
            --serve_hosts=127.0.0.1:7200
    """
    from distributed_tensorflow_examples_tpu import serve as serve_pkg
    from distributed_tensorflow_examples_tpu.utils.flags import parse_hostports

    if not FLAGS.registry_dir or not FLAGS.serve_model_version:
        raise app.UsageError(
            "--job_name=serve needs --registry_dir and "
            "--serve_model_version (the transformer serves pinned "
            "registry versions; it has no PS run to hot-track)"
        )
    port = 0
    if FLAGS.serve_hosts:
        entries = parse_hostports(FLAGS.serve_hosts, "--serve_hosts")
        port = entries[min(FLAGS.task_index, len(entries) - 1)][1]
    serve_pkg.host_serve_task(
        init_fn=lambda rng: models.transformer.init(cfg, rng),
        predict_fn=lambda p, b: models.transformer.apply(cfg, p, b["x"]),
        decode_fns=models.transformer.serve_decode_fns(cfg),
        decode_max_len=FLAGS.seq_len,
        ps_addrs=[],
        membership=False,
        port=port,
        registry_dir=FLAGS.registry_dir,
        model_name="transformer_lm",
        model_version=FLAGS.serve_model_version,
    )


def _publish_to_registry(cfg, exp):
    """Publish the trained params as a NEW immutable registry version
    (the deployable artifact a pinned serve replica loads)."""
    import jax
    import numpy as np

    from distributed_tensorflow_examples_tpu.serve.registry import (
        ModelRegistry,
    )
    from distributed_tensorflow_examples_tpu.train.checkpoint import (
        flat_params_of,
    )

    if jax.process_count() > 1:
        logging.warning(
            "--registry_dir publish skipped on multi-host runs; restore "
            "the checkpoint single-host and publish there."
        )
        return
    params = exp.state.params
    if cfg.pipeline_stages > 1:
        # Registry snapshots use the SERVING layout (per-layer block_i
        # keys): a pinned replica decodes with the stages collapsed.
        _dcfg, params = models.transformer.collapse_pipeline(
            cfg, jax.device_get(params)
        )
    version = ModelRegistry(FLAGS.registry_dir).publish(
        "transformer_lm",
        flat_params_of(params),
        step=int(np.asarray(jax.device_get(exp.state.step))),
        source=f"transformer_lm seed={FLAGS.seed}",
    )
    logging.info(
        "registry: published transformer_lm/v%d under %s "
        "(serve it: --job_name=serve --serve_model_version=%d)",
        version, FLAGS.registry_dir, version,
    )


def main(argv):
    del argv
    logging.basicConfig(level=logging.INFO, format="%(message)s")
    import jax
    import optax

    info = resolve_legacy_cluster(FLAGS)
    if info["is_legacy_ps_process"]:
        print("job_name=ps: parameter servers are not needed on TPU; exiting 0.")
        return
    if getattr(FLAGS, "job_name", "") == "serve":
        _serve_task(_cfg_from_flags())
        return
    prompt_len = 16
    sampling = FLAGS.sample_tokens > 0
    if sampling and prompt_len + FLAGS.sample_tokens > FLAGS.seq_len:
        # Validate BEFORE training: generate() would raise after the whole
        # run completed and lose the FINAL line.
        raise app.UsageError(
            f"--sample_tokens={FLAGS.sample_tokens} + {prompt_len} prompt "
            f"tokens exceeds --seq_len={FLAGS.seq_len}"
        )

    ids, vocab, source = data.datasets.text_corpus(
        FLAGS.data_dir,
        vocab_size=FLAGS.vocab_size,
        synth_tokens=max(2_000_000, FLAGS.batch_size * (FLAGS.seq_len + 1) * 50),
        seed=FLAGS.seed,
    )
    logging.info("corpus source: %s (%d tokens)", source, len(ids))

    cfg = _cfg_from_flags()
    exp = train.Experiment(
        init_fn=lambda rng: models.transformer.init(cfg, rng),
        loss_fn=None,  # set after mesh exists (ring attention needs it)
        optimizer=optax.chain(
            optax.clip_by_global_norm(FLAGS.clip_norm),
            optax.adamw(FLAGS.learning_rate),
        ),
        rules=models.transformer.sharding_rules(cfg),
        flags=FLAGS,
        loss_fn_factory=lambda mesh: models.transformer.loss_fn(cfg, mesh=mesh),
        batch_spec=models.transformer.batch_spec(cfg),
    )

    # Per-host data shard: each host owns a disjoint block of the token
    # stream and a disjoint block of batch rows (the Dataset.shard analog).
    n_hosts = jax.process_count()
    if FLAGS.batch_size % n_hosts:
        raise ValueError(
            f"--batch_size={FLAGS.batch_size} not divisible by {n_hosts} hosts"
        )
    local_rows = FLAGS.batch_size // n_hosts
    block = len(ids) // n_hosts
    local_ids = ids[jax.process_index() * block : (jax.process_index() + 1) * block]
    it = data.datasets.lm_batches(
        local_ids, batch_size=local_rows, seq_len=FLAGS.seq_len
    )
    exp.run(it)

    if sampling:
        # Inference surface: KV-cache greedy decode from a corpus prompt.
        import numpy as np

        if cfg.pipeline_stages > 1:
            if jax.process_count() > 1:
                # Sharded params spanning hosts are not fully addressable —
                # device_get would raise AFTER the whole training run and
                # lose the FINAL line.  Collapse-serving is a single-host
                # demo surface; multi-host serving re-shards a restored
                # checkpoint instead.
                logging.warning(
                    "--sample_tokens skipped on multi-host pipelined runs; "
                    "restore the checkpoint single-host and sample there."
                )
                dcfg = None
            else:
                # Pipeline-trained weights serve through the COLLAPSED
                # layout (a pipelined decode would bubble O(stages) per
                # token at T=1); sampling is a demo surface, so decode
                # replicated on host-fetched weights rather than
                # re-sharding.
                dcfg, dparams = models.transformer.collapse_pipeline(
                    cfg, jax.device_get(exp.state.params)
                )
                dmesh = None
        else:
            dcfg, dparams, dmesh = cfg, exp.state.params, exp.mesh
        if dcfg is not None:
            # Batch dim must cover the batch shards — ('data','expert')
            # for MoE; decode runs sharded on the same mesh the model
            # trained on (KV cache heads on 'model', expert FFNs on their
            # ranks).
            dp = 1
            if dmesh is not None:
                dp = dmesh.shape.get("data", 1) * dmesh.shape.get("expert", 1)
            prompt = np.tile(
                np.asarray(ids[:prompt_len], dtype=np.int32)[None], (dp, 1)
            )
            out = models.transformer.generate(
                dcfg, dparams, prompt, max_new_tokens=FLAGS.sample_tokens,
                mesh=dmesh,
            )
            logging.info(
                "sampled token ids: %s",
                np.asarray(out)[0, prompt_len:].tolist(),
            )
    if FLAGS.registry_dir:
        _publish_to_registry(cfg, exp)
    m = exp.session.last_metrics
    exp.finish(final_perplexity=float(m.get("perplexity", 0.0)))


if __name__ == "__main__":
    app.run(main)
