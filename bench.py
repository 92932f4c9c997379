"""Benchmark driver: prints ONE JSON line with the headline metric.

Headline (BASELINE.md): images/sec/chip on the flagship workload (ResNet-50).
Runs on a TPU only: on any other platform it exits non-zero and prints no
metric line — a CPU number must never appear under a device metric's name.

Run: ``python bench.py [--model resnet50|mlp] [--steps 30] [--batch-per-chip N]``
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def _require_tpu() -> dict:
    """The device this run measures, as JAX reports it; exits non-zero
    (no metric line) when the platform is not ``tpu``."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(
            f"bench.py: platform is {devs[0].platform!r}, not 'tpu' — "
            "no measurement (a host number is not a device metric)"
        )
    return {
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "device_count": len(devs),
    }


def _bench_step_loop(step_fn, state, batch, *, steps: int, warmup: int):
    """Time the compiled step over an on-device batch.

    The batch is reused so the number measures the step, not host->device
    transfer.  Timing is closed by a host fetch of the loss scalar, which
    cannot complete before the device has.  Two windows are timed and the
    faster wins.
    """
    for _ in range(warmup):
        state, metrics = step_fn(state, batch)
    float(metrics["loss"])
    best = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        for _ in range(steps):
            state, metrics = step_fn(state, batch)
        float(metrics["loss"])
        best = min(best, time.perf_counter() - t0)
    return best


#: Published per-chip peaks keyed by ``device_kind``, each with its source.
#: A device that is not in the table is an error, not a default.
_PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM.
    "TPU v5 lite": {"bf16_tflops": 197.0, "hbm_gbps": 819.0},
}


def _peak_tflops(device_kind: str) -> float:
    if device_kind not in _PEAKS:
        raise ValueError(
            f"no published peak recorded for device_kind {device_kind!r} "
            f"(known: {sorted(_PEAKS)}); add it to bench._PEAKS with its "
            "source"
        )
    return _PEAKS[device_kind]["bf16_tflops"]


def _step_flops(compiled) -> float:
    """Per-step PER-DEVICE FLOPs from XLA's cost analysis of the compiled
    step (the SPMD module is per-device, so this is already FLOPs/chip —
    verified: a 4-way sharded program reports 1/4 the unsharded count)."""
    return float(compiled.cost_analysis()["flops"])


def _bench(
    name,
    model_mod,
    cfg,
    optimizer,
    make_batch,
    *,
    steps,
    batch_per_chip,
    warmup,
    loss_fn_factory=None,
    init_fn_factory=None,
    unit_per_example=1,
):
    """``unit_per_example``: how many headline units one batch row carries
    (1 image for the conv nets, seq_len tokens for the LMs).  The factories
    receive ``(mesh, global_batch)`` — mesh-dependent losses (ring
    attention) and batch-shaped state (the LSTM carry) hook in there.
    """
    import jax
    import numpy as np

    from distributed_tensorflow_examples_tpu import data, parallel, train

    mesh = parallel.build_mesh(parallel.MeshSpec())
    n_chips = mesh.size
    global_batch = batch_per_chip * n_chips

    init_fn = (
        init_fn_factory(mesh, global_batch)
        if init_fn_factory
        else (lambda rng: model_mod.init(cfg, rng))
    )
    state, shardings = train.create_sharded_state(
        init_fn,
        optimizer,
        jax.random.key(0),
        mesh=mesh,
        rules=model_mod.SHARDING_RULES,
    )
    step_fn = train.build_train_step(
        loss_fn_factory(mesh, global_batch) if loss_fn_factory else model_mod.loss_fn(cfg),
        optimizer,
        mesh=mesh,
        state_shardings=shardings,
    )
    rng = np.random.default_rng(0)
    batch = data.pipeline.as_global(make_batch(rng, global_batch), mesh)
    # build_train_step returns a jitted fn: AOT-compile ONCE, read XLA's
    # FLOP count from the same executable the timing loop drives.
    step_fn = step_fn.lower(state, batch).compile()
    flops = _step_flops(step_fn)
    dt = _bench_step_loop(step_fn, state, batch, steps=steps, warmup=warmup)
    images_per_sec = steps * global_batch * unit_per_example / dt
    achieved = flops * (steps / dt) / 1e12  # TFLOP/s/chip (flops is /chip)
    return {
        "model": name,
        "images_per_sec": images_per_sec,
        "images_per_sec_per_chip": images_per_sec / n_chips,
        "n_chips": n_chips,
        "steps_per_sec": steps / dt,
        "global_batch": global_batch,
        "achieved_tflops_per_chip": achieved,
        "mfu": achieved / _peak_tflops(jax.devices()[0].device_kind),
        "step_gflops_per_chip": flops / 1e9,
    }


def bench_resnet50(steps: int, batch_per_chip: int, image_size: int = 224):
    """Flagship: ResNet-50 fwd+bwd+update images/sec/chip (BASELINE.md)."""
    import optax

    from distributed_tensorflow_examples_tpu import models

    cfg = models.resnet.Config()
    return _bench(
        "resnet50",
        models.resnet,
        cfg,
        optax.sgd(0.1, momentum=0.9),
        lambda rng, n: {
            "image": rng.normal(size=(n, image_size, image_size, 3)).astype("float32"),
            "label": rng.integers(0, 1000, size=(n,)).astype("int32"),
        },
        steps=steps,
        batch_per_chip=batch_per_chip,
        warmup=5,
        # NOTE deliberately NOT mesh-aware: the fused-BN experiments (ops/
        # bn.py) measured SLOWER than XLA's own reduce emitter end-to-end —
        # Pallas stats forced layout-conversion copies (+39 ms/step) and
        # broke conv fusion chains; MXU-matmul stats got algebraically
        # simplified back into the same reduces plus loop overhead.  Full
        # account: BASELINE.md r3 ResNet section.
    )


def bench_transformer(
    steps: int, batch_per_chip: int, seq_len: int = 2048, remat: bool = False,
    loss_chunks: int = 0, n_heads: int = 8, experts: int = 0, top_k: int = 2,
    moe_group_size: int = 1024,
):
    """Transformer LM tokens/sec/chip + MFU (flash attention on TPU).

    ``loss_chunks>1``: the chunked head+CE path — the [B, T, 32k] logits
    never materialise, which lets batch 16 fit in 16 GB without remat; it
    costs ~4%% throughput, so the flagship default stays dense (BASELINE.md
    r3 flagship account).

    ``experts>0``: the SAME flagship dims with GShard MoE FFNs (E experts,
    top-k routing) — one code path so dense-vs-MoE A/Bs can never skew on
    a dropped knob.
    """
    import numpy as np
    import optax

    from distributed_tensorflow_examples_tpu import models

    # n_heads=8 -> head_dim 128: the MXU-native head width (128-wide
    # contraction/output lanes; head_dim 64 runs the attention matmuls at
    # half the MXU issue rate and doubles the per-head softmax VPU area).
    cfg = models.transformer.Config(
        vocab_size=32000, dim=1024, n_layers=12, n_heads=n_heads,
        max_seq_len=seq_len, remat=remat, loss_chunks=loss_chunks,
        moe_experts=experts, moe_top_k=top_k, moe_group_size=moe_group_size,
    )

    def make_batch(rng: np.random.Generator, n: int):
        toks = rng.integers(0, cfg.vocab_size, size=(n, seq_len + 1)).astype("int32")
        return {"x": toks[:, :-1], "y": toks[:, 1:]}

    return _bench(
        "transformer_moe" if experts else "transformer",
        models.transformer,
        cfg,
        optax.adamw(1e-3),
        make_batch,
        steps=steps,
        batch_per_chip=batch_per_chip,
        warmup=3,
        loss_fn_factory=lambda mesh, _: models.transformer.loss_fn(cfg, mesh=mesh),
        unit_per_example=seq_len,  # headline unit = tokens
    )


def bench_moe(steps: int, batch_per_chip: int, **kw):
    """MoE flagship (VERDICT r3 missing #3: the expert-parallel axis needs a
    measured number, not just HLO proofs): ``bench_transformer`` with E=8
    top-2 — ~0.9B params, so the f32 AdamW state caps the single-chip batch
    (default 4; sweep on TPU).  BASELINE.md records the dispatch-einsum
    share of step time against the dense flagship."""
    kw.setdefault("experts", 8)
    return bench_transformer(steps, batch_per_chip, **kw)


def bench_lstm(steps: int, batch_per_chip: int, seq_len: int = 20):
    """W5 PTB LSTM tokens/sec/chip (batch rows x seq_len per step)."""
    import numpy as np
    import optax

    from distributed_tensorflow_examples_tpu import models

    cfg = models.lstm.Config(vocab_size=10000, dim=200, num_layers=2)

    def make_batch(rng: np.random.Generator, n: int):
        toks = rng.integers(0, cfg.vocab_size, size=(n, seq_len + 1)).astype("int32")
        return {"x": toks[:, :-1], "y": toks[:, 1:]}

    return _bench(
        "ptb_lstm",
        models.lstm,
        cfg,
        optax.sgd(1.0),
        make_batch,
        steps=steps,
        batch_per_chip=batch_per_chip,
        warmup=3,
        init_fn_factory=lambda _, gb: (
            lambda rng: models.lstm.init(cfg, rng, batch_size=gb)
        ),
        unit_per_example=seq_len,
    )


def bench_word2vec(steps: int, batch_per_chip: int):
    """W4 skip-gram pairs/sec/chip (NCE, sharded-table workload)."""
    import numpy as np
    import optax

    from distributed_tensorflow_examples_tpu import models

    cfg = models.word2vec.Config(vocab_size=100_000, dim=256)

    def make_batch(rng: np.random.Generator, n: int):
        return {
            "center": rng.integers(0, cfg.vocab_size, size=(n,)).astype("int32"),
            "context": rng.integers(0, cfg.vocab_size, size=(n,)).astype("int32"),
        }

    return _bench(
        "word2vec",
        models.word2vec,
        cfg,
        optax.sgd(0.5),
        make_batch,
        steps=steps,
        batch_per_chip=batch_per_chip,
        warmup=5,
    )


def bench_decode(
    batch_per_chip: int, prompt_len: int = 32, new_tokens: int = 256,
    variant: str = "dense",
):
    """Inference surface: KV-cache autoregressive decode throughput on the
    flagship config (tokens/sec/chip; the whole decode loop is ONE jitted
    lax.scan, so host dispatch amortises over every position).

    ``steps_per_sec`` reports decode POSITIONS/s over ALL executed
    positions (prompt teacher-forcing runs the same per-position work:
    prompt_len - 1 + new_tokens of them) — the number bandwidth math must
    use; the headline tokens/s counts only the new_tokens actually
    produced.

    ``variant`` (VERDICT r4 #5 — the r4 serving paths need tokens/s rows):
    - ``dense``: the flagship config (the r2 row).
    - ``moe``: same dims with E=8 top-2 GShard FFNs — decode routes each
      position through the SAME dispatch/combine einsums as training
      (models/transformer.py _block_decode_batch), so this prices MoE serving's
      per-token routing overhead against the dense row.
    - ``pipeline``: a pipeline-trained checkpoint (stacked ``blocks``
      layout, stages=4) collapsed to the flat serving layout via
      ``collapse_pipeline`` and decoded through the ordinary KV-cache path
      — a pipelined decode would bubble O(stages) per token at T=1, so
      serving collapses the stages; weights are bit-identical, and the row
      should match ``dense`` (the measurement proves the path, the parity
      test proves the weights).
    """
    import dataclasses

    import jax
    import numpy as np

    from distributed_tensorflow_examples_tpu import models

    cfg = models.transformer.Config(
        vocab_size=32000, dim=1024, n_layers=12, n_heads=8,
        max_seq_len=prompt_len + new_tokens,
        moe_experts=8 if variant == "moe" else 0, moe_top_k=2,
    )
    if variant == "pipeline":
        train_cfg = dataclasses.replace(cfg, pipeline_stages=4, microbatches=4)
        stacked = models.transformer.init(train_cfg, jax.random.key(0))
        cfg, params = models.transformer.collapse_pipeline(train_cfg, stacked)
    else:
        params = models.transformer.init(cfg, jax.random.key(0))
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, size=(batch_per_chip, prompt_len)).astype("int32")
    out = models.transformer.generate(cfg, params, prompt, max_new_tokens=new_tokens)
    np.asarray(out)  # warm + compile
    best = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        out = models.transformer.generate(cfg, params, prompt, max_new_tokens=new_tokens)
        np.asarray(out)
        best = min(best, time.perf_counter() - t0)
    positions = prompt_len - 1 + new_tokens
    tps = batch_per_chip * new_tokens / best
    # ``generate`` runs unsharded: report the devices the output actually
    # lives on, not an assumed count.
    n_chips = len(out.devices())
    return {
        "model": "decode" if variant == "dense" else f"decode_{variant}",
        "images_per_sec": tps,
        "images_per_sec_per_chip": tps / n_chips,
        "n_chips": n_chips,
        "steps_per_sec": positions / best,
        "global_batch": batch_per_chip,
        "prompt_len": prompt_len,
        "new_tokens": new_tokens,
    }


def bench_mlp(steps: int, batch_per_chip: int):
    import optax

    from distributed_tensorflow_examples_tpu import models

    return _bench(
        "mnist_mlp",
        models.mlp,
        models.mlp.Config(),
        optax.sgd(0.05),
        lambda rng, n: {
            "image": rng.normal(size=(n, 28, 28, 1)).astype("float32"),
            "label": rng.integers(0, 10, size=(n,)).astype("int32"),
        },
        steps=steps,
        batch_per_chip=batch_per_chip,
        warmup=20,
    )


_UNITS = {
    "decode": "tokens/sec/chip",
    "decode_moe": "tokens/sec/chip",
    "decode_pipeline": "tokens/sec/chip",
    "resnet50": "images/sec/chip",
    "mnist_mlp": "images/sec/chip",
    "transformer": "tokens/sec/chip",
    "transformer_moe": "tokens/sec/chip",
    "ptb_lstm": "tokens/sec/chip",
    "word2vec": "pairs/sec/chip",
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--model",
        default="resnet50",
        choices=["resnet50", "mlp", "transformer", "moe", "lstm", "word2vec", "decode"],
    )
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--batch-per-chip", type=int, default=None)
    ap.add_argument("--seq-len", type=int, default=None)
    ap.add_argument("--remat", action="store_true")
    # Flagship defaults = the measured optimum (BASELINE.md r3): batch 8,
    # dense loss (loss_chunks is the fit-bigger knob, not a throughput one).
    ap.add_argument("--loss-chunks", type=int, default=0)
    ap.add_argument("--n-heads", type=int, default=8)
    ap.add_argument(
        "--moe-group-size", type=int, default=1024,
        help="--model moe: GShard routing-group size G — the dispatch-share "
        "knob (dispatch FLOPs/token ~ G); sweep if profile shows dispatch "
        "einsums above the ~15%% budget",
    )
    ap.add_argument(
        "--decode-variant", choices=["dense", "moe", "pipeline"], default="dense",
        help="--model decode: dense flagship, MoE (E=8 top-2 routed per "
        "position), or pipeline-trained checkpoint collapsed for serving",
    )
    args = ap.parse_args()
    device = _require_tpu()
    from distributed_tensorflow_examples_tpu.utils import compile_cache

    compile_cache.enable()

    if args.model == "resnet50":
        # Headline (BASELINE.md): per-chip batch 256 is the measured optimum.
        r = bench_resnet50(args.steps or 30, args.batch_per_chip or 256)
    elif args.model == "transformer":
        r = bench_transformer(
            args.steps or 10, args.batch_per_chip or 8, args.seq_len or 2048,
            remat=args.remat, loss_chunks=args.loss_chunks, n_heads=args.n_heads,
        )
    elif args.model == "moe":
        r = bench_moe(
            args.steps or 10, args.batch_per_chip or 4,
            seq_len=args.seq_len or 2048, remat=args.remat,
            loss_chunks=args.loss_chunks, n_heads=args.n_heads,
            moe_group_size=args.moe_group_size,
        )
    elif args.model == "decode":
        # --seq-len maps to the decode budget: prompt 32 + the rest new.
        total = args.seq_len or (32 + 256)
        r = bench_decode(
            args.batch_per_chip or 8, prompt_len=32, new_tokens=total - 32,
            variant=args.decode_variant,
        )
    elif args.model == "lstm":
        r = bench_lstm(args.steps or 50, args.batch_per_chip or 256, args.seq_len or 20)
    elif args.model == "word2vec":
        r = bench_word2vec(args.steps or 50, args.batch_per_chip or 4096)
    else:
        r = bench_mlp(args.steps or 200, args.batch_per_chip or 1024)
    unit = _UNITS[r["model"]]
    metric = f"{r['model']}_{unit.split('/')[0]}_per_sec_per_chip"
    detail = {k: round(v, 4) if isinstance(v, float) else v for k, v in r.items()}
    detail.update(device)
    print(
        json.dumps(
            {
                "metric": metric,
                "value": round(r["images_per_sec_per_chip"], 1),
                "unit": unit,
                "detail": detail,
            }
        )
    )


if __name__ == "__main__":
    main()
