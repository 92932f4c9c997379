"""Chip smoke: the transformer train -> publish -> serve path, once, on the TPU.

The quickest proof that the system still starts on the chip — NOT a
benchmark.  One process holds the chip for every phase (server and client
threads inside it; no child process is started):

1. *device*   — establish the platform first; anything but ``tpu`` fails in
               seconds, before a model is built.
2. *train*    — ``train.Experiment`` -> ``create_sharded_state`` ->
               ``build_train_step`` at the flagship width (dim 1024, 12
               layers, 8 heads of 128, vocab 32000, T=2048, global batch 8,
               bf16 compute, ``attention="auto"``), fed by
               ``data.datasets.lm_batches`` through ``prefetch_to_mesh``.
               The train step is AOT-compiled first and must contain the
               Mosaic custom calls of the flash forward, dq and dkv kernels
               (a silent XLA-attention or interpret-mode step is a failure).
               The loss must be finite and lower after the last step than
               after the first.
3. *publish*  — ``ModelRegistry.publish(flat_params_of(params))``.
4. *serve*    — a registry-pinned ``serve.ModelReplicaServer`` with the
               model's own decode functions answers more concurrent
               ``ServeClient.generate`` requests than it has decode slots;
               every answer must hold the requested number of in-vocabulary
               tokens.  Whether the served tokens equal
               ``models.transformer.generate`` on the same params and prompt
               is reported (and required on the CPU, where tier-1 runs this
               body at tiny size).

Prints the full record as one JSON line, writes it to
``<out_dir>/result.json`` and, only when every phase passed, prints as the
LAST line ``{"ok": true, "device": {"platform", "kind", "count"}}`` and
exits 0.  Any failed check exits 1 with the reasons on stderr.

Run: ``python chip_smoke.py [--mesh data=2,model=2] [--steps 8]``
"""

from __future__ import annotations

import argparse
import collections
import concurrent.futures
import itertools
import json
import os
import re
import shutil
import sys
import time
import types

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

#: The flagship configuration (dim 1024, 12 layers, heads of 128).
FLAGSHIP = dict(vocab_size=32000, dim=1024, n_layers=12, n_heads=8)
SEQ_LEN = 2048
GLOBAL_BATCH = 8

#: The Pallas kernels the train step must carry as Mosaic custom calls.
FLASH_KERNELS = ("_fwd_kernel", "_dq_kernel", "_dkv_kernel")

MODEL_NAME = "transformer_lm"


def device_record() -> dict:
    """The device and installation as JAX reports them."""
    import importlib.metadata as md

    import jax
    import jaxlib

    def _version(dist: str) -> str | None:
        try:
            return md.version(dist)
        except md.PackageNotFoundError:
            return None

    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "device_count": len(devs),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": _version("libtpu"),
    }


def _memory(devices) -> list[dict]:
    """Per-device allocator counters (None on backends without them)."""
    out = []
    for d in devices:
        ms = d.memory_stats() or {}
        out.append({
            "device": str(d),
            "bytes_in_use": ms.get("bytes_in_use"),
            "peak_bytes_in_use": ms.get("peak_bytes_in_use"),
        })
    return out


def _placement(tree, mesh, platform: str) -> dict:
    """Where a pytree of arrays lives: leaves off ``platform`` and the mesh
    devices that hold a shard of EVERY leaf (all of them, for state laid
    out by NamedSharding over the mesh)."""
    import jax

    leaves = jax.tree.leaves(tree)
    off = sum(1 for l in leaves for d in l.devices() if d.platform != platform)
    holders = set(mesh.devices.flat)
    for l in leaves:
        holders &= {s.device for s in l.addressable_shards}
    return {
        "leaves": len(leaves),
        "off_platform": off,
        "devices_holding_every_leaf": len(holders),
    }


class _LossRecorder:
    """Session hook: the loss and wall time of every step, each closed by a
    host fetch of the loss scalar."""

    def __init__(self):
        self.losses: list[float] = []
        self.step_s: list[float] = []
        self._t0 = 0.0

    def begin(self, loop):
        pass

    def before_step(self, loop):
        self._t0 = time.perf_counter()

    def after_step(self, loop, metrics):
        self.losses.append(float(metrics["loss"]))
        self.step_s.append(time.perf_counter() - self._t0)

    def end(self, loop):
        pass


def _train(cfg, *, platform, seq_len, batch, steps, mesh_spec, seed, fail):
    """Phase 2.  Returns ``(record, experiment)``."""
    import jax
    import numpy as np
    import optax

    from distributed_tensorflow_examples_tpu import data, models, train

    ids, _vocab, source = data.datasets.text_corpus(
        None, vocab_size=cfg.vocab_size,
        synth_tokens=batch * (seq_len + 1) * (steps + 2), seed=seed,
    )
    recorder = _LossRecorder()
    flags = types.SimpleNamespace(
        mesh=mesh_spec, seed=seed, unroll=1, log_dir="", train_steps=steps,
        log_every_steps=1, batch_size=batch, checkpoint_every_steps=steps,
    )
    t0 = time.perf_counter()
    exp = train.Experiment(
        init_fn=lambda rng: models.transformer.init(cfg, rng),
        loss_fn=None,
        optimizer=optax.chain(
            optax.clip_by_global_norm(1.0), optax.adamw(1e-3)
        ),
        rules=models.transformer.sharding_rules(cfg),
        flags=flags,
        loss_fn_factory=lambda mesh: models.transformer.loss_fn(cfg, mesh=mesh),
        batch_spec=models.transformer.batch_spec(cfg),
        extra_hooks=[recorder],
    )
    jax.block_until_ready(exp.state)
    init_s = time.perf_counter() - t0

    batches = exp.batches(
        data.datasets.lm_batches(ids, batch_size=batch, seq_len=seq_len)
    )
    first = next(batches)

    # The kernel proof: AOT-compile the step the session is about to run
    # (the jit call below reuses the executable) and read the Mosaic custom
    # calls out of it.
    t0 = time.perf_counter()
    lowered = exp.step_fn.lower(exp.state, first)
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0
    lowered_kernels = collections.Counter(
        re.findall(r'kernel_name = "([^"]+)"', lowered.as_text())
    )
    compiled_calls = compiled.as_text().count(
        'custom_call_target="tpu_custom_call"'
    )
    kernels = {
        "lowered_mosaic_kernels": dict(lowered_kernels),
        "compiled_tpu_custom_calls": compiled_calls,
    }
    # The allocator's peak_bytes_in_use (``memory`` below) does not count
    # an executable's temporaries; XLA's own account of the step does.
    ma = compiled.memory_analysis()
    step_memory = {
        "argument_bytes": ma.argument_size_in_bytes,
        "output_bytes": ma.output_size_in_bytes,
        "alias_bytes": ma.alias_size_in_bytes,
        "temp_bytes": ma.temp_size_in_bytes,
    }
    if platform == "tpu":
        missing = [k for k in FLASH_KERNELS if not lowered_kernels[k]]
        if missing:
            fail(f"train: step lowered without Mosaic kernels {missing}")
        if compiled_calls < sum(lowered_kernels[k] for k in FLASH_KERNELS):
            fail(
                f"train: compiled step holds {compiled_calls} "
                f"tpu_custom_call(s), lowered {dict(lowered_kernels)}"
            )

    placement = {
        "mesh": {k: int(v) for k, v in exp.mesh.shape.items() if v > 1},
        "params": _placement(exp.state.params, exp.mesh, platform),
        "opt_state": _placement(exp.state.opt_state, exp.mesh, platform),
        "batch": _placement(first, exp.mesh, platform),
    }
    qkv = exp.state.params["block_0"]["qkv"]["kernel"]
    placement["qkv_kernel"] = {
        "global_shape": list(qkv.shape),
        "shard_shape": list(qkv.addressable_shards[0].data.shape),
    }
    for name in ("params", "opt_state", "batch"):
        p = placement[name]
        if p["off_platform"] or p["devices_holding_every_leaf"] != exp.mesh.size:
            fail(f"train: {name} placement {p} on a {exp.mesh.size}-device mesh")

    t0 = time.perf_counter()
    exp.state = exp.session.run(itertools.chain([first], batches))
    run_s = time.perf_counter() - t0
    memory = _memory(exp.mesh.devices.flat)
    if any(m["bytes_in_use"] == 0 for m in memory):
        fail(f"train: a mesh device holds nothing: {memory}")

    losses = recorder.losses
    if len(losses) != steps:
        fail(f"train: ran {len(losses)} steps, wanted {steps}")
    if not all(np.isfinite(losses)):
        fail(f"train: non-finite loss {losses}")
    elif not losses[-1] < losses[0]:
        fail(f"train: loss did not fall: first {losses[0]}, last {losses[-1]}")
    exp.finish(final_loss=losses[-1])
    return {
        "corpus": source,
        "global_batch": batch,
        "seq_len": seq_len,
        "steps": len(losses),
        "losses": [round(l, 5) for l in losses],
        "init_s": round(init_s, 2),
        "compile_s": round(compile_s, 2),
        # First step: jit dispatch of the already-compiled executable.
        "first_step_s": round(recorder.step_s[0], 3),
        "step_s": [round(s, 4) for s in recorder.step_s[1:]],
        "run_s": round(run_s, 2),
        "kernels": kernels,
        "placement": placement,
        "step_memory": step_memory,
        "memory": memory,
    }, exp


def _publish(exp, registry_dir: str):
    """Phase 3, as examples/transformer_lm.py does it.  Returns
    ``(record, version, flat)``."""
    import jax
    import numpy as np

    from distributed_tensorflow_examples_tpu.serve.registry import ModelRegistry
    from distributed_tensorflow_examples_tpu.train.checkpoint import (
        flat_params_of,
    )

    t0 = time.perf_counter()
    flat = flat_params_of(exp.state.params)
    version = ModelRegistry(registry_dir).publish(
        MODEL_NAME, flat,
        step=int(np.asarray(jax.device_get(exp.state.step))),
        source="chip_smoke",
    )
    return {
        "version": version,
        "num_params": int(flat.size),
        "publish_s": round(time.perf_counter() - t0, 2),
    }, version, flat


def _reference_decode(cfg, exp, flat, prompt, new_tokens):
    """The model's own unbatched greedy decode over the published values
    (its device copy of the params dies with this frame)."""
    import numpy as np

    from distributed_tensorflow_examples_tpu import models
    from distributed_tensorflow_examples_tpu.parallel import ps_shard

    _total, unflatten = ps_shard.flat_param_spec(exp.state.params)
    out = models.transformer.generate(
        cfg, unflatten(flat), prompt[None], max_new_tokens=new_tokens,
    )
    return np.asarray(out)[0, len(prompt):].astype(np.int32)


def _serve(
    cfg, exp, flat, version, *, platform, registry_dir, max_len, slots,
    n_requests, prompt_len, new_tokens, seed, fail,
):
    """Phase 4.  Returns the record."""
    import jax
    import numpy as np

    from distributed_tensorflow_examples_tpu import models, serve

    rng = np.random.default_rng(seed)
    # More requests than slots, of unequal prompt lengths, so the
    # SlotBatcher seats, frees and reseats.
    prompts = [
        rng.integers(0, cfg.vocab_size, size=prompt_len + 2 * i).astype(np.int32)
        for i in range(n_requests)
    ]
    t0 = time.perf_counter()
    ref = _reference_decode(cfg, exp, flat, prompts[0], new_tokens)
    generate_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    server = serve.ModelReplicaServer(
        lambda r: models.transformer.init(cfg, r),
        lambda p, b: models.transformer.apply(cfg, p, b["x"]),
        [], registry_dir=registry_dir, model_name=MODEL_NAME,
        model_version=version,
        decode_fns=models.transformer.serve_decode_fns(cfg),
        decode_slots=slots, decode_max_len=max_len, role="smoke_serve0",
    )
    load_s = time.perf_counter() - t0
    try:
        # The replica keeps its model and cache private; the smoke reads
        # them only to say where they live.
        served = {
            "params": jax.tree.leaves(server._model[1]),
            "cache": jax.tree.leaves(server._engine._cache),
        }
        for name, leaves in served.items():
            off = {str(d) for l in leaves for d in l.devices()
                   if d.platform != platform}
            if off:
                fail(f"serve: {name} live on {sorted(off)}, not on {platform}")
        served_on = sorted(
            {str(d) for l in served["params"] for d in l.devices()}
        )

        def request(i: int, n: int):
            client = serve.ServeClient(
                "127.0.0.1", server.port, role=f"smoke_client{i}"
            )
            try:
                # The first decode step compiles inside the request.
                return client.generate(prompts[i], n, deadline_s=600.0)
            finally:
                client.close()

        # Compile + first token, alone: set-up time, kept apart from the
        # concurrent run below.
        t0 = time.perf_counter()
        warm = request(0, 1)
        first_request_s = time.perf_counter() - t0
        if len(warm) != 1:
            fail(f"serve: warm-up request returned {len(warm)} tokens, wanted 1")

        t0 = time.perf_counter()
        outs: list = [None] * n_requests
        with concurrent.futures.ThreadPoolExecutor(n_requests) as pool:
            futures = [
                pool.submit(request, i, new_tokens) for i in range(n_requests)
            ]
            for i, f in enumerate(futures):
                try:
                    outs[i] = f.result(timeout=900)
                except (
                    serve.ServeError, OSError, concurrent.futures.TimeoutError
                ) as e:
                    fail(f"serve: request {i} failed: {type(e).__name__}: {e}")
        run_s = time.perf_counter() - t0
        for i, out in enumerate(outs):
            if out is None:
                continue
            if len(out) != new_tokens:
                fail(f"serve: request {i} returned {len(out)} tokens, "
                     f"wanted {new_tokens}")
            if out.size and not (0 <= out.min() and out.max() < cfg.vocab_size):
                fail(f"serve: request {i} returned tokens outside "
                     f"[0, {cfg.vocab_size})")
        stats = server.stats()
    finally:
        server.stop()

    # Request 0 against the reference decode of the SAME published values.
    equal = outs[0] is not None and bool(np.array_equal(outs[0], ref))
    if platform == "cpu" and not equal:
        fail(f"serve: served tokens {outs[0]} != generate {ref}")
    return {
        "served_on": served_on,
        "decode_slots": slots,
        "decode_max_len": max_len,
        "load_s": round(load_s, 2),
        "first_request_s": round(first_request_s, 2),
        "requests": n_requests,
        "answered": sum(o is not None for o in outs),
        "new_tokens_each": new_tokens,
        "tokens": int(sum(len(o) for o in outs if o is not None)),
        "run_s": round(run_s, 2),
        "decode_steps": stats["decode_steps"],
        "decode_sessions": stats["decode_sessions"],
        "served_equals_generate": equal,
        "generate_s": round(generate_s, 2),
        "memory": _memory(jax.devices()),
    }


def run_smoke(
    cfg, *, platform: str, out_dir: str, seq_len: int, batch: int, steps: int,
    mesh_spec: str = "", decode_slots: int = 4, n_requests: int = 6,
    prompt_len: int = 24, new_tokens: int = 32, seed: int = 0,
) -> dict:
    """Every phase at the given size.  ``platform`` is the platform the run
    must be on: ``tpu`` for the smoke itself, ``cpu`` for the tier-1 test of
    this body (interpret-mode kernels asked for through
    ``cfg.attention="flash"``).  Returns the record; ``record["ok"]`` is
    the conjunction of the phases and ``record["failures"]`` says why not.
    """
    from distributed_tensorflow_examples_tpu.utils import compile_cache

    device = device_record()
    if device["platform"] != platform:
        raise RuntimeError(
            f"chip_smoke: platform is {device['platform']!r}, not "
            f"{platform!r} — nothing was run"
        )
    failures: list[str] = []
    record = {
        "ok": False, "note": "set-up facts, not a benchmark",
        "device": device, "failures": failures,
        "compile_cache": compile_cache.enable(),
    }
    registry_dir = os.path.join(out_dir, "registry")
    os.makedirs(out_dir, exist_ok=True)
    try:
        record["train"], exp = _train(
            cfg, platform=platform, seq_len=seq_len, batch=batch, steps=steps,
            mesh_spec=mesh_spec, seed=seed, fail=failures.append,
        )
        record["publish"], version, flat = _publish(exp, registry_dir)
        record["serve"] = _serve(
            cfg, exp, flat, version, platform=platform,
            registry_dir=registry_dir, max_len=seq_len, slots=decode_slots,
            n_requests=n_requests, prompt_len=prompt_len,
            new_tokens=new_tokens, seed=seed, fail=failures.append,
        )
    finally:
        # The published blob is the size of the model; only the record is
        # worth keeping.
        if os.path.isdir(registry_dir):
            shutil.rmtree(registry_dir)
    record["ok"] = not failures
    return record


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--mesh", default="",
        help='parallel.MeshSpec.parse spelling, e.g. "data=2,model=2"; empty '
        "= every device on the data axis",
    )
    ap.add_argument("--steps", type=int, default=8, help="train steps")
    ap.add_argument(
        "--moe_experts", type=int, default=0,
        help=">0: the same width with GShard MoE FFNs (pass a mesh with an "
        "expert axis)",
    )
    ap.add_argument(
        "--out_dir", default=os.path.join(ROOT, "chiprun_out", "chip_smoke"),
        help="result.json and the run's scratch (registry) go here",
    )
    args = ap.parse_args(argv)

    # Phase 1: the device, before anything is built.
    device = device_record()
    print(f"chip_smoke: device {json.dumps(device)}", file=sys.stderr, flush=True)
    if device["platform"] != "tpu":
        print(
            f"chip_smoke: FAILED — jax.devices()[0].platform is "
            f"{device['platform']!r}, not 'tpu'; nothing was run",
            file=sys.stderr,
        )
        return 1

    from distributed_tensorflow_examples_tpu import models

    cfg = models.transformer.Config(
        **FLAGSHIP, max_seq_len=SEQ_LEN, attention="auto",
        moe_experts=args.moe_experts,
    )
    record = run_smoke(
        cfg, platform="tpu", out_dir=args.out_dir, seq_len=SEQ_LEN,
        batch=GLOBAL_BATCH, steps=args.steps, mesh_spec=args.mesh,
    )
    line = json.dumps(record)
    with open(os.path.join(args.out_dir, "result.json"), "w") as f:
        f.write(line + "\n")
    print(line, flush=True)
    if not record["ok"]:
        for reason in record["failures"]:
            print(f"chip_smoke: FAILED — {reason}", file=sys.stderr)
        return 1
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": device["platform"],
            "kind": device["device_kind"],
            "count": device["device_count"],
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
