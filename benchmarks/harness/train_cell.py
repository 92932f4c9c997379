"""A training cell: ``train.Experiment``'s compiled step, fed by the
program's own input pipeline, timed by step completions.  What it trains it
asks of the configuration's family (``families/<model>/train.py``).

Set-up builds ONE object, the compiled step with its state, drives it from
the seed through its first steps by the window's own call and feed (those
steps are what the reference follows), and hands the same object to the
window.  A watcher thread stamps each step's completion by waiting on its
loss in order, so the dispatching loop is never drained by the measurement.
"""

from __future__ import annotations

import queue
import shutil
import statistics
import tempfile
import threading
import time
import types

import numpy as np

from benchmarks.reference import weights

from . import manifest, stats, trace
from .report import Phases, say, say_compared

RUN_AHEAD = 4
WARM_STEPS = 5
#: Numbers that must be at least their limit; every other at most.
AT_LEAST = ("grad_cosine_median",)


def optimizer_of(opt: dict):
    import optax

    if opt["optimizer"] == "sgd":
        return optax.sgd(opt["learning_rate"], momentum=opt["momentum"])
    raise ValueError(f"unknown optimizer {opt['optimizer']!r}")


def first_gradient(opt_state):
    """The first gradient as the optimizer got it, from its state after one
    step: momentum's trace is the gradient itself."""
    import jax
    import optax

    is_trace = lambda n: isinstance(n, optax.TraceState)  # noqa: E731
    for node in jax.tree.leaves(opt_state, is_leaf=is_trace):
        if is_trace(node):
            return node.trace
    raise ValueError("no momentum state to read the gradient from")


def worst_leaf_gap(got: dict, ref: dict, keep=lambda leaf: True) -> float:
    """Largest gap between the program's norm and the reference's, against
    the reference's norm of that leaf or of the median leaf, whichever is
    larger (some gradients are all but zero), over the leaves whose name
    ``keep`` takes."""
    floor = statistics.median(ref.values())
    return max(abs(got[k] - ref[k]) / max(ref[k], floor) for k in ref if keep(k))


def build(cell, seed: int):
    """The experiment, its data with the reader of a fed batch's rows, the
    batches and the per-example model FLOPs."""
    t_build = time.monotonic()
    import jax

    from distributed_tensorflow_examples_tpu import data, train
    from distributed_tensorflow_examples_tpu.parallel import MeshSpec, build_mesh

    say(f"setup phase import_program: {time.monotonic() - t_build:.2f} s")
    tr, opt = cell.traffic, cell.config["train"]
    hi, lo = int(seed) >> 31, int(seed) & 0x7FFFFFFF
    mesh = build_mesh(MeshSpec.parse(tr["mesh"]), devices=jax.devices()[: cell.chips])
    flags = types.SimpleNamespace(
        mesh=tr["mesh"], seed=lo, unroll=1, log_dir="", train_steps=10 ** 9,
        log_every_steps=10 ** 9, batch_size=tr["global_batch"],
        checkpoint_every_steps=10 ** 9, watchdog=False,
    )
    family = cell.family
    cfg, tree_fn = family.build(cell.config)
    arrays, rows_of = family.batches(cell.config, tr, seed)
    per_example = family.train_flops_per_example(cell.config, tr)
    exp = train.Experiment(
        init_fn=lambda rng: tree_fn(jax.random.fold_in(rng, hi)),
        optimizer=optimizer_of(opt), flags=flags, mesh=mesh,
        loss_fn=family.loss_fn(cfg, cell.config),
        rules=family.sharding_rules(cfg),
    )
    pipeline = data.pipeline.InMemoryPipeline(
        arrays, batch_size=tr["global_batch"], shuffle=True, seed=lo)
    return exp, arrays, rows_of, iter(pipeline), per_example


def make_step(exp, state, first_batch):
    """The compiled step the window drives, with XLA's account of its
    memory.  Tests break the timed path by replacing this."""
    compiled = exp.step_fn.lower(state, first_batch).compile()
    ma = compiled.memory_analysis()
    return compiled, int(ma.argument_size_in_bytes + ma.temp_size_in_bytes)


class Watcher:
    """Stamps step completions in order, on a thread of its own.  A step
    that fails is raised again in the dispatching loop at its next ``put``,
    not left to block it."""

    def __init__(self):
        self.q: queue.Queue = queue.Queue(maxsize=RUN_AHEAD)
        self.done: list[float] = []
        self.losses: list = []
        self.error: BaseException | None = None
        self._thread = threading.Thread(target=self._loop, daemon=True, name="bench-watch")
        self._thread.start()

    def _loop(self):
        try:
            while True:
                loss = self.q.get()
                if loss is None:
                    return
                loss.block_until_ready()
                self.done.append(time.monotonic())
                self.losses.append(loss)
        except BaseException as e:  # noqa: BLE001 - handed to the loop, which raises it
            self.error = e

    def put(self, loss) -> None:
        while True:
            if self.error is not None:
                raise RuntimeError("a step failed on the device") from self.error
            try:
                return self.q.put(loss, timeout=1.0)
            except queue.Full:
                continue

    def drain(self):
        self.put(None)
        self._thread.join()
        if self.error is not None:
            raise RuntimeError("a step failed on the device") from self.error


def reference_numbers(cell, seed: int, rows: list, arrays: dict, mode: str) -> dict:
    """The family's reference over the rows that the compared steps were fed."""
    batches = [{k: v[r] for k, v in arrays.items()} for r in rows]
    return cell.family.reference_train(cell.config, seed, batches, mode)


def gradient_cosines(got, ref, keep) -> dict:
    """The cosine between the program's first gradient and the reference's,
    leaf by leaf over those whose name ``keep`` takes: unlike a gap between
    norms, first order in rounding noise."""
    import jax
    import jax.numpy as jnp

    def cos(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return jnp.sum(a * b) / jnp.sqrt(jnp.sum(jnp.square(a)) * jnp.sum(jnp.square(b)))

    errs = jax.jit(lambda g, r: jax.tree.map(cos, g, r))(got, ref)
    named = {
        "/".join(str(getattr(p, "key", p)) for p in path): float(v)
        for path, v in jax.tree_util.tree_leaves_with_path(errs)
    }
    return {k: v for k, v in named.items() if keep(k)}


def compare(got: dict, ref: dict, kernels) -> dict:
    """Every number compared.  The norms' gaps and the cosine are taken over
    the leaves the family names (``compared_kernels``); the all-leaf gaps are
    printed beside them."""
    cos = gradient_cosines(got["first_grads"], ref["first_grads"], kernels)
    return {
        "loss_gap": max(abs(a - b) / abs(b) for a, b in zip(got["losses"], ref["losses"])),
        "grad_gap_kernels": worst_leaf_gap(got["grad_norms"], ref["grad_norms"], kernels),
        "delta_gap_kernels": worst_leaf_gap(got["delta_norms"], ref["delta_norms"], kernels),
        # Higher is better: held to "at least" its limit (see AT_LEAST).
        "grad_cosine_median": statistics.median(cos.values()),
        "grad_cosine_worst": min(cos.values()),
        "grad_gap_all_leaves": worst_leaf_gap(got["grad_norms"], ref["grad_norms"]),
        "delta_gap_all_leaves": worst_leaf_gap(got["delta_norms"], ref["delta_norms"]),
    }


def run(cell, seed: int, seconds: float, traced: bool, t_proc0: float,
        control: str | None = None) -> dict:
    import jax
    import jax.numpy as jnp

    phases = Phases(t_proc0)
    phases.mark("runtime_start")
    tr = cell.traffic
    exp, arrays, rows_of, host_batches, per_example = build(cell, seed)
    jax.block_until_ready(exp.state)
    phases.mark("init")

    fed_rows: list = []
    check_steps = int(tr["check_steps"])

    def noting_rows(it):
        for b in it:
            if len(fed_rows) < check_steps:
                fed_rows.append(rows_of(b))
            yield b

    batches = exp.batches(noting_rows(host_batches))
    state = exp.state
    exp.state = exp.session.state = None
    first = next(batches)
    step, compiled_bytes = make_step(exp, state, first)
    phases.mark("compile")

    # The first steps, by the window's own call and feed.
    params0 = jax.tree.map(jnp.copy, state.params)
    got = {"losses": []}
    batch = first
    for i in range(check_steps):
        state, metrics = step(state, batch)
        got["losses"].append(float(metrics["loss"]))
        if i == 0:
            got["first_grads"] = jax.tree.map(
                jnp.copy, first_gradient(state.opt_state))
            got["grad_norms"] = weights.leaf_norms(got["first_grads"])
        batch = next(batches)
    got["delta_norms"] = weights.leaf_norms(jax.tree.map(jnp.subtract, state.params, params0))
    del params0
    phases.mark("first_steps")

    if tr["feed"] == "resident":
        # One device-resident batch from here on: no input path in the window.
        import itertools

        batches.close()
        batches = itertools.repeat(batch)
    elif tr["feed"] != "pipeline":
        raise ValueError(f"unknown feed {tr['feed']!r}")
    watcher = Watcher()
    for _ in range(WARM_STEPS):
        state, metrics = step(state, batch)
        watcher.put(metrics["loss"])
        batch = next(batches)
    while len(watcher.done) < WARM_STEPS:
        if watcher.error is not None:
            watcher.drain()
        time.sleep(0.001)
    phases.mark("warm_up")

    # The window opens on the completion of the last warm step.
    w0 = watcher.done[-1]
    setup_s = w0 - t_proc0
    first_measured = len(watcher.done)
    trace_dir = None
    trace_s = min(float(tr["trace_s"]), seconds)
    window_span = None
    spans = {"bench.input_wait": 0.0, "bench.dispatch": 0.0, "bench.run_ahead_wait": 0.0}
    t = time.monotonic()
    while t - w0 < seconds:
        if traced and trace_dir is None and t - w0 >= seconds - trace_s:
            trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            trace.start(trace_dir)
            traced_from = len(watcher.done)
            window_span = jax.profiler.TraceAnnotation(trace.WINDOW_SPAN)
            window_span.__enter__()
            t = time.monotonic()
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            state, metrics = step(state, batch)
        t1 = time.monotonic()
        with jax.profiler.TraceAnnotation("bench.run_ahead_wait"):
            watcher.put(metrics["loss"])
        t2 = time.monotonic()
        with jax.profiler.TraceAnnotation("bench.input_wait"):
            batch = next(batches)
        t3 = time.monotonic()
        spans["bench.dispatch"] += t1 - t
        spans["bench.run_ahead_wait"] += t2 - t1
        spans["bench.input_wait"] += t3 - t2
        t = t3
    watcher.drain()
    if window_span is not None:
        window_span.__exit__(None, None, None)
        t_stop = time.monotonic()
        jax.profiler.stop_trace()
        before = watcher.done[first_measured - 1: traced_from]
        during = watcher.done[traced_from:]
        if len(before) > 2 and len(during) > 2:
            say(f"traced steps {1e3 * statistics.median(b - a for a, b in zip(during, during[1:])):.1f} ms, "
                f"untraced {1e3 * statistics.median(b - a for a, b in zip(before, before[1:])):.1f} ms, "
                f"stop_trace {time.monotonic() - t_stop:.1f} s")
    done = watcher.done[first_measured - 1:]
    losses = [float(l) for l in watcher.losses[first_measured:]]
    memory = [d.memory_stats() or {} for d in jax.devices()[: cell.chips]]
    if hasattr(batches, "close"):
        batches.close()
    del state, batch, first, step
    phases.mark("window")

    rate, window_s = stats.window_rate(done, float(tr["global_batch"]))
    # A run that reads far off says whether it stalled once or ran slow throughout.
    gaps = [b - a for a, b in zip(done, done[1:])]
    say(f"window {len(gaps)} steps, median interval {1e3 * statistics.median(gaps):.2f} ms, "
        f"longest {1e3 * max(gaps):.1f} ms, {done[gaps.index(max(gaps))] - done[0]:.1f} s into it")
    peak = manifest.peak_for(jax.devices()[0].device_kind)["bf16_flops_per_s"]
    mfu = 100.0 * per_example * rate / (cell.chips * peak)

    t_ref = time.monotonic()
    ref = reference_numbers(cell, seed, fed_rows, arrays, "float32")
    kernels = cell.family.compared_kernels(cell.config)
    check = compare(got, ref, kernels)
    check["losses"] = got["losses"]
    for mode in (control or "").split(",") if control else ():
        if mode == "half_batch":  # the fault the loss is there to catch
            half = [r[: len(r) // 2] for r in fed_rows]
            low = reference_numbers(cell, seed, half, arrays, "float32")
        else:
            low = reference_numbers(cell, seed, fed_rows, arrays, mode)
        check[f"control_{mode}"] = compare(low, ref, kernels)
        del low
    del got["first_grads"], ref["first_grads"]
    limits = tr["correct"]["limits"]
    finite = all(np.isfinite(losses)) and len(losses) > 0
    verdicts = []
    for name, value in check.items():
        if isinstance(value, float):
            say_compared(f"{name} {value} "
                f"{'at least' if name in AT_LEAST else 'limit'} {limits.get(name)}")
    for name, limit in limits.items():
        if limit is None:
            verdicts.append(False)
        elif name in AT_LEAST:
            verdicts.append(check[name] >= limit)
        else:
            verdicts.append(check[name] <= limit)
    say_compared(f"window losses finite {finite} (reference "
        f"{time.monotonic() - t_ref:.1f} s)")
    evidence = {
        "cell": cell, "window_s": window_s, "memory": memory,
        "compiled_bytes": compiled_bytes, "spans": spans,
        "series": {"step_done_s": done}, "setup_s": setup_s,
        "trace": trace.load(trace.find_xplane(trace_dir)) if traced else None,
    }
    if trace_dir:
        shutil.rmtree(trace_dir, ignore_errors=True)
    return {
        "correct": bool(all(verdicts) and finite), "attempted": len(done) - 1,
        "failed": 0 if finite else sum(1 for l in losses if not np.isfinite(l)),
        "end_to_end": {"setup_s": setup_s, "train_mfu": mfu},
        "evidence": evidence, "check": check,
    }
