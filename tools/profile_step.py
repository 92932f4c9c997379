"""Per-op device-time profile of a training step (the measurement behind the
MFU accounts in BASELINE.md).

Runs a few steps of a bench.py workload under ``jax.profiler.trace``, parses
the XPlane dump with the installed ``xprof`` converter, and prints the top
HLO ops by total device self-time — the table that names the Pallas-kernel
targets (round-2 profile: ResNet's ~200 conv fusions at 25-40% of MXU peak,
the ``select_and_scatter`` maxpool backward, the biggest ~1.5 ms fusions).

Run: python tools/profile_step.py --model transformer --batch-per-chip 8
     python tools/profile_step.py --model resnet50 --top 40
"""

from __future__ import annotations

import argparse
import glob
import gzip
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _trace_step(model: str, steps: int, batch_per_chip: int | None, **kw):
    """Build the bench workload and run ``steps`` steps under the profiler;
    returns the trace directory."""
    import jax

    import bench

    # Build via the bench helpers so the profiled program IS the benched one.
    fn = {
        "resnet50": lambda: bench.bench_resnet50,
        "transformer": lambda: bench.bench_transformer,
        "moe": lambda: bench.bench_moe,
        "lstm": lambda: bench.bench_lstm,
        "word2vec": lambda: bench.bench_word2vec,
        "mlp": lambda: bench.bench_mlp,
    }[model]()
    defaults = {
        "resnet50": dict(batch_per_chip=256),
        "transformer": dict(batch_per_chip=8),
        "moe": dict(batch_per_chip=4),
        "lstm": dict(batch_per_chip=256),
        "word2vec": dict(batch_per_chip=4096),
        "mlp": dict(batch_per_chip=1024),
    }[model]
    if batch_per_chip:
        defaults["batch_per_chip"] = batch_per_chip
    defaults.update(kw)

    # Monkey-patch the timing loop: warm up outside the trace, then trace.
    orig = bench._bench_step_loop
    tdir = tempfile.mkdtemp(prefix="xprof_")

    def traced_loop(step_fn, state, batch, *, steps: int, warmup: int):
        for _ in range(max(warmup, 2)):
            state, metrics = step_fn(state, batch)
        float(metrics["loss"])
        with jax.profiler.trace(tdir):
            for _ in range(steps):
                state, metrics = step_fn(state, batch)
            float(metrics["loss"])
        return 1.0  # dt unused

    bench._bench_step_loop = traced_loop
    try:
        fn(steps=steps, **defaults)
    finally:
        bench._bench_step_loop = orig
    return tdir


def op_table(trace_dir: str, top: int, steps: int):
    """Parse the xplane dump -> [(op_name, total_self_us, occurrences)]."""
    from xprof.convert import raw_to_tool_data

    paths = glob.glob(
        os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True
    )
    if not paths:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir}")
    data, _ = raw_to_tool_data.xspace_to_tool_data(paths, "trace_viewer", {})
    if isinstance(data, bytes):
        try:
            data = gzip.decompress(data)
        except OSError:
            pass
    trace = json.loads(data)
    events = trace["traceEvents"] if isinstance(trace, dict) else trace

    # Leaf per-op lane only ("XLA Ops" thread on the device track): scope/
    # module lanes nest above it and would double-count device time.
    tid_names = {
        (e.get("pid"), e.get("tid")): e.get("args", {}).get("name", "")
        for e in events
        if e.get("ph") == "M" and e.get("name") == "thread_name"
    }
    op_lanes = {k for k, n in tid_names.items() if "XLA Ops" in n}
    agg: dict[str, list[float]] = {}
    for e in events:
        if e.get("ph") != "X" or (e.get("pid"), e.get("tid")) not in op_lanes:
            continue
        name = e.get("name", "?")
        a = agg.setdefault(name, [0.0, 0])
        a[0] += e.get("dur", 0.0)
        a[1] += 1
    rows = sorted(agg.items(), key=lambda kv: -kv[1][0])
    total = sum(v[0] for v in agg.values())
    print(f"device total: {total / 1e3:.2f} ms over trace ({steps} steps -> "
          f"{total / 1e3 / steps:.2f} ms/step)")
    print(f"{'us/step':>10}  {'%':>5}  {'n':>4}  op")
    for name, (us, n) in rows[:top]:
        print(f"{us / steps:>10.0f}  {100 * us / total:>5.1f}  {n:>4}  {name[:110]}")
    return rows, total


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="transformer")
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--batch-per-chip", type=int, default=None)
    ap.add_argument("--seq-len", type=int, default=None)
    ap.add_argument("--loss-chunks", type=int, default=None)
    ap.add_argument("--n-heads", type=int, default=None)
    ap.add_argument("--top", type=int, default=30)
    args = ap.parse_args()
    kw = {}
    if args.seq_len:
        kw["seq_len"] = args.seq_len
    if args.loss_chunks is not None:
        kw["loss_chunks"] = args.loss_chunks
    if args.n_heads is not None:
        kw["n_heads"] = args.n_heads
    from distributed_tensorflow_examples_tpu.utils import compile_cache

    compile_cache.enable()
    tdir = _trace_step(args.model, args.steps, args.batch_per_chip, **kw)
    op_table(tdir, args.top, args.steps)
    print(f"trace dir: {tdir}")


if __name__ == "__main__":
    main()
