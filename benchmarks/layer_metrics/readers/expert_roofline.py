"""Roofline share of the grouped expert feed-forward kernel, in %.

The least time the kernel's calls of the traced stretch could take, over the
device time they took.  A call's least is the larger of its bytes over the
published HBM bandwidth and its operations over the published bf16 peak, as
the configuration's family counts them from shapes (``expert_call_bytes``:
each touched expert's matrices once, the rows in and out;
``expert_call_flops``).  How many experts a call touched and how many rows
it had is known on the device only; the model counts both, for the step's
calls and the chunk's APART (a chunk's call touches every expert held, a
step's a handful, and how many chunks a stretch of 4 s holds swings with the
arrivals), and this reader takes each program's means over the WINDOW from
the counters read at its two ends, and the number of each program's calls
and their time from the trace: the operations whose name holds ``op`` that
start inside a launch of ``step_module`` / ``chunk_module``.  What is left
between window and stretch is how a program's own calls vary (the step's
with the rows live).  Memory-bound wherever an expert has fewer rows than
some 270."""

import bisect

from benchmarks.harness import manifest, trace

#: The counters a program's means are taken from: all calls, and the chunk's.
COUNTS = ("calls", "experts_touched", "choices_held")


def _calls_inside(tr, op, module):
    """``(number, seconds)`` of the operations named ``op`` that start
    inside a launch of ``module`` in the traced window."""
    w0, w1 = trace.window_of(tr)
    launches = trace.clip(trace.module_events(tr, module), w0, w1)
    starts = [s for s, _e, *_ in launches]
    first = tr["devices"][sorted(tr["devices"])[0]]
    n, total = 0, 0.0
    for s, e, name in trace.clip(first["ops"], w0, w1):
        i = bisect.bisect_right(starts, s) - 1
        if op in name and i >= 0 and s < launches[i][1]:
            n, total = n + 1, total + (e - s)
    return n, total


def read(evidence, *, op, step_module, chunk_module, prefix, chunk_prefix):
    tr, c = evidence.get("trace"), evidence.get("counters")
    if not tr or not tr["devices"] or not c:
        return None
    keys = [p + k for p in (prefix, chunk_prefix) for k in COUNTS]
    if any(k not in c["end"] or k not in c["start"] for k in keys):
        return None
    config = evidence["cell"].config
    family = manifest.family(config["model"], "serve")
    count_bytes = getattr(family, "expert_call_bytes", None)
    count_flops = getattr(family, "expert_call_flops", None)
    if count_bytes is None or count_flops is None:
        return None
    delta = lambda k: c["end"][k] - c["start"][k]
    chunk = {k: delta(chunk_prefix + k) for k in COUNTS}
    step = {k: delta(prefix + k) - chunk[k] for k in COUNTS}
    peaks = evidence["peaks"]
    least_s = busy_s = 0.0
    for counts, module in ((step, step_module), (chunk, chunk_module)):
        n, seconds = _calls_inside(tr, op, module)
        if not n:
            continue
        if counts["calls"] <= 0:
            return None
        touched = counts["experts_touched"] / counts["calls"]
        rows = counts["choices_held"] / counts["calls"]
        least_s += n * max(
            count_bytes(config, touched, rows) / peaks["hbm_bytes_per_s"],
            count_flops(config, rows) / peaks["bf16_flops_per_s"],
        )
        busy_s += seconds
    return 100.0 * least_s / busy_s if busy_s else None
