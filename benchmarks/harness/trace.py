"""From a profiler trace (``.xplane.pb``) to intervals, with nothing but
``jax.profiler.ProfileData``.

Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per executed operation and ``XLA Modules`` one per launched program.
Host planes hold the ``TraceAnnotation`` spans of the benchmark
(``bench.*``) and, on the lines of the Python threads, the runtime's own
host events (``PjitFunction(step_fn)``, ``np.asarray(jax.Array)``, ...);
both name idle gaps, the benchmark's spans first.  All times are seconds on
the trace's own clock.
"""

from __future__ import annotations

import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
GAP_FLOOR_S = 50e-6


def start(log_dir: str) -> None:
    """Start the profiler: no Python tracer, no HLO dump; host tracer at
    level 2, where the runtime's own host events come besides the
    benchmark's spans."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def short_name(name: str) -> str:
    """``%fusion.26 = bf16[8,50304]{...} fusion(...)`` -> ``fusion.26 bf16[8,50304]``."""
    head, sep, rest = name.partition(" = ")
    head = head.lstrip("%")
    if not sep:
        return head
    result = rest.split("{", 1)[0].split(" ", 1)[0]
    if result.startswith("("):
        result += ",...)"
    return f"{head} {result}"


def load(path: str, device_prefix: str = DEVICE_PREFIX) -> dict:
    """``{"devices": {plane: {"ops": [...], "modules": [...]}}, "spans": [...]}``
    with every event as ``(start_s, end_s, name)``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, spans, host = {}, [], []
    for plane in data.planes:
        if plane.name.startswith(device_prefix):
            lines = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key is None:
                    continue
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    lines[key].append((s, s + ev.duration_ns * 1e-9, short_name(ev.name)))
            if lines["ops"]:
                devices[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                python_thread = line.name.startswith("python")
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((s, s + ev.duration_ns * 1e-9, ev.name))
                    elif python_thread:
                        host.append((s, s + ev.duration_ns * 1e-9, ev.name))
    return {"devices": devices, "spans": sorted(spans), "host": sorted(host)}


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted intervals."""
    out: list[list[float]] = []
    for s, e in sorted((i[0], i[1]) for i in intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, w0: float, w1: float):
    return [(max(s, w0), min(e, w1)) + tuple(rest)
            for s, e, *rest in intervals if e > w0 and s < w1]


def _work(device: dict) -> list:
    """A device's intervals of work: its operations and the launches of its
    programs.  A launch covers its operations, and is still there should a
    trace lack some of them."""
    return device["ops"] + device["modules"]


def _first_device(tr: dict) -> dict:
    """The first device's lines; a trace in which no operation ran has no
    device at all (``load`` keeps the planes that hold operations), and its
    first device then holds nothing."""
    if not tr["devices"]:
        return {"ops": [], "modules": []}
    return tr["devices"][sorted(tr["devices"])[0]]


def window_of(tr: dict) -> tuple[float, float]:
    """The traced window: the ``bench.window`` span, cut to the extent of
    device work (what a trace that starts late or ends early did not record
    is not idle time); without the span, the extent of device work.  An IDLE
    trace, in which no operation ran, is the whole span."""
    starts = [ev[0] for d in tr["devices"].values() for ev in _work(d)]
    ends = [ev[1] for d in tr["devices"].values() for ev in _work(d)]
    windows = [(s, e) for s, e, name in tr["spans"] if name == WINDOW_SPAN]
    if not starts:
        if not windows:
            raise ValueError("the trace holds neither device work nor a window span")
        return windows[0]
    for s, e in windows:
        if s < max(ends) and e > min(starts):
            return max(s, min(starts)), min(e, max(ends))
    return min(starts), max(ends)


def busy(tr: dict) -> tuple[float, float]:
    """``(busy_s, window_s)``: seconds in which an operation ran, averaged
    over the devices (0 in an idle trace), and the length of the traced
    window."""
    w0, w1 = window_of(tr)
    per_device = [
        sum(e - s for s, e in union(clip(_work(d), w0, w1)))
        for d in tr["devices"].values()
    ]
    return sum(per_device) / max(1, len(per_device)), w1 - w0


def _label(g0: float, g1: float, events) -> str | None:
    """The event name that covers most of the gap, if it covers half of it.
    A name's cover is the UNION of its events: the runtime emits two nested
    ``PjitFunction(step_fn)`` a launch, and their sum would pass for half a
    gap of which they cover a quarter."""
    by_name: dict[str, list] = {}
    for s, e, name in events:
        if e > g0 and s < g1:
            by_name.setdefault(name, []).append((max(s, g0), min(e, g1)))
    if not by_name:
        return None
    cover = {name: sum(e - s for s, e in union(ivs)) for name, ivs in by_name.items()}
    best = max(cover, key=cover.get)
    return best if cover[best] >= 0.5 * (g1 - g0) else None


def idle_gaps(tr: dict, top: int = 10) -> list[list]:
    """The idle time of the first device by what the host was doing: the
    benchmark span that covers most of each gap, else the runtime's host
    event that does, else ``unattributed``; gaps under ``GAP_FLOOR_S`` are
    pooled."""
    w0, w1 = window_of(tr)
    first = _first_device(tr)
    merged = union(clip(_work(first), w0, w1))
    edges = [w0] + [t for iv in merged for t in iv] + [w1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    spans = [s for s in tr["spans"] if s[2] != WINDOW_SPAN]
    totals: dict[str, float] = {}
    for g0, g1 in gaps:
        if g1 - g0 < GAP_FLOOR_S:
            label = "short_gaps__not_labelled"
        else:
            label = (_label(g0, g1, spans) or _label(g0, g1, tr.get("host", ()))
                     or "unattributed")
        totals[label] = totals.get(label, 0.0) + (g1 - g0)
    return [[k, v] for k, v in sorted(totals.items(), key=lambda kv: -kv[1])[:top]]


def device_ops(tr: dict, top: int = 10) -> list[list]:
    """The operations of the first device that took most time."""
    w0, w1 = window_of(tr)
    first = _first_device(tr)
    totals: dict[str, float] = {}
    for s, e, name in clip(first["ops"], w0, w1):
        totals[name] = totals.get(name, 0.0) + (e - s)
    return [[k, v] for k, v in sorted(totals.items(), key=lambda kv: -kv[1])[:top]]


def module_events(tr: dict, pattern: str) -> list[tuple[float, float, str]]:
    """Launches on the first device of the program whose name holds
    ``pattern``; with several candidates, the one launched most often."""
    first = _first_device(tr)
    by_name: dict[str, list] = {}
    for ev in first["modules"]:
        if pattern in ev[2]:
            by_name.setdefault(ev[2], []).append(ev)
    if not by_name:
        return []
    return sorted(max(by_name.values(), key=len))


def op_seconds(tr: dict, pattern: str) -> tuple[float, int]:
    """Total device seconds and number of the first device's operations
    whose name holds ``pattern``."""
    w0, w1 = window_of(tr)
    first = _first_device(tr)
    hit = [e - s for s, e, name in clip(first["ops"], w0, w1) if pattern in name]
    return sum(hit), len(hit)
