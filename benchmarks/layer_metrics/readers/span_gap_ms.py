"""Idle milliseconds of the first device per launch of ``module`` that lie
under the program's own host span(s) ``span`` (one name or a list): the
device's idle gaps in the traced window, as ``trace.idle_gaps`` takes them,
each shared out by OVERLAP with the spans' events - not given whole to the
event that covers most of it - and the sum divided by the launches in the
window.  Spans that never overlap each other (the program's are leaves) sum
to at most the idle time per launch."""

from benchmarks.harness import trace


def read(evidence, *, span, module):
    tr = evidence.get("trace")
    if not tr:
        return None
    names = {span} if isinstance(span, str) else set(span)
    events = trace.union(ev for ev in tr.get("host", ()) if ev[2] in names)
    if not events:
        return None
    w0, w1 = trace.window_of(tr)
    launches = trace.clip(trace.module_events(tr, module), w0, w1)
    if len(launches) < 3:
        return None
    first = tr["devices"][sorted(tr["devices"])[0]]
    busy = trace.union(trace.clip(first["ops"] + first["modules"], w0, w1))
    edges = [w0] + [t for iv in busy for t in iv] + [w1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)]
    under = sum(
        min(e, g1) - max(s, g0)
        for g0, g1 in gaps for s, e in events if e > g0 and s < g1
    )
    return 1e3 * under / len(launches)
