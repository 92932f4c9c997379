"""Device time, in ms, of the operations whose name holds ``op`` that ran
INSIDE launches of the program whose name holds ``module``, per launch of
that program, over the traced window: what a kernel that two programs call
(the decode step and the prefill chunk) costs each of them.  Nothing where
the program was not launched or none of its launches ran the operation."""

import bisect

from benchmarks.harness import trace


def read(evidence, *, op, module):
    tr = evidence.get("trace")
    if not tr or not tr["devices"]:
        return None
    w0, w1 = trace.window_of(tr)
    launches = trace.clip(trace.module_events(tr, module), w0, w1)
    if not launches:
        return None
    starts = [s for s, _e, *_ in launches]
    first = tr["devices"][sorted(tr["devices"])[0]]
    total, hits = 0.0, 0
    for s, e, name in trace.clip(first["ops"], w0, w1):
        if op not in name:
            continue
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < launches[i][1]:
            total += e - s
            hits += 1
    return 1e3 * total / len(launches) if hits else None
