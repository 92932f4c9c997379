"""Median interval, in ms, between consecutive launches of one jitted
program on the device (device time plus the host's turn-round; a pause with
nothing to do falls out of a median)."""

import statistics

from benchmarks.harness import trace


def read(evidence, *, module):
    tr = evidence.get("trace")
    if not tr:
        return None
    starts = [s for s, _e, _n in trace.module_events(tr, module)]
    if len(starts) < 3:
        return None
    return 1e3 * statistics.median(b - a for a, b in zip(starts, starts[1:]))
