"""Every launch of ``module`` in the traced window followed from the host's
dispatch to the host's read, on the trace's one clock.

A launch ``L`` on the first device is joined to the latest event of the
program's span ``dispatch`` that began before ``L`` began, and to the first
event of its span ``fetch`` that ends after ``L`` ended.  ``what``:

``start``  the START LAG, in ms, the mean over the joined launches:
           ``max(0, L.start - max(dispatch.end, end of the device's work
           before L))`` - how long the device stood free with ``L`` already
           dispatched.  That part of an idle gap is the runtime's (the
           launch or its uploads had not reached the device), not the
           program's loop.
``read``   the READ LAG, in ms, the median: ``fetch.end - max(L.end,
           fetch.start)`` - how long after the step was done and asked for
           the host had what it selected.

A launch with no such dispatch or fetch in the trace, or whose dispatch or
fetch another launch was joined to as well, is not joined.  At the window's
edges that is expected of two (the dispatch before the trace began, the read
after it ended).  With more than two, the trace's spans and launches do not
pair off one to one, and the reader returns nothing: it does not guess."""

import bisect
import collections
import itertools
import statistics

from benchmarks.harness import trace

EDGE_ALLOWANCE = 2


def _once(keys):
    """The keys that occur once; None is no key."""
    return {k for k, n in collections.Counter(keys).items()
            if n == 1 and k is not None}


def read(evidence, *, module, dispatch, fetch, what):
    tr = evidence.get("trace")
    if not tr or not tr.get("devices"):
        return None
    host = tr.get("host", ())
    dispatches = sorted(ev for ev in host if ev[2] == dispatch)
    fetches = sorted((ev for ev in host if ev[2] == fetch), key=lambda ev: ev[1])
    w0, w1 = trace.window_of(tr)
    launches = [ev for ev in trace.module_events(tr, module)
                if ev[0] >= w0 and ev[1] <= w1]
    if not dispatches or not fetches or len(launches) < 3:
        return None
    d_starts = [ev[0] for ev in dispatches]
    f_ends = [ev[1] for ev in fetches]
    first = tr["devices"][sorted(tr["devices"])[0]]
    work = sorted(first["ops"] + first["modules"])
    work_starts = [ev[0] for ev in work]
    ended = list(itertools.accumulate((ev[1] for ev in work), max))
    joins = []
    for s, e, _name in launches:
        d = bisect.bisect_left(d_starts, s) - 1  # the latest begun before s
        f = bisect.bisect_right(f_ends, e)  # the first that ends after e
        joins.append((s, e, d if d >= 0 else None, f if f < len(fetches) else None))
    d_once, f_once = _once(j[2] for j in joins), _once(j[3] for j in joins)
    joined = [j for j in joins if j[2] in d_once and j[3] in f_once]
    if len(launches) - len(joined) > EDGE_ALLOWANCE or len(joined) < 3:
        return None
    if what == "read":
        return 1e3 * statistics.median(
            fetches[f][1] - max(e, fetches[f][0]) for _s, e, _d, f in joined)
    if what != "start":
        raise ValueError(f"what is 'start' or 'read', not {what!r}")
    lags = []
    for s, _e, d, _f in joined:
        # Work that began before this launch did is not this launch's.
        k = bisect.bisect_left(work_starts, s)
        before = ended[k - 1] if k else float("-inf")
        lags.append(max(0.0, s - max(dispatches[d][1], before)))
    return 1e3 * statistics.fmean(lags)
