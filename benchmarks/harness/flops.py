"""What a roofline share is counted from that no family owns: how many cache
rows the seated sessions hold, from the clients' stamps.

A family's own operations and bytes from shapes (the least the algorithm
needs; a multiply-add is two operations; recomputation is not counted) sit
with it, in ``families/<model>/<path>.py``.
"""

from __future__ import annotations

import statistics


def mean_cache_rows(records: list, requests: list, t_a: float, t_b: float) -> float:
    """Cache rows held by the sessions seated between ``t_a`` and ``t_b``,
    averaged over that time, from the clients' stamps: a session has written
    prompt + j rows when its j-th token arrives, and fed its prompt at one
    row a step before the first."""
    prompt_len = {r["id"]: len(r["prompt"]) for r in requests}
    gaps = [b - a for r in records for a, b in zip(r["times"], r["times"][1:]) if b > a]
    if not gaps:
        return 0.0
    step = statistics.median(gaps)
    points = [t_a + (t_b - t_a) * (i + 0.5) / 20 for i in range(20)]
    total = 0.0
    for r in records:
        if not r["times"]:
            continue
        p = prompt_len[r["id"]]
        first, last = r["times"][0], r["times"][-1]
        for t in points:
            if first - p * step <= t <= last:
                total += p + (t - first) / step
    return total / len(points)
