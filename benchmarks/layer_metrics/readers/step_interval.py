"""Median interval, in ms, between consecutive step completions."""

import statistics


def read(evidence):
    done = (evidence.get("series") or {}).get("step_done_s")
    if not done or len(done) < 3:
        return None
    return 1e3 * statistics.median(b - a for a, b in zip(done, done[1:]))
