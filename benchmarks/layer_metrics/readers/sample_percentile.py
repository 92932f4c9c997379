"""A percentile of a series the clients or the loop stamped."""

from benchmarks.harness import stats


def read(evidence, *, series, q):
    values = (evidence.get("series") or {}).get(series)
    return stats.percentile(values, q) if values else None
