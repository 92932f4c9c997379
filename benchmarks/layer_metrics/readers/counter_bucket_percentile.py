"""The ``q``-th percentile of what one ``telemetry.Histogram`` of the program
observed INSIDE the window, from ``server.stats()`` read at its two ends.

The program exports, beside a histogram's ``<name>_count``, the number of
observations it ever made at or under each edge of a fixed geometric grid
(``edges_per_octave`` to a doubling) as ``<name>/le/<edge>``, at every edge
whose own bucket is not empty; an edge left out reads what the edge before
it reads.  The end's counts less the start's are the window's own, bucket by
bucket.  A rank is answered with the geometric middle of its bucket, which
no value of that bucket is further from than half a bucket's width
(2.2 % at 16 edges to the octave); ranks are taken and interpolated as
``stats.percentile`` does.  ``hist`` is ``"registry:<name>"`` for a
histogram of the nested ``registry`` table.

Nothing where ``<name>_count`` is missing at either end (the parent has no
such histogram), where nothing was observed in the window, or where the
rank falls into the bucket that has no upper edge."""


def _table(stats, hist):
    table, sep, name = hist.partition(":")
    return (stats.get(table, {}), name) if sep else (stats, hist)


def _cumulative(stats, name):
    prefix = name + "/le/"
    return sorted((float(k[len(prefix):]), v) for k, v in stats.items()
                  if k.startswith(prefix))


def _at(cumulative, edge):
    """What a sparse cumulative table reads at ``edge``."""
    below = [v for e, v in cumulative if e <= edge]
    return below[-1] if below else 0


def read(evidence, *, hist, q, edges_per_octave):
    c = evidence.get("counters")
    if not c:
        return None
    (start, name), (end, _) = _table(c["start"], hist), _table(c["end"], hist)
    if f"{name}_count" not in start or f"{name}_count" not in end:
        return None
    cum0, cum1 = _cumulative(start, name), _cumulative(end, name)
    edges = sorted({e for e, _v in cum0 + cum1})
    window = [(e, _at(cum1, e) - _at(cum0, e)) for e in edges]  # still cumulative
    n = window[-1][1] if window else 0
    if n <= 0:
        return None
    half = 2.0 ** (-0.5 / edges_per_octave)

    def value_at(rank):
        edge = next(e for e, upto in window if upto > rank)
        return None if edge == float("inf") else edge * half

    pos = (n - 1) * q / 100.0
    lo = int(pos)
    a, b = value_at(lo), value_at(min(lo + 1, n - 1))
    if a is None or b is None:
        return None
    return a + (b - a) * (pos - lo)
