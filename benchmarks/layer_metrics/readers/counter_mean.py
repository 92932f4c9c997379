"""scale x sum(delta num_i) / delta den, from ``server.stats()`` read at the
window's two ends; without ``den`` the plain scale x sum(delta num_i).
``num`` is one key or a list; a key ``"registry:<name>"`` names an entry of
the nested ``registry`` table (the process's ``telemetry`` registry).
Nothing where a key is missing at either end or ``delta den`` is 0."""


def _value(stats, key):
    table, sep, name = key.partition(":")
    if sep:
        stats, key = stats.get(table, {}), name
    return stats.get(key)


def _delta(counters, key):
    a, b = _value(counters["start"], key), _value(counters["end"], key)
    return None if a is None or b is None else b - a


def read(evidence, *, num, den=None, scale=1.0):
    c = evidence.get("counters")
    if not c:
        return None
    deltas = [_delta(c, k) for k in ([num] if isinstance(num, str) else num)]
    if any(d is None for d in deltas):
        return None
    if den is None:
        return scale * sum(deltas)
    d_den = _delta(c, den)
    return scale * sum(deltas) / d_den if d_den else None
