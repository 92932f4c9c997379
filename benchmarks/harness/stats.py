"""The arithmetic that turns stamps into metrics."""

from __future__ import annotations


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile (numpy's default), q in [0, 100]."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of nothing")
    pos = (len(v) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def window_rate(done_times: list[float], units_per_step: float) -> tuple[float, float]:
    """Units per second over the whole window: ``done_times[0]`` opens it,
    every later entry is one step's completion and the last one closes it,
    so all the work is taken over all the time.  Returns ``(rate, window_s)``."""
    steps = len(done_times) - 1
    if steps < 1:
        raise ValueError("no step completed in the window")
    window_s = done_times[-1] - done_times[0]
    return steps * units_per_step / window_s, window_s


def tokens_in_window(token_times, w0: float, w1: float) -> int:
    """Tokens whose receipt falls in ``[w0, w1)``."""
    return sum(1 for t in token_times if w0 <= t < w1)


def gaps_ending_in_window(per_request_times, w0: float, w1: float) -> list[float]:
    """Gaps between consecutive tokens of one request, for every gap whose
    later token falls in the window."""
    out = []
    for times in per_request_times:
        for a, b in zip(times, times[1:]):
            if w0 <= b < w1:
                out.append(b - a)
    return out
