"""The one general traffic generator.

A mix is a data file of parameters.  Serving traffic is a FIXED REPLAY, not
a random process: every seed plays the same stratified set of lengths and
arrival gaps (the quantiles of the stated distributions) in the same cyclic
order from another starting point, with other tokens, so that two seeds ask
for the same work.  What it cannot show is how a server takes one draw of
the arrivals against another: burst-to-burst variation is not in it.
"""

from __future__ import annotations

import math
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()
#: The one order in which every run plays a mix's stratified set.
ORDER_SEED = 0


def _quantiles(n: int) -> list[float]:
    return [(i + 0.5) / n for i in range(n)]


def lengths(spec: dict, n: int) -> np.ndarray:
    """The stratified set of ``n`` lengths of a ``{"dist", ...}`` spec."""
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "lognormal":
        mu, sigma = math.log(spec["median"]), float(spec["sigma"])
        raw = [math.exp(mu + sigma * _NORMAL.inv_cdf(u)) for u in _quantiles(n)]
    elif spec["dist"] == "uniform":
        raw = [lo + u * (hi - lo + 1) - 0.5 for u in _quantiles(n)]
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(raw), lo, hi).astype(np.int64)


def arrival_gaps(spec: dict, n: int) -> np.ndarray:
    """The stratified set of ``n`` gaps between arrivals, in seconds."""
    rate = float(spec["rate_per_s"])
    if spec["gaps"] == "exponential":
        return np.asarray([-math.log(1.0 - u) / rate for u in _quantiles(n)])
    raise ValueError(f"unknown distribution of arrival gaps {spec['gaps']!r}")


def serve_schedule(traffic: dict, vocab: int, seed: int, horizon_s: float) -> list[dict]:
    """The requests of one run: ``{"id", "prompt", "n", "due_s" | "client"}``,
    enough of them to last ``horizon_s`` seconds.

    The lengths and gaps are one stratified set in ONE fixed order; the seed
    draws the tokens and where in that order the run starts.  Open loop: the
    set is a ring of the mix's own ``period_s``, played round and round, so
    that every stretch of one period of a server in steady state sees each of
    its requests once, whatever the seed - a run-to-run spread then is the
    system's, not the draw's.  Closed loop: the ring is dealt to the clients,
    longer than any can finish.
    """
    order = np.random.default_rng(ORDER_SEED)
    rng = np.random.default_rng(int(seed))
    if traffic["kind"] == "serve-open":
        period_s = float(traffic["arrivals"]["period_s"])
        n = max(1, round(traffic["arrivals"]["rate_per_s"] * period_s))
        gaps = order.permutation(arrival_gaps(traffic["arrivals"], n))
        at = np.cumsum(gaps * (period_s / gaps.sum()))
        count = math.ceil(n * horizon_s / period_s) + 1
    elif traffic["kind"] == "serve-closed":
        clients = int(traffic["clients"])
        least = traffic["prompt_len"]["min"] + traffic["output_len"]["min"]
        n = count = clients * max(2, math.ceil(horizon_s / (least * 0.02)))
    else:
        raise ValueError(f"not a serving mix: {traffic['kind']!r}")
    prompts = order.permutation(lengths(traffic["prompt_len"], n))
    outputs = order.permutation(lengths(traffic["output_len"], n))
    start = int(rng.integers(n))
    out = []
    for i in range(count):
        j = (start + i) % n
        req = {"id": i, "n": int(outputs[j]),
               "prompt": rng.integers(0, vocab, size=int(prompts[j])).tolist()}
        if traffic["kind"] == "serve-open":
            req["due_s"] = float(at[j] - at[start] + period_s * ((start + i) // n))
        else:
            req["client"] = i % clients
        out.append(req)
    return out


def images(spec: dict, seed: int) -> dict[str, np.ndarray]:
    """``spec["n"]`` float32 images and labels.  Pixel ``[i, 0, 0, 0]`` is
    the row's own index scaled, so that a batch names the rows it holds."""
    rng = np.random.default_rng(int(seed))
    n, s, ch = int(spec["n"]), int(spec["image_size"]), int(spec["channels"])
    x = rng.standard_normal((n, s, s, ch), dtype=np.float32)
    x[:, 0, 0, 0] = np.arange(n, dtype=np.float32) / n
    y = rng.integers(0, int(spec["num_classes"]), size=n).astype(np.int32)
    return {"image": x, "label": y}


def image_rows(batch_images: np.ndarray, n: int) -> np.ndarray:
    """The row indices written into a batch by :func:`images`."""
    return np.rint(np.asarray(batch_images[:, 0, 0, 0], np.float64) * n).astype(np.int64)
