import json
import os

import numpy as np
import pytest

from benchmarks.harness import manifest, traffic

MIXES = sorted(
    f[:-5] for f in os.listdir(os.path.join(manifest.BENCH_DIR, "traffic"))
    if f.endswith(".json")
)


def _mix(name):
    with open(os.path.join(manifest.BENCH_DIR, "traffic", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", [m for m in MIXES if _mix(m)["kind"].startswith("serve")])
def test_same_seed_same_schedule_other_seed_same_work_other_order(name):
    mix = _mix(name)
    horizon = 15.0 + 40.0 + 5.0
    a = traffic.serve_schedule(mix, 50257, 2**31 + 11, horizon)
    b = traffic.serve_schedule(mix, 50257, 2**31 + 11, horizon)
    c = traffic.serve_schedule(mix, 50257, 12, horizon)
    assert a == b
    assert a != c
    if mix["kind"] == "serve-closed":
        assert sorted(r["n"] for r in a) == sorted(r["n"] for r in c)
        assert sorted(len(r["prompt"]) for r in a) == sorted(len(r["prompt"]) for r in c)
    if mix["kind"] == "serve-open":
        # Any stretch of one period holds each request of the ring once: the
        # same work under every seed, from another point of the same order.
        period = mix["arrivals"]["period_s"]
        n = round(mix["arrivals"]["rate_per_s"] * period)
        longer = traffic.serve_schedule(mix, 50257, 12, 15.0 + 2 * period)
        for s in (a, c, longer):
            assert s[0]["due_s"] == 0.0
        for w0 in (15.0, 3.3):
            in_window = lambda s: [r for r in s if w0 <= r["due_s"] < w0 + period]
            both = [in_window(traffic.serve_schedule(mix, 50257, seed, w0 + period + 5.0))
                    for seed in (2**31 + 11, 12)]
            assert len(both[0]) == len(both[1]) == n
            for key in (lambda r: r["n"], lambda r: len(r["prompt"])):
                assert sorted(map(key, both[0])) == sorted(map(key, both[1]))
        # The ring is the mix's own: a longer run plays the same requests on.
        assert [(r["n"], len(r["prompt"]), r["due_s"]) for r in longer[: len(c)]] == \
               [(r["n"], len(r["prompt"]), r["due_s"]) for r in c]
        assert a[-1]["due_s"] >= horizon - 5.0
        pairs = lambda s: {(len(x["prompt"]), len(y["prompt"])) for x, y in zip(s, s[1:])}
        assert len(pairs(a) & pairs(c)) >= min(len(a), n) - 3   # the same neighbours
    for r in a:
        assert mix["prompt_len"]["min"] <= len(r["prompt"]) <= mix["prompt_len"]["max"]
        assert mix["output_len"]["min"] <= r["n"] <= mix["output_len"]["max"]
        assert 0 <= min(r["prompt"]) and max(r["prompt"]) < 50257


def test_lognormal_set_has_the_stated_median():
    spec = {"dist": "lognormal", "median": 160, "sigma": 0.7, "min": 16, "max": 768}
    v = traffic.lengths(spec, 101)
    assert abs(int(np.median(v)) - 160) <= 1
    assert v.min() >= 16 and v.max() <= 768


def test_images_name_their_rows():
    spec = {"n": 32, "image_size": 8, "channels": 3, "num_classes": 10}
    a, b = traffic.images(spec, 5), traffic.images(spec, 5)
    assert np.array_equal(a["image"], b["image"]) and np.array_equal(a["label"], b["label"])
    assert not np.array_equal(a["image"], traffic.images(spec, 6)["image"])
    rows = np.array([31, 0, 7])
    assert np.array_equal(traffic.image_rows(a["image"][rows], 32), rows)
