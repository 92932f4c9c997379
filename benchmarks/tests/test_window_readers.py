"""The two readers of PR 36 on hand-built evidence - ``counter_bucket_percentile``
(a window's percentile from a histogram's counts at the window's two ends)
and ``launch_join`` (each launch of the step followed from its dispatch to
its read) - and the rehearsal of every serve cell, which has to name each
metric that reads the program's new counters among those a chip run would
report.  (``test_inside_readers.py`` holds the readers that were there.)"""

import time

import pytest

from benchmarks.harness import manifest, stats, trace

bucket_percentile = manifest.reader("counter_bucket_percentile")
launch_join = manifest.reader("launch_join")

M = manifest.benchmark()
NEW = ("engine_itl_p95_ms", "engine_itl_max_ms", "batch_engine_itl_max_ms",
       "engine_ttft_p50_ms", "engine_ttft_p95_ms", "seat_wait_p95_ms",
       "host_loop_ms", "batch_host_loop_ms", "host_paced_step_share",
       "batch_host_paced_step_share", "step_start_lag_ms",
       "batch_step_start_lag_ms", "step_read_lag_ms", "batch_step_read_lag_ms")
#: Those of them that need no trace: a run on the CPU reads them too.
FROM_COUNTERS = tuple(n for n in NEW if "_lag_" not in n)

HIST = {"hist": "registry:decode/itl_ms", "edges_per_octave": 16}


def _edge(k: int) -> str:
    """The program's spelling of the edge ``2**(k/16)``."""
    return f"{2.0 ** (k / 16):.6g}"


def _registry(count, le):
    reg = {"decode/itl_ms_count": count, "decode/itl_ms_p50": 1.0}
    reg.update({f"decode/itl_ms/le/{e}": v for e, v in le.items()})
    return {"registry": reg}


def _counters(start, end):
    return {"counters": {"start": start, "end": end}}


def test_bucket_percentile_reads_the_windows_own_observations():
    """Before the window: 100 observations at 2**3 ms and one stall of 2**10.
    In it: 90 more at 2**3, 9 in the bucket above 2**4 and one of 2**5.8
    (a bucket that was empty at the start, so its key appears at the end
    only).  The stall from before the window is in no percentile."""
    b8, b16, bmax, bstall = _edge(48), _edge(65), _edge(93), _edge(160)
    start = _registry(101, {b8: 100, bstall: 101})
    end = _registry(201, {b8: 190, b16: 199, bmax: 200, bstall: 201})
    ev = _counters(start, end)
    mid = 2.0 ** (-1 / 32)  # a bucket's geometric middle, from its upper edge
    assert bucket_percentile(ev, q=50, **HIST) == pytest.approx(8.0 * mid)
    # Ranks 0-89 at 8, 90-98 above 16, 99 at the top: p95 is rank 94.05.
    assert bucket_percentile(ev, q=95, **HIST) == pytest.approx(float(b16) * mid, rel=1e-5)
    assert bucket_percentile(ev, q=100, **HIST) == pytest.approx(float(bmax) * mid, rel=1e-5)
    # Between two ranks of different buckets it interpolates as stats.percentile.
    want = stats.percentile([8.0 * mid] * 90 + [float(b16) * mid] * 9 + [float(bmax) * mid], 99.5)
    assert bucket_percentile(ev, q=99.5, **HIST) == pytest.approx(want, rel=1e-5)
    # A top-level histogram is named without a table.
    flat = _counters(start["registry"], end["registry"])
    assert bucket_percentile(flat, q=50, hist="decode/itl_ms", edges_per_octave=16) == \
        pytest.approx(8.0 * mid)


def test_bucket_percentile_finds_nothing_where_there_is_nothing_to_read():
    b8 = _edge(48)
    full = _registry(10, {b8: 10})
    more = _registry(30, {b8: 30})
    assert bucket_percentile({}, q=50, **HIST) is None
    # The parent's registry: no such histogram at either end, or at one.
    bare = {"registry": {"decode/fetch/ns": 5}}
    assert bucket_percentile(_counters(bare, bare), q=50, **HIST) is None
    assert bucket_percentile(_counters(bare, more), q=50, **HIST) is None
    assert bucket_percentile(_counters(full, bare), q=50, **HIST) is None
    assert bucket_percentile(_counters({}, {}), q=50, **HIST) is None
    # Nothing observed inside the window: no percentile of nothing.
    assert bucket_percentile(_counters(full, full), q=50, **HIST) is None
    # An observation past the last edge has no value to give.
    over = _registry(31, {b8: 30, "inf": 31})
    assert bucket_percentile(_counters(full, over), q=50, **HIST) is not None
    assert bucket_percentile(_counters(full, over), q=100, **HIST) is None


# -- launch_join ----------------------------------------------------------------

JOIN = {"module": "jit_step_fn", "dispatch": "decode/dispatch", "fetch": "decode/fetch"}


def _trace(launches, host, other=()):
    """Launches of ``jit_step_fn`` (and ``other`` device work) in ms under a
    window that the harness cuts to the extent of device work."""
    ms = 1e-3
    mods = [(s * ms, e * ms, "jit_step_fn(1)") for s, e in launches]
    mods += [(s * ms, e * ms, "jit_prefill_fn(2)") for s, e in other]
    ops = [(s, e, "fusion.1 bf16[8]") for s, e, _n in mods]
    return {
        "devices": {"/device:TPU:0": {"ops": sorted(ops), "modules": sorted(mods)}},
        "spans": [(0.0, 1.0, trace.WINDOW_SPAN)],
        "host": sorted((s * ms, e * ms, n) for s, e, n in host),
    }


def _run_ahead(n=6, step=10.0, lag=2.0):
    """The engine in its steady state: step k runs ``[10k, 10k + 10)``; the
    host dispatches k + 1 one ms into step k and then reads step k, which
    it has ``lag`` ms after the step ended.  Step 0 is dispatched to a free
    device, which begins it as the dispatch returns."""
    launches = [(step * k, step * (k + 1)) for k in range(n)]
    host = []
    for k in range(n):
        host.append((step * k - 9.0, step * k - 8.5, "decode/dispatch") if k
                    else (-0.5, 0.0, "decode/dispatch"))
        host.append((step * k + 1.5, step * (k + 1) + lag, "decode/fetch"))
    return launches, host


def test_launch_join_of_a_device_that_is_never_free():
    launches, host = _run_ahead()
    ev = {"trace": _trace(launches, host)}
    # Every launch began the instant the one before it ended: no start lag.
    assert launch_join(ev, what="start", **JOIN) == pytest.approx(0.0, abs=1e-9)
    assert launch_join(ev, what="read", **JOIN) == pytest.approx(2.0)


def test_launch_join_tells_a_starved_start_from_a_late_dispatch():
    """Two idle gaps of 4 ms before launches 2 and 4.  Before launch 2 the
    host had dispatched long since and the device was free: all 4 ms are
    start lag.  Before launch 4 the dispatch itself ended 1 ms before the
    launch began: 1 ms is the runtime's, 3 ms the loop's."""
    launches = [(0, 10), (10, 20), (24, 34), (34, 44), (48, 58), (58, 68)]
    host = [(-0.5, 0, "decode/dispatch"), (1, 12, "decode/fetch"),
            (2, 2.5, "decode/dispatch"), (12.5, 22, "decode/fetch"),
            (13, 13.5, "decode/dispatch"), (22.5, 36, "decode/fetch"),
            (25, 25.5, "decode/dispatch"), (36.5, 46, "decode/fetch"),
            (46.5, 47, "decode/dispatch"), (47.2, 60, "decode/fetch"),
            (49, 49.5, "decode/dispatch"), (60.5, 70, "decode/fetch")]
    ev = {"trace": _trace(launches, host)}
    assert launch_join(ev, what="start", **JOIN) == pytest.approx((4.0 + 1.0) / 6)
    assert launch_join(ev, what="read", **JOIN) == pytest.approx(2.0)
    # A chunk that fills the first gap: the device was at work until launch 2
    # began, so nothing of that gap is a lag.
    ev = {"trace": _trace(launches, host, other=[(20, 24)])}
    assert launch_join(ev, what="start", **JOIN) == pytest.approx(1.0 / 6)


def test_launch_join_of_a_host_that_reads_late():
    """A host-paced engine: each step is long done when the host comes to
    read it, so the read lag is the read itself (0.3 ms), not the wait."""
    launches = [(20.0 * k, 20.0 * k + 5) for k in range(5)]
    host = []
    for k in range(5):
        host.append((20.0 * k - 1, 20.0 * k - 0.2, "decode/dispatch"))
        host.append((20.0 * k + 12, 20.0 * k + 12.3, "decode/fetch"))
    ev = {"trace": _trace(launches, host)}
    assert launch_join(ev, what="read", **JOIN) == pytest.approx(0.3)
    # The device began 0.2 ms after each dispatch returned.
    assert launch_join(ev, what="start", **JOIN) == pytest.approx(0.2)


def test_launch_join_does_not_guess():
    launches, host = _run_ahead()
    assert launch_join({}, what="read", **JOIN) is None
    assert launch_join({"trace": None}, what="read", **JOIN) is None
    # An idle trace, the runtime's events alone, another program's launches.
    idle = {"devices": {}, "spans": [(0.0, 1.0, trace.WINDOW_SPAN)], "host": []}
    assert launch_join({"trace": idle}, what="read", **JOIN) is None
    runtime = [(s, e, "PjitFunction(step_fn)") for s, e, _n in host]
    assert launch_join({"trace": _trace(launches, runtime)}, what="read", **JOIN) is None
    other = dict(JOIN, module="jit_other")
    assert launch_join({"trace": _trace(launches, host)}, what="read", **other) is None
    # At the window's edges two launches may lack a span: the first one's
    # dispatch predates the trace, the last one's read outlasts it.
    edges = [h for h in host if h != host[0] and h != host[-1]]
    ev = {"trace": _trace(launches, edges)}
    assert launch_join(ev, what="read", **JOIN) == pytest.approx(2.0)
    # A trace that lost every other dispatch pairs two launches with one
    # span: nothing, not a number from the half that happened to pair.
    lossy = [h for i, h in enumerate(host) if h[2] != "decode/dispatch" or i % 4 == 0]
    assert launch_join({"trace": _trace(launches, lossy)}, what="start", **JOIN) is None
    with pytest.raises(ValueError, match="start.*read"):
        launch_join({"trace": _trace(launches, host)}, what="both", **JOIN)


# -- the manifest and the rehearsal ---------------------------------------------

def test_the_fourteen_metrics_are_entries_and_files_of_their_families():
    by_name = {p["name"]: p for p in M["per_layer"]}
    chat = ["cgpt13b-serve-chat", "jamba2-3b-serve-chat-busy", "longcat-omni-serve-longctx"]
    batch = ["cgpt13b-serve-batch", "deepseek-v2-serve-gen"]
    assert [p["name"] for p in M["per_layer"]][-len(NEW):] == list(NEW)
    for name in NEW:
        p = by_name[name]
        assert p["workloads"] == (batch if name.startswith("batch_") else chat)
        assert p["moves"] == ("served_tokens_per_s" if name.startswith("batch_") else "itl_p95_ms")
        spec = manifest.layer_metric(name)
        twin = manifest.layer_metric(name.removeprefix("batch_"))
        assert spec == twin, "a batch_ metric reads what its twin reads"
    # host_loop_ms and host_paced_step_share are data files of old readers.
    assert manifest.layer_metric("host_loop_ms")["reader"] == "counter_mean"
    assert manifest.layer_metric("host_paced_step_share")["reader"] == "counter_ratio"


SERVE_CELLS = [w["name"] for w in M["workloads"]
               if manifest.Cell(w["name"]).path == "serve"]


@pytest.mark.parametrize("workload", SERVE_CELLS)
def test_a_rehearsal_names_every_metric_that_reads_the_new_counters(workload):
    """The rehearsal's own control flow (``rehearse.py``: the cell shrunk,
    its runner, the child-process generator) and then what a traced chip run
    does with the evidence: every new metric of the cell that needs no trace
    is among the names it would report.  Names, never a value."""
    from benchmarks import rehearse
    from benchmarks.harness import serve_cell

    cell = rehearse.shrink(manifest.Cell(workload))
    out = serve_cell.run(cell, 11, 3.0, False, time.monotonic())
    assert out["failed"] == 0 and out["attempted"] > 0
    names = set(manifest.read_per_layer(cell, out["evidence"]))
    mine = {p["name"] for p in cell.per_layer if p["name"] in FROM_COUNTERS}
    assert mine and mine <= names, sorted(mine - names)
    # The window's counts of the three histograms are the counters' deltas,
    # to within what two steps emit: ``stats()`` reads the batcher's sums
    # first and the registry last, a step or so apart at either end.
    c = out["evidence"]["counters"]
    skew = 2 * c["end"]["decode_slots"]

    def delta(key):
        table, sep, name = key.partition(":")
        a, b = (c["start"][table], c["end"][table]) if sep else (c["start"], c["end"])
        return b[name if sep else key] - a[name if sep else key]

    for hist, counted in (
        ("itl_ms", delta("decode_emitted") - delta("decode_first_tokens")),
        ("ttft_ms", delta("decode_first_tokens")),
        ("seat_wait_ms", delta("decode_seated")),
    ):
        assert abs(delta(f"registry:decode/{hist}_count") - counted) <= skew, hist
