"""The trace reduction, on a small trace recorded on a TPU v5e
(``tools/record_fixture.py``: five launches of one small program, 30 ms
apart, each wait under ``bench.input_wait``) and on hand-made intervals."""

import os
import statistics
import time

import pytest

from benchmarks.harness import manifest, report, trace

FIXTURE = os.path.join(os.path.dirname(__file__), "fixture.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
    return trace.load(FIXTURE)


def test_recorded_trace_planes_and_spans(recorded):
    assert list(recorded["devices"]) == ["/device:TPU:0"]
    dev = recorded["devices"]["/device:TPU:0"]
    assert len(dev["modules"]) == 5 and len(dev["ops"]) == 15
    names = [n for _s, _e, n in recorded["spans"]]
    assert names.count("bench.window") == 1 and names.count("bench.input_wait") == 5
    assert any(n.startswith("PjitFunction(fixture_step)") for _s, _e, n in recorded["host"])


def test_recorded_trace_reduces_to_busy_idle_and_cadence(recorded):
    busy_s, window_s = trace.busy(recorded)
    # bench.window runs 0.0477..0.2052 s; device work ends at 0.1728 s.
    assert window_s == pytest.approx(0.1251, abs=1e-3)
    assert 5e-6 < busy_s < 5e-5          # four launches of ~3 us inside it
    launches = trace.module_events(recorded, "fixture_step")
    starts = [s for s, _e, _n in launches]
    gap = statistics.median(b - a for a, b in zip(starts, starts[1:]))
    assert 0.030 < gap < 0.033           # sleep(0.03) plus the turn-round
    gaps = trace.idle_gaps(recorded)
    assert gaps[0][0] == "bench.input_wait"
    assert gaps[0][1] == pytest.approx(window_s - busy_s, rel=1e-3)
    ops = trace.device_ops(recorded)
    assert ops[0][0] == "fusion bf16[512,512]"
    assert sum(v for _k, v in ops) == pytest.approx(busy_s, rel=0.01)  # launches lap their ops
    seconds, count = trace.op_seconds(recorded, "fusion")
    assert count == 4 and seconds == pytest.approx(ops[0][1])


def test_recorded_trace_through_the_readers(recorded):
    ev = {"trace": recorded}
    idle = manifest.reader("trace_idle_share")(ev)
    assert 99.9 < idle < 100.0
    step_ms = manifest.reader("trace_module_interval")(ev, module="fixture_step")
    assert 30.0 < step_ms < 33.0
    assert manifest.reader("trace_module_interval")(ev, module="no_such_program") is None


def test_short_names():
    long = ("%fusion.26 = bf16[8,50304]{1,0:T(8,128)(2,1)} fusion(f32[2048,50304]{1,0} "
            "%params__head____kernel__.1), kind=kOutput")
    assert trace.short_name(long) == "fusion.26 bf16[8,50304]"
    assert trace.short_name("%x.1 = (bf16[8,16]{1,0}, bf16[2]{0}) fusion(%a)") == "x.1 (bf16[8,16],...)"
    assert trace.short_name("jit_step_fn(123)") == "jit_step_fn(123)"


def _made(ops, spans=(), host=(), second=None):
    devices = {"/device:TPU:0": {"ops": ops, "modules": []}}
    if second is not None:
        devices["/device:TPU:1"] = {"ops": second, "modules": []}
    return {"devices": devices, "spans": sorted(spans), "host": sorted(host)}


def test_union_merges_overlap_and_touching():
    assert trace.union([(0, 1), (0.5, 2), (2, 3), (5, 6)]) == [(0, 3), (5, 6)]


def test_busy_is_the_union_clipped_to_the_window_and_averaged_over_devices():
    tr = _made(
        ops=[(0.0, 2.0, "a"), (1.0, 3.0, "b"), (8.0, 12.0, "c")],
        spans=[(1.0, 11.0, "bench.window")],
        second=[(1.0, 2.0, "a")],
    )
    busy_s, window_s = trace.busy(tr)
    assert trace.window_of(tr) == (1.0, 11.0)
    assert window_s == pytest.approx(10.0)
    assert busy_s == pytest.approx(((3.0 - 1.0) + (11.0 - 8.0) + 1.0) / 2)


def test_idle_gaps_take_the_benchmarks_span_then_the_runtimes_event_then_nothing():
    tr = _made(
        ops=[(0.0, 1.0, "a"), (2.0, 3.0, "a"), (4.0, 5.0, "a"), (6.0, 7.0, "a")],
        spans=[(0.0, 7.0, "bench.window"), (1.0, 1.9, "bench.input_wait")],
        host=[(1.0, 2.0, "PjitFunction(f)"), (3.0, 3.8, "PjitFunction(f)"),
              (5.0, 5.2, "np.asarray(jax.Array)")],
    )
    assert dict(trace.idle_gaps(tr)) == pytest.approx({
        "bench.input_wait": 1.0, "PjitFunction(f)": 1.0, "unattributed": 1.0})


def test_the_window_span_is_cut_to_the_extent_of_device_work():
    tr = _made(ops=[(2.0, 3.0, "a"), (5.0, 6.0, "b")], spans=[(0.0, 20.0, "bench.window")])
    assert trace.window_of(tr) == (2.0, 6.0)


def test_without_a_window_span_the_window_is_the_extent_of_device_work():
    tr = _made(ops=[(2.0, 3.0, "a"), (5.0, 6.0, "b")])
    assert trace.window_of(tr) == (2.0, 6.0)
    assert trace.busy(tr) == (2.0, 4.0)
    with pytest.raises(ValueError):
        trace.window_of(_made(ops=[]))


def test_nested_events_of_one_name_cover_a_gap_once():
    # The runtime emits two nested PjitFunction(step_fn) a launch: together
    # they cover 0.3 of this gap, not 0.6.
    tr = _made(
        ops=[(0.0, 1.0, "a"), (2.0, 3.0, "a")],
        host=[(1.0, 1.3, "PjitFunction(step_fn)"), (1.05, 1.3, "PjitFunction(step_fn)")],
    )
    assert dict(trace.idle_gaps(tr)) == pytest.approx({"unattributed": 1.0})
    tr["host"].append((1.3, 1.6, "PjitFunction(step_fn)"))
    assert dict(trace.idle_gaps(tr)) == pytest.approx({"PjitFunction(step_fn)": 1.0})


def test_an_idle_trace_is_reported_as_idle():
    # No operation in the traced seconds: the window is the span, nothing ran.
    tr = {"devices": {}, "spans": [(10.0, 14.0, "bench.window")],
          "host": [(9.0, 15.0, "decode/park")]}
    assert trace.window_of(tr) == (10.0, 14.0)
    assert trace.busy(tr) == (0.0, 4.0)
    assert trace.module_events(tr, "jit_step_fn") == []
    assert trace.device_ops(tr) == []
    assert trace.op_seconds(tr, "fusion") == (0, 0)
    assert trace.idle_gaps(tr) == [["decode/park", 4.0]]


@pytest.fixture(scope="module")
def recorded_idle(tmp_path_factory):
    """A trace recorded here, on the CPU, beside the chip's fixture: no TPU
    plane, so no device work at all, under a ``bench.window`` span."""
    import jax

    log_dir = str(tmp_path_factory.mktemp("idle_trace"))
    trace.start(log_dir)
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation("decode/park"):
            time.sleep(0.05)
    jax.profiler.stop_trace()
    return trace.load(trace.find_xplane(log_dir))


def test_a_recorded_idle_trace_gives_a_result_line_and_no_step_metric(
        recorded_idle, monkeypatch, capsys):
    assert recorded_idle["devices"] == {}
    busy_s, window_s = trace.busy(recorded_idle)
    assert busy_s == 0.0 and 0.05 <= window_s < 0.5
    monkeypatch.setattr(manifest, "peak_for", lambda kind: {"hbm_bytes_per_s": 1.0})
    cell = manifest.Cell("cgpt13b-serve-chat")
    outcome = {"correct": True, "attempted": 38, "failed": 0, "end_to_end": {},
               "evidence": {"cell": cell, "memory": [], "trace": recorded_idle}}
    line = report.result(cell, outcome, traced=True)
    assert line["device"]["busy_s"] == 0.0 and line["device"]["window_s"] == window_s
    assert line["metrics"] == {"decode_device_idle_share": {"value": 100.0, "unit": "%"}}
    assert line["breakdown"]["device_ops"] == []
    assert sum(s for _name, s in line["breakdown"]["idle_gaps"]) == pytest.approx(window_s)
    assert "the traced window was idle" in capsys.readouterr().out
