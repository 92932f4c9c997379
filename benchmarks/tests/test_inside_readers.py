"""The two readers of the program's own spans and counters, on hand-built
evidence: ``span_gap_ms`` (idle time shared out by overlap) and
``counter_mean`` (deltas of ``server.stats()``, the nested registry too)."""

import pytest

from benchmarks.harness import manifest, trace

span_gap_ms = manifest.reader("span_gap_ms")
counter_mean = manifest.reader("counter_mean")


def _trace(host):
    """Three launches of ``jit_step_fn`` busy 0-10, 20-30, 40-50 ms under a
    ``bench.window`` of 0-60 ms, which the harness cuts to the extent of
    device work: idle gaps 10-20 and 30-40."""
    ms = 1e-3
    launches = [(0 * ms, 10 * ms, "jit_step_fn(1)"), (20 * ms, 30 * ms, "jit_step_fn(1)"),
                (40 * ms, 50 * ms, "jit_step_fn(1)")]
    ops = [(s, e, "fusion.1 bf16[8]") for s, e, _n in launches]
    return {
        "devices": {"/device:TPU:0": {"ops": ops, "modules": launches}},
        "spans": [(0.0, 60 * ms, trace.WINDOW_SPAN)],
        "host": sorted((s * ms, e * ms, n) for s, e, n in host),
    }


HOST = [
    # fetch waits through the first launch and 2 ms into the gap; then 3 ms
    # of select, 1 ms of emit and 4 ms of dispatch that ends as the second
    # launch starts.
    (1, 12, "decode/fetch"), (12, 15, "decode/select"), (15, 16, "decode/emit"),
    (16, 20, "decode/dispatch"), (16.5, 19.5, "PjitFunction(step_fn)"),
    # The second gap: fetch ends 1 ms in, dispatch covers its last 6 ms and
    # laps 2 ms over the launch.
    (21, 31, "decode/fetch"), (34, 42, "decode/dispatch"),
    # After the last launch: outside the window, not idle time.
    (52, 57, "decode/select"),
]


def test_span_gap_shares_idle_time_by_overlap_across_gaps():
    ev = {"trace": _trace(HOST)}
    # fetch: 2 ms of the first gap + 1 ms of the second, over 3 launches.
    assert span_gap_ms(ev, span="decode/fetch", module="jit_step_fn") == pytest.approx(3 / 3)
    # dispatch: 4 ms + 6 ms (its 2 ms over the launch are not idle time).
    assert span_gap_ms(ev, span="decode/dispatch", module="jit_step_fn") == pytest.approx(10 / 3)
    # A list of spans: select 3, emit 1.
    host = span_gap_ms(ev, span=["decode/select", "decode/emit", "decode/fill"],
                       module="jit_step_fn")
    assert host == pytest.approx(4 / 3)
    # Leaves never overlap, so the shares sum to no more than the idle time:
    # 17 of the 20 idle ms lie under a span of the program.
    busy_s, window_s = trace.busy(ev["trace"])
    assert (window_s - busy_s) * 1e3 == pytest.approx(20)
    # Winner-takes-all would have given the whole first gap to nobody
    # (no event covers half of it) and the second to dispatch.
    assert trace.idle_gaps(ev["trace"])[0][0] in ("unattributed", "decode/dispatch")


def test_span_gap_finds_nothing_without_spans_launches_or_trace():
    ev = {"trace": _trace(HOST)}
    assert span_gap_ms({}, span="decode/fetch", module="jit_step_fn") is None
    assert span_gap_ms({"trace": None}, span="decode/fetch", module="jit_step_fn") is None
    # The parent's trace: the runtime's events only.
    parent = {"trace": _trace([(16.5, 19.5, "PjitFunction(step_fn)")])}
    assert span_gap_ms(parent, span="decode/dispatch", module="jit_step_fn") is None
    assert span_gap_ms(ev, span="decode/park", module="jit_step_fn") is None
    assert span_gap_ms(ev, span="decode/fetch", module="jit_other") is None
    two = _trace(HOST)
    two["devices"]["/device:TPU:0"]["modules"].pop()
    assert span_gap_ms({"trace": two}, span="decode/fetch", module="jit_step_fn") is None


def test_span_gap_counts_only_launches_inside_the_window():
    tr = _trace(HOST)
    tr["spans"] = [(15e-3, 60e-3, trace.WINDOW_SPAN)]  # the first launch is outside
    assert span_gap_ms({"trace": tr}, span="decode/fetch", module="jit_step_fn") is None
    tr["devices"]["/device:TPU:0"]["modules"].append((58e-3, 59e-3, "jit_step_fn(1)"))
    # Window 15-59 ms: 5 ms of the first gap (1 of emit, 4 of dispatch), the
    # second gap whole, 50-58; three launches.
    got = span_gap_ms({"trace": tr}, span="decode/dispatch", module="jit_step_fn")
    assert got == pytest.approx((4 + 6) / 3)


def _counters(start, end):
    return {"counters": {"start": start, "end": end}}


def test_counter_mean_over_deltas_lists_and_the_nested_registry():
    ev = _counters(
        {"decode_steps": 100, "decode_seated": 4, "decode_seat_wait_ns": 1_000_000,
         "registry": {"decode/fetch/ns": 5_000_000, "decode/emit/ns": 1_000_000,
                      "jax/compiles": 3}},
        {"decode_steps": 300, "decode_seated": 9, "decode_seat_wait_ns": 11_000_000,
         "registry": {"decode/fetch/ns": 45_000_000, "decode/emit/ns": 5_000_000,
                      "jax/compiles": 3}},
    )
    assert counter_mean(ev, num="decode_seat_wait_ns", den="decode_seated",
                        scale=1e-6) == pytest.approx(2.0)
    assert counter_mean(
        ev, num=["registry:decode/fetch/ns", "registry:decode/emit/ns"],
        den="decode_steps", scale=1e-6) == pytest.approx(0.22)
    # No denominator: the plain delta, and a delta of nothing is 0, not None.
    assert counter_mean(ev, num="registry:jax/compiles") == 0.0
    assert counter_mean(ev, num="decode_steps") == 200.0


def test_counter_mean_finds_nothing_where_a_key_is_missing_or_nothing_happened():
    start = {"decode_steps": 5, "decode_seated": 2, "decode_seat_wait_ns": 7, "registry": {}}
    end = dict(start, decode_steps=9)
    ev = _counters(start, end)
    assert counter_mean({}, num="decode_steps") is None
    assert counter_mean(ev, num="decode_fed") is None                    # the parent has no such key
    assert counter_mean(ev, num="registry:jax/compiles") is None         # nor this one
    assert counter_mean(ev, num=["decode_steps", "decode_fed"]) is None  # one of a list
    assert counter_mean(ev, num="decode_steps", den="decode_opens") is None
    assert counter_mean(_counters({"decode_steps": 5}, end), num="registry:jax/compiles") is None
    # Nobody was seated in the window: no mean, not a division by zero.
    assert counter_mean(ev, num="decode_seat_wait_ns", den="decode_seated") is None
