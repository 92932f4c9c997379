"""Disaggregated data service (r8 tentpole): shared wire helpers, the
batch codec, the dispatcher's split protocol (FCFS assignment, per-epoch
at-least-once visitation, steady-state exclusivity), the ``dsvc://``
branch of the stream resolution, and the e2e acceptance scenarios — two
training workers consuming one sharded epoch, with and without a data
server restart in the middle.

Fault-plan-driven matrix runs (drop_conn/delay/die against the
``data_service`` role) live in tests/test_faults.py with the rest of the
fault matrix.
"""

from __future__ import annotations

import json
import socket
import threading
import time

import numpy as np
import pytest

from distributed_tensorflow_examples_tpu.data import (
    data_service as dsvc,
    filestream,
    streams,
)
from distributed_tensorflow_examples_tpu.parallel import ps_service, wire
from distributed_tensorflow_examples_tpu.utils import faults
from distributed_tensorflow_examples_tpu.utils.metrics import MetricsWriter


@pytest.fixture(autouse=True)
def _clean_fault_env(monkeypatch):
    monkeypatch.delenv("DTX_FAULT_PLAN", raising=False)
    monkeypatch.delenv("DTX_FAULT_ROLE", raising=False)
    monkeypatch.setattr(faults, "_role", None)


def _splits(n=6, rows=8, batch=4):
    return [
        {
            "image": np.full((rows, 4), i, np.uint8),
            "label": np.arange(rows, dtype=np.int64),
        }
        for i in range(n)
    ]


def _source(port, w, **kw):
    kw.setdefault("op_timeout_s", 10.0)
    kw.setdefault("reconnect_deadline_s", 30.0)
    kw.setdefault("role", f"dw{w}_ds")
    return dsvc.RemoteDatasetSource(
        f"dsvc://127.0.0.1:{port}", worker_id=w, **kw
    )


# ----------------------------------------------------------------------------
# Shared wire helpers (the factor-out satellite)
# ----------------------------------------------------------------------------


def test_wire_module_is_the_shared_definition():
    """ps_service must expose the SAME objects wire defines (drift guard),
    and the codec must round-trip."""
    assert ps_service._f32_to_bf16 is wire.f32_to_bf16
    assert ps_service._bf16_to_f32 is wire.bf16_to_f32
    assert ps_service.WIRE_VERSION == wire.WIRE_VERSION
    assert ps_service.WIRE_DTYPES is wire.WIRE_DTYPES
    x = np.array([0.0, 1.0, -2.5, 3.14159e7, 6.1e-5], np.float32)
    rt = wire.bf16_to_f32(wire.f32_to_bf16(x))
    assert np.all(np.abs(rt - x) <= np.abs(x) * 0.005)  # bf16 has 8 mantissa bits


def test_wire_frame_roundtrip_over_socketpair():
    a, b = socket.socketpair()
    try:
        payload = np.arange(1000, dtype=np.float32)
        hdr = wire.pack_request(7, "acc", -3, 12, payload.size)
        wire.send_frame(a, hdr, payload)
        got = wire.read_request(b)
        assert got == (7, "acc", -3, 12, payload.size)
        out = np.empty(payload.size, np.float32)
        wire.recv_exact(b, memoryview(out).cast("B"))
        np.testing.assert_array_equal(out, payload)
        # Clean EOF before a new frame is None, not an exception.
        a.close()
        assert wire.read_request(b) is None
    finally:
        b.close()


def test_batch_codec_zero_copy_roundtrip():
    a, b = socket.socketpair()
    try:
        batch = {
            "image": np.arange(48, dtype=np.uint8).reshape(2, 4, 6),
            "label": np.array([3, -1], np.int64),
            "w": np.array([[0.5]], np.float32),
            "scalar": np.float32(3.5),  # 0-d: shape survives the round trip
        }
        bufs = dsvc.encode_batch(batch)
        n = dsvc.encoded_nbytes(bufs)
        wire.send_frames(a, bufs)
        out = dsvc.read_batch(b, n)
        assert set(out) == set(batch)
        for k in batch:
            np.testing.assert_array_equal(out[k], batch[k])
            assert out[k].dtype == batch[k].dtype
    finally:
        a.close()
        b.close()


def test_dialing_the_wrong_service_fails_loudly():
    """A data client dialing the PS server must fail the connect (HELLO
    service tag), not misparse op codes."""
    port = ps_service.start_server(0)
    try:
        with pytest.raises(dsvc.DSVCError, match="not a data service"):
            dsvc.DataServiceClient(
                "127.0.0.1", port, role="probe_ds", reconnect_deadline_s=0.0
            )
    finally:
        ps_service.stop_server()


# ----------------------------------------------------------------------------
# Split protocol
# ----------------------------------------------------------------------------


def test_split_protocol_fcfs_ack_wait_and_epoch_roll():
    srv = dsvc.DataServiceServer(_splits(3), batch_size=4, seed=0)
    try:
        c = dsvc.DataServiceClient(
            "127.0.0.1", srv.port, worker_id=0, role="p0_ds"
        )
        c2 = dsvc.DataServiceClient(
            "127.0.0.1", srv.port, worker_id=1, role="p1_ds"
        )
        s0, raw = c.call(dsvc.DSVC_GET_SPLIT, a=0, b=-1)
        assert s0 >= 0
        info = json.loads(raw)
        assert info["epoch"] == 0 and info["num_batches"] == 2
        # Replay safety: an unacked worker re-requesting gets the SAME split.
        s0b, _ = c.call(dsvc.DSVC_GET_SPLIT, a=0, b=-1)
        assert s0b == s0
        # FCFS: the other worker gets a different split.
        s1, _ = c2.call(dsvc.DSVC_GET_SPLIT, a=1, b=-1)
        assert s1 >= 0 and s1 != s0
        # Third split to worker 0 (ack + next), then nothing pending: WAIT
        # for worker 0, while worker 1 still holds its split.
        s2, _ = c.call(dsvc.DSVC_GET_SPLIT, a=0, b=s0)
        assert s2 >= 0 and s2 not in (s0, s1)
        sw, _ = c.call(dsvc.DSVC_GET_SPLIT, a=0, b=s2)
        assert sw == dsvc.WAIT
        # Single-epoch constraint: once worker 1 acks, the epoch rolls and
        # an epoch=0-strict request answers EPOCH_ROLLED (a bare epoch tag
        # only scopes the ack, it does not constrain assignment).
        c2.call(dsvc.DSVC_GET_SPLIT, name="epoch=0", a=1, b=s1)
        se, raw = c.call(dsvc.DSVC_GET_SPLIT, name="epoch=0,strict", a=0, b=-1)
        assert se == dsvc.EPOCH_ROLLED and json.loads(raw)["epoch"] == 1
        st = c.stats()
        assert st["epochs_completed"] == 1
        assert st["last_epoch_min_visits"] >= 1
        assert st["reassigned"] == 0
        c.close()
        c2.close()
    finally:
        srv.stop()


def test_claim_split_statuses():
    srv = dsvc.DataServiceServer(_splits(2), batch_size=4, seed=0, shuffle=False)
    try:
        c0 = dsvc.DataServiceClient("127.0.0.1", srv.port, worker_id=0, role="c0_ds")
        c1 = dsvc.DataServiceClient("127.0.0.1", srv.port, worker_id=1, role="c1_ds")
        s, _ = c0.call(dsvc.DSVC_GET_SPLIT, a=0, b=-1)
        # Re-claiming one's own assignment is idempotent.
        st, raw = c0.call(dsvc.DSVC_CLAIM_SPLIT, a=0, b=s)
        assert st == dsvc.OK and json.loads(raw)["num_batches"] == 2
        # Claiming a split held by a LIVE other worker is refused.
        st, _ = c1.call(dsvc.DSVC_CLAIM_SPLIT, a=1, b=s)
        assert st == dsvc.CLAIM_TAKEN
        # Claiming a completed split answers done (the client skips it).
        c0.call(dsvc.DSVC_GET_SPLIT, a=0, b=s)
        st, _ = c1.call(dsvc.DSVC_CLAIM_SPLIT, a=1, b=s)
        assert st == dsvc.CLAIM_DONE
        # Out-of-range split: error.
        st, _ = c1.call(dsvc.DSVC_CLAIM_SPLIT, a=1, b=99)
        assert st == dsvc.ERR
        c0.close()
        c1.close()
    finally:
        srv.stop()


def test_stale_epoch_ack_does_not_poison_the_new_epoch():
    """A worker that stalls past reassignment and acks AFTER the epoch
    rolled must not mark the new epoch's copy of its split completed with
    zero deliveries — acks are epoch-tagged and a stale one is ignored
    (the split is re-served instead: at-least-once preserved)."""
    srv = dsvc.DataServiceServer(
        _splits(2), batch_size=4, seed=0, shuffle=False, reassign_after_s=0.2
    )
    try:
        cA = dsvc.DataServiceClient("127.0.0.1", srv.port, worker_id=0, role="sa_ds")
        cB = dsvc.DataServiceClient("127.0.0.1", srv.port, worker_id=1, role="sb_ds")
        sA, _ = cA.call(dsvc.DSVC_GET_SPLIT, name="epoch=0", a=0, b=-1)
        sB, _ = cB.call(dsvc.DSVC_GET_SPLIT, name="epoch=0", a=1, b=-1)
        # A goes silent; B acks its split and (after A's liveness goes
        # stale) is handed A's split too, delivers it, and acks — epoch 0
        # completes entirely through B and the epoch rolls.
        deadline = time.time() + 10
        got, ack = -1, sB
        while time.time() < deadline:
            got, _ = cB.call(dsvc.DSVC_GET_SPLIT, name="epoch=0", a=1, b=ack)
            ack = -1
            if got == sA:
                break
            time.sleep(0.05)
        assert got == sA, "stale assignment was never handed to the live worker"
        st, _ = cB.call(dsvc.DSVC_GET_SPLIT, name="epoch=0,strict", a=1, b=sA)
        assert st == dsvc.EPOCH_ROLLED  # B's ack completed epoch 0
        # A's ack arrives late, still tagged epoch 0: it must be IGNORED —
        # epoch 1's copy of the split stays pending/assignable, not falsely
        # completed.
        sA2, raw = cA.call(dsvc.DSVC_GET_SPLIT, name="epoch=0", a=0, b=sA)
        info = json.loads(raw)
        assert info["epoch"] == 1 and sA2 >= 0  # fresh epoch-1 assignment
        assert srv.stats()["completed"] == 0, (
            "a stale-epoch ack falsely completed a new-epoch split"
        )
        cA.close()
        cB.close()
    finally:
        srv.stop()


def test_restart_during_strict_get_split_does_not_end_the_epoch_early():
    """A server restart while a single-epoch consumer's GET_SPLIT is in
    recovery must not terminate the iterator: the replayed request carries
    the PRE-restart epoch constraint (the reclaim hook already adopted the
    new incarnation's epoch mid-call), and the resulting EPOCH_ROLLED
    answer is a stale-constraint artifact, not a genuine roll — the client
    adopts the restarted epoch and consumes every split."""
    n_splits = 4
    splits = _splits(n_splits, rows=8, batch=4)
    srv = dsvc.DataServiceServer(splits, batch_size=4, seed=0)
    port = srv.port
    # Advance the server to epoch 1 by draining epoch 0 with one worker.
    warm = _source(port, 7)
    assert sum(1 for _ in warm.batches(repeat=False)) == n_splits * 2
    warm.close()
    # A fresh consumer joins at epoch 1 — then the server restarts (back to
    # epoch 0) BEFORE its first GET_SPLIT, so that op runs entirely through
    # the recovery path with a stale "epoch=1,strict" constraint.
    src = _source(port, 0)
    assert int(src.server_info["epoch"]) == 1
    srv.stop()
    srv2 = dsvc.DataServiceServer(splits, batch_size=4, seed=0, port=port)
    try:
        seen = {int(b["image"][0, 0]) for b in src.batches(repeat=False)}
        assert seen == set(range(n_splits)), (
            seen, "iterator ended early on the stale epoch constraint",
        )
        src.close()
    finally:
        srv2.stop()


def test_batches_deterministic_in_seed_and_split_not_epoch():
    """Resume-exactness contract: a split's batches must be identical
    across epochs and server restarts (shuffle keyed on (seed, split))."""
    srv = dsvc.DataServiceServer(_splits(2, rows=12), batch_size=4, seed=7)
    port = srv.port
    try:
        c = dsvc.DataServiceClient("127.0.0.1", port, role="d0_ds")
        _, b0 = c.call(dsvc.DSVC_GET_BATCH, a=0, b=1, batch=True)
        c.close()
    finally:
        srv.stop()
    srv2 = dsvc.DataServiceServer(_splits(2, rows=12), batch_size=4, seed=7, port=port)
    try:
        c = dsvc.DataServiceClient("127.0.0.1", port, role="d0_ds")
        _, b1 = c.call(dsvc.DSVC_GET_BATCH, a=0, b=1, batch=True)
        c.close()
        for k in b0:
            np.testing.assert_array_equal(b0[k], b1[k])
    finally:
        srv2.stop()


# ----------------------------------------------------------------------------
# E2E acceptance: 2 workers, 1 server, one sharded epoch
# ----------------------------------------------------------------------------


def _consume_epoch(port, w, seen, counts, errors, delay=0.0):
    try:
        src = _source(port, w)
        for b in src.batches(repeat=False):
            seen[w].add(int(b["image"][0, 0]))
            counts[w] += 1
            if delay:
                time.sleep(delay)
        src.close()
    except BaseException as e:  # noqa: BLE001 — asserted by the test
        errors.append((w, e))


def test_two_workers_consume_one_epoch_every_split_once():
    """The steady-state acceptance: every split visited at least once, no
    split delivered to two workers, all batches accounted for."""
    n_splits, rows, batch = 6, 8, 4
    srv = dsvc.DataServiceServer(_splits(n_splits, rows, batch), batch_size=batch, seed=0)
    seen = {0: set(), 1: set()}
    counts = {0: 0, 1: 0}
    errors: list = []
    try:
        ts = [
            threading.Thread(
                target=_consume_epoch, args=(srv.port, w, seen, counts, errors)
            )
            for w in (0, 1)
        ]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in ts), "workers hung"
        assert not errors, errors
        # Every split visited at least once...
        assert seen[0] | seen[1] == set(range(n_splits))
        # ...and never delivered to two workers in steady state...
        assert not (seen[0] & seen[1]), (seen, "split delivered twice")
        # ...with every batch of the epoch delivered exactly once.
        assert counts[0] + counts[1] == n_splits * (rows // batch)
        st = _source(srv.port, 9).stats()
        assert st["epochs_completed"] == 1 and st["last_epoch_min_visits"] == 1
    finally:
        srv.stop()


def test_server_restart_mid_epoch_still_visits_every_split(caplog):
    """The failover acceptance: the data server is killed and restarted
    mid-epoch (fresh incarnation, assignment state lost); clients
    reconnect, RE-CLAIM their in-flight splits, and between the two
    workers every split is still visited at least once."""
    caplog.set_level("INFO", logger="dtx.faults")
    n_splits = 8
    splits = _splits(n_splits, rows=16, batch=4)  # 32 batches per epoch
    srv = dsvc.DataServiceServer(splits, batch_size=4, seed=0)
    port = srv.port
    seen = {0: set(), 1: set()}
    counts = {0: 0, 1: 0}
    errors: list = []
    ts = [
        threading.Thread(
            target=_consume_epoch,
            args=(port, w, seen, counts, errors), kwargs=dict(delay=0.05),
        )
        for w in (0, 1)
    ]
    for t in ts:
        t.start()
    # Kill strictly MID-epoch: gate on consumed batches, not wall time (a
    # loaded box must not let the epoch finish before the fault lands).
    deadline = time.time() + 30
    while sum(counts.values()) < 6 and time.time() < deadline:
        time.sleep(0.01)
    assert sum(counts.values()) >= 6, "workers never started consuming"
    srv.stop()
    time.sleep(0.4)  # outage window: clients are in backoff-reconnect
    srv2 = dsvc.DataServiceServer(splits, batch_size=4, seed=0, port=port)
    try:
        for t in ts:
            t.join(timeout=90)
        assert not any(t.is_alive() for t in ts), "workers hung after restart"
        assert not errors, errors
        assert seen[0] | seen[1] == set(range(n_splits)), (
            seen, "a split was never visited across the restart",
        )
        events = [
            r.getMessage() for r in caplog.records if "dtx.faults" in r.getMessage()
        ]
        assert any("event=reconnected" in m and "_ds" in m for m in events), events
        assert any("event=dsvc_reincarnation" in m for m in events), events
    finally:
        srv2.stop()


# ----------------------------------------------------------------------------
# streams.py integration (the fourth source branch)
# ----------------------------------------------------------------------------


def test_streams_resolution_and_train_iter(tmp_path):
    rng = np.random.default_rng(0)
    filestream.write_array_shards(
        str(tmp_path),
        {
            "image": rng.integers(0, 255, size=(96, 8, 8, 3)).astype(np.uint8),
            "label": rng.integers(0, 10, size=96).astype(np.int64),
        },
        rows_per_shard=16,
    )
    srv = dsvc.serve_from_dir(str(tmp_path), batch_size=8, seed=0)
    try:
        spec = f"dsvc://127.0.0.1:{srv.port}"
        src = streams.resolve_image_source(
            spec,
            fallback=lambda: pytest.fail("fallback must not be used for dsvc"),
            seed=0,
            num_classes=10,
        )
        assert src.kind == "dsvc" and src.remote_spec == spec
        # Eval split: the held-out shard, decoded locally like the on-disk
        # branches.
        assert src.ds.test["image"].dtype == np.float32
        assert len(src.ds.test["image"]) == 16
        it = streams.train_iter(src, batch_size=8, seed=0, worker=0, n_workers=2)
        b = next(it)
        # Ready batches: decode/augment ran SERVER-side.
        assert b["image"].dtype == np.float32 and b["image"].shape == (8, 8, 8, 3)
        assert b["label"].dtype == np.int32
        for _ in range(12):
            next(it)
        it.close()
    finally:
        srv.stop()


def test_bad_spec_and_missing_eval():
    with pytest.raises(ValueError, match="dsvc://"):
        dsvc.parse_spec("dsvc://nohost")
    with pytest.raises(ValueError, match="not a data-service spec"):
        dsvc.parse_spec("/some/dir")
    srv = dsvc.DataServiceServer(_splits(1), batch_size=4)  # no eval chunk
    try:
        src = _source(srv.port, 0)
        assert src.eval_chunk() is None
        src.close()
    finally:
        srv.stop()


# ----------------------------------------------------------------------------
# Satellite: MetricsWriter context manager
# ----------------------------------------------------------------------------


def test_metrics_writer_context_manager_flushes_and_is_idempotent(tmp_path):
    with MetricsWriter(str(tmp_path)) as w:
        w.scalars(1, {"loss": 2.5})
    lines = (tmp_path / "metrics.jsonl").read_text().splitlines()
    assert json.loads(lines[-1])["loss"] == 2.5
    # TB events (if the writer is available) must be flushed to disk by the
    # context exit, not lost in the writer thread's buffer.
    assert w._tb is None and w._f is None  # closed
    w.close()  # idempotent
    w.flush()  # no-op after close, must not raise
    with MetricsWriter(None) as w2:  # disabled sink: context still works
        w2.scalars(1, {"x": 1.0})
