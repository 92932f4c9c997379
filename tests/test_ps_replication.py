"""PS shard replication (r12): REPL_SYNC state transfer, state-token
lineage, client failover, layout-versioned identity, and the partition/
divergence guard — the protocol-level half of the tentpole (the fault-plan
matrix and the e2e failover proof live in tests/test_faults.py).
"""

from __future__ import annotations

import numpy as np
import pytest

from distributed_tensorflow_examples_tpu import native
from distributed_tensorflow_examples_tpu.parallel import (
    ps_service,
    ps_shard,
    wire,
)
from distributed_tensorflow_examples_tpu.utils import faults


@pytest.fixture(autouse=True)
def _stop_servers():
    yield
    ps_service.stop_server()


def _pair(n_elems: int = 8):
    """A replicated single-shard pair (in-process): primary, backup, both
    peered, tokens converged."""
    pa = ps_service.start_server(0)
    pb = ps_service.start_server(0, peer=("127.0.0.1", pa), sync_wait_s=10.0)
    ps_service.set_server_peer(pa, ("127.0.0.1", pb))
    return pa, pb


# ---------------------------------------------------------------------------
# REPL_SYNC + state token
# ---------------------------------------------------------------------------


def test_start_sync_adopts_peer_token_and_state():
    pa = ps_service.start_server(0)
    c = ps_service.PSClient("127.0.0.1", pa, timeout_s=5.0, worker_tag=3)
    st = ps_service.RemoteParamStore(c, "params", 6)
    st.set(7, np.arange(6, dtype=np.float32))
    acc = ps_service.RemoteAccumulator(c, "acc", 6)
    assert acc.apply(0, np.ones(6))  # records (worker=3, seq=1)
    gq = ps_service.RemoteGradientQueue(c, "gq", 6, capacity=4)
    assert gq.push(0, np.ones(6))  # records (worker=3, seq=1)

    # A replica starting AFTER the fact pulls everything via REPL_SYNC.
    pb = ps_service.start_server(0, peer=("127.0.0.1", pa), sync_wait_s=10.0)
    assert ps_service.server_state_token(pb) == ps_service.server_state_token(pa)
    cb = ps_service.PSClient("127.0.0.1", pb, timeout_s=5.0)
    step, flat = ps_service.RemoteParamStore(cb, "params", 6).get()
    assert step == 7
    np.testing.assert_array_equal(flat, np.arange(6, dtype=np.float32))
    # The dedup tables crossed: replaying the already-processed sequences
    # against the synced replica answers "duplicate", never re-applies.
    s, _ = cb.call(
        ps_service._ACC_APPLY_TAGGED, "acc", 0, native._tag(3, 1),
        payload=np.ones(6),
    )
    assert s == 2, s
    s, _ = cb.call(
        ps_service._GQ_PUSH_TAGGED, "gq", 0, native._tag(3, 1),
        payload=np.ones(6), server_wait_s=1.0,
    )
    assert s == 2, s
    c.close()
    cb.close()


def test_cold_pair_tokens_converge_and_live_mirror():
    pa, pb = _pair()
    assert ps_service.server_state_token(pa) == ps_service.server_state_token(pb)
    c = ps_service.PSClient("127.0.0.1", pa, timeout_s=5.0, worker_tag=1)
    st = ps_service.RemoteParamStore(c, "params", 4)
    st.set(3, np.array([1, 2, 3, 4], np.float32))
    acc = ps_service.RemoteAccumulator(c, "acc", 4)
    assert acc.apply(0, np.ones(4))
    # The backup mirrors the pstore payload and the dedup tag LIVE (the
    # forward path), without mirroring accumulator CONTENTS.
    cb = ps_service.PSClient("127.0.0.1", pb, timeout_s=5.0)
    step, flat = ps_service.RemoteParamStore(cb, "params", 4).get()
    assert step == 3
    np.testing.assert_array_equal(flat, [1, 2, 3, 4])
    s, _ = cb.call(
        ps_service._ACC_APPLY_TAGGED, "acc", 0, native._tag(1, 1),
        payload=np.ones(4),
    )
    assert s == 2  # duplicate: the tag was mirrored
    # Contents were NOT mirrored: the backup's accumulator holds nothing
    # (a take would block), pinned via its pending count being zero.
    s, _ = cb.call(ps_service._ACC_TAKE, "acc", 1, 100, server_wait_s=0.2)
    assert s == -3  # timed out: nothing aggregated on the mirror
    c.close()
    cb.close()


def test_bf16_client_sets_are_mirrored():
    """The non-streamed forward path: a bf16 client's publish is decoded
    then forwarded f32 — the mirror must match the primary bit-for-bit
    (both store the same RNE-rounded values)."""
    pa, pb = _pair()
    c = ps_service.PSClient(
        "127.0.0.1", pa, timeout_s=5.0, wire_dtype="bf16"
    )
    st = ps_service.RemoteParamStore(c, "params", 5, cache_pulls=False)
    vals = np.array([1.0, 2.5, -3.25, 0.125, 7.0], np.float32)  # bf16-exact
    st.set(2, vals)
    cb = ps_service.PSClient("127.0.0.1", pb, timeout_s=5.0)
    step, flat = ps_service.RemoteParamStore(cb, "params", 5).get()
    assert step == 2
    np.testing.assert_array_equal(flat, vals)
    c.close()
    cb.close()


def test_fresh_dial_into_partitioned_peer_diverges_not_silent():
    """Regression (review round): when the forward CONNECTION itself must
    be re-dialed into a policy-refusing peer — no established link to
    carry the refusal — the dial's refusal must still latch divergence.
    The pre-fix path discarded it and the dial backoff then read 'peer
    down' forever: every publish applied one-sided, silently."""
    import time as _time

    pa, pb = _pair()
    ps_service.set_server_partitioned(pb, True)  # BEFORE any forward dial
    c = ps_service.PSClient("127.0.0.1", pa, op_timeout_s=5.0)
    # Every mutating op — the very first one included, whose forward must
    # dial fresh — refuses loudly; repeats inside the dial-backoff window
    # must stay refusals, never flip to a one-sided local apply.
    for _ in range(3):
        with pytest.raises(ps_service.PSError, match="replication diverged"):
            ps_service.RemoteParamStore(c, "params", 4, cache_pulls=False)
        _time.sleep(0.05)
    assert ps_service.server_diverged(pa) == 1
    c.close()


def test_resync_clears_divergence_after_partition_heals():
    pa, pb = _pair()
    c = ps_service.PSClient("127.0.0.1", pa, timeout_s=5.0)
    st = ps_service.RemoteParamStore(c, "params", 4, cache_pulls=False)
    st.set(1, np.zeros(4, np.float32))
    ps_service.set_server_partitioned(pb, True)
    with pytest.raises(ps_service.PSError, match="replication diverged"):
        st.set(2, np.ones(4, np.float32))
    assert ps_service.server_diverged(pa) == 1
    # Heal: lift the partition, the lagging side re-syncs from the
    # survivor — which clears the survivor's divergence latch.
    ps_service.set_server_partitioned(pb, False)
    assert ps_service.resync_server(pb, wait_s=10.0)
    assert ps_service.server_diverged(pa) == 0
    st.set(2, np.ones(4, np.float32))  # mutations accepted again
    assert st.get()[0] == 2
    c.close()


# ---------------------------------------------------------------------------
# Layout-versioned shard identity
# ---------------------------------------------------------------------------


def test_layout_version_mismatch_fails_loudly_naming_both_ends():
    port = ps_service.start_server(0, layout_version=3)
    with pytest.raises(
        ps_service.PSError, match=r"EPOCH 3.*expected epoch 5"
    ):
        ps_service.PSClient("127.0.0.1", port, timeout_s=5.0, expect_layout=5)
    # The matching epoch — and an unversioned legacy client — connect.
    c = ps_service.PSClient("127.0.0.1", port, timeout_s=5.0, expect_layout=3)
    c.ping()
    c.close()
    legacy = ps_service.PSClient("127.0.0.1", port, timeout_s=5.0)
    legacy.ping()
    legacy.close()


def test_layout_version_packs_alongside_shard_identity():
    b = wire.pack_hello_b(1, shard_id=3, shard_count=7, layout_version=9)
    assert wire.unpack_shard_mismatch(-5 - (b - 1)) == (3, 7, 9)
    # The repl flag rides above the layout field and below the service id.
    br = wire.pack_hello_b(0, repl=True, service="ps")
    assert (br >> wire.HELLO_REPL_SHIFT) & 1
    assert wire.hello_expected_service(br) == "ps"


def test_sharded_clients_pin_layout_version():
    ports = [
        ps_service.start_server(0, shard_id=i, shard_count=2, layout_version=4)
        for i in range(2)
    ]
    addrs = [("127.0.0.1", p) for p in ports]
    # Matching epoch: connects and serves.
    g = ps_shard.ShardedPSClients(addrs, role="w0", timeout_s=5.0,
                                  layout_version=4)
    g.clients[0].ping()
    g.close()
    # A stale-epoch client fails the dial loudly.
    with pytest.raises(ps_service.PSError, match="EPOCH 4"):
        ps_shard.ShardedPSClients(addrs, role="w0", timeout_s=5.0,
                                  layout_version=6)


# ---------------------------------------------------------------------------
# Client failover
# ---------------------------------------------------------------------------


def test_client_fails_over_to_backup_without_rebuild(caplog):
    caplog.set_level("INFO", logger="dtx.faults")
    pa, pb = _pair()
    fired = []
    c = ps_service.PSClient(
        "127.0.0.1", pa, op_timeout_s=5.0, reconnect_deadline_s=20.0,
        worker_tag=2, role="w0",
        addrs=[("127.0.0.1", pa), ("127.0.0.1", pb)],
    )
    c.on_reincarnation(lambda: fired.append("reseed"))
    st = ps_service.RemoteParamStore(c, "params", 4)
    st.set(5, np.arange(4, dtype=np.float32))
    ps_service.stop_server(pa)  # kill the primary
    step, flat = st.get()  # heals via the backup inside this very call
    assert step == 5
    np.testing.assert_array_equal(flat, np.arange(4, dtype=np.float32))
    assert fired == [], "failover must not run the reseed callbacks"
    events = [
        r.getMessage() for r in caplog.records if "dtx.faults" in r.getMessage()
    ]
    assert any("event=replica_state_intact" in m and "replica=1" in m
               for m in events), events
    assert not any("event=state_rebuilt" in m for m in events), events
    # Writes keep flowing on the backup (its forward sees a dead peer —
    # solo mode, never divergence).
    st.set(6, np.ones(4, np.float32))
    assert st.get()[0] == 6
    c.close()


@pytest.mark.parametrize("stood_on", [0, 1])
def test_both_replicas_restarted_empty_runs_reseed_path(caplog, stood_on):
    """Total loss: the last resort runs, and it runs on the PRIMARY whichever
    replica the client stood on when the loss came — a chief that had failed
    over and a worker that had not must not rebuild on different replicas
    (the chief would pop a queue no worker pushes to)."""
    caplog.set_level("INFO", logger="dtx.faults")
    pa, pb = _pair()
    fired = []
    c = ps_service.PSClient(
        "127.0.0.1", pa, op_timeout_s=5.0, reconnect_deadline_s=30.0,
        role="w0", addrs=[("127.0.0.1", pa), ("127.0.0.1", pb)],
    )
    st = ps_service.RemoteParamStore(c, "params", 4)
    st.set(5, np.arange(4, dtype=np.float32))
    c.on_reincarnation(lambda: fired.append("reseed"))
    if stood_on == 1:
        # An earlier incident: the primary died, the client failed over,
        # the primary came back and synced from the survivor.
        ps_service.stop_server(pa)
        assert st.get()[0] == 5
        ps_service.start_server(pa, peer=("127.0.0.1", pb), sync_wait_s=10.0)
        assert fired == []
    assert c._cur == stood_on
    # Kill BOTH, restart BOTH empty on the same ports (fresh lineage).
    ps_service.stop_server(pa)
    ps_service.stop_server(pb)
    ps_service.start_server(pa)
    ps_service.start_server(pb, peer=("127.0.0.1", pa), sync_wait_s=10.0)
    ps_service.set_server_peer(pa, ("127.0.0.1", pb))
    step, _ = st.get()
    assert step == -1  # empty store: the owner must reseed
    assert fired == ["reseed"], "total state loss must run the last resort"
    assert c._cur == 0, "the rebuild belongs on the primary"
    c.close()


def test_shard_layout_replica_dimension():
    lay = ps_shard.ShardLayout(10, 2, num_replicas=2, version=3)
    addrs = [("h0", 1), ("h1", 2), ("h0b", 3), ("h1b", 4)]
    assert lay.replica_addrs(addrs) == [
        [("h0", 1), ("h0b", 3)],
        [("h1", 2), ("h1b", 4)],
    ]
    with pytest.raises(ValueError, match="need 4 addresses"):
        lay.replica_addrs(addrs[:3])
    # The partition math ignores replication (checkpoint stability).
    assert lay == ps_shard.ShardLayout(10, 2)
    with pytest.raises(ValueError, match="num_replicas"):
        ps_shard.ShardLayout(10, 2, num_replicas=0)


def test_ps_shard_topology_flag_validation():
    from types import SimpleNamespace

    from distributed_tensorflow_examples_tpu.utils.flags import (
        ps_shard_topology,
    )

    f = SimpleNamespace(
        ps_hosts="a:1,b:2,c:3,d:4", ps_shards=-1, ps_replicas=2,
    )
    addrs, n_shards, n_replicas = ps_shard_topology(f)
    assert (n_shards, n_replicas) == (2, 2) and len(addrs) == 4
    with pytest.raises(ValueError, match="ps_replicas=3 unsupported"):
        ps_shard_topology(
            SimpleNamespace(ps_hosts="a:1,b:2,c:3", ps_shards=-1, ps_replicas=3)
        )
    with pytest.raises(ValueError, match="does not tile"):
        ps_shard_topology(
            SimpleNamespace(ps_hosts="a:1,b:2,c:3", ps_shards=-1, ps_replicas=2)
        )
    with pytest.raises(ValueError, match="invalid"):
        ps_shard_topology(
            SimpleNamespace(ps_hosts="a:1,b:2,c:3", ps_shards=2, ps_replicas=2)
        )


def test_partition_spec_parsing_and_peer_glob():
    specs = faults.parse_plan("partition:role=ps0,peer=ps2,after_s=1.5")
    assert specs[0].kind == "partition"
    assert specs[0].matches_peer("ps2") and not specs[0].matches_peer("ps1")
    # Round-trips through format_plan (the supervisor heal path).
    assert faults.parse_plan(faults.format_plan(specs))[0].peer == "ps2"
    # The client shape needs an explicit op; the process shape may omit it.
    client = faults.parse_plan("partition:role=w0,op=4")[0]
    assert client.op == 4
    inj = faults.ClientFaultInjector(role="w0", plan="partition:role=w0,op=2")
    assert not inj.before_op(1)
    assert inj.before_op(1) and inj.before_op(1)  # persistent from op 2 on
    # A process-shape spec (no op) must NOT sever client legs.
    inj2 = faults.ClientFaultInjector(
        role="ps0", plan="partition:role=ps0,peer=ps2"
    )
    assert inj2 is not None and not inj2._specs
