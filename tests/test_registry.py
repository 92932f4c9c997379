"""Model registry + canary/rolling serving (r19 tentpole).

Pins the versioned-deploy subsystem end to end:

- :class:`serve.ModelRegistry`: immutable publishes, crash-safe atomic
  manifests, GC retention that can never delete a version a live pin
  protects (lease-style refcount with expiry).
- Pin-mode replicas: an immutable registry version served with the
  ``model_version`` stamp on HELLO / predict responses / STATS, the pin
  renewed for the replica's lifetime and released on stop.
- Canary-weighted routing: ``ServePool.set_canary`` honors its traffic
  split deterministically, degrades to plain rotation when a lane dies
  (replica ejection), and keeps per-version latency/error accounting.
- :class:`serve.RollingDeploy`: the acceptance flip — a 3-replica pool
  goes stable→canary→promoted under closed-loop load with ZERO failed
  predicts and a monotone served version; rollback is exercised and also
  zero-failure.
- :func:`serve.canary_verdict`: the promote-or-rollback policy.
"""

from __future__ import annotations

import json
import os
import threading
import time

import numpy as np
import pytest

from distributed_tensorflow_examples_tpu import serve
from distributed_tensorflow_examples_tpu.parallel import wire
from distributed_tensorflow_examples_tpu.serve.registry import (
    ModelRegistry,
    RegistryError,
)

D = 8


def _init_fn(rng):
    import jax.numpy as jnp

    return {"w": jnp.zeros((D,), jnp.float32)}


def _predict_fn(params, batch):
    return batch["x"] * params["w"][None, :]


def _publish(reg, value, step, version=None):
    return reg.publish(
        "default", np.full(D, value, np.float32), step=step, version=version
    )


# ----------------------------------------------------------------------------
# ModelRegistry
# ----------------------------------------------------------------------------


def test_registry_publish_load_immutability(tmp_path):
    reg = ModelRegistry(str(tmp_path))
    assert reg.versions("default") == [] and reg.latest("default") is None
    v1 = _publish(reg, 1.0, step=5)
    v2 = _publish(reg, 2.0, step=9)
    assert (v1, v2) == (1, 2)
    assert reg.versions("default") == [1, 2] and reg.latest("default") == 2
    step, flat, man = reg.load("default", 1)
    assert step == 5 and np.array_equal(flat, np.full(D, 1.0, np.float32))
    assert man["num_elems"] == D and man["dtype"] == "float32"
    # Immutable: re-publishing an existing version is refused loudly.
    with pytest.raises(RegistryError, match="immutable"):
        _publish(reg, 3.0, step=1, version=1)
    # Unknown version is a typed error, not a stack of OSErrors.
    with pytest.raises(RegistryError, match="no published"):
        reg.load("default", 99)


def test_registry_version_without_manifest_is_invisible(tmp_path):
    """Crash-safety contract: the manifest is written LAST — a version
    dir without one (a crashed publish) is not a version."""
    reg = ModelRegistry(str(tmp_path))
    _publish(reg, 1.0, step=1)
    half = tmp_path / "default" / "v000002"
    half.mkdir()
    np.save(half / "params.npy", np.zeros(D, np.float32))
    assert reg.versions("default") == [1]
    assert reg.latest("default") == 1
    # And the next publish takes the slot over cleanly.
    assert _publish(reg, 2.0, step=2) == 2
    assert reg.versions("default") == [1, 2]


def test_registry_load_validates_blob_against_manifest(tmp_path):
    reg = ModelRegistry(str(tmp_path))
    v = _publish(reg, 1.0, step=1)
    blob = tmp_path / "default" / "v000001" / "params.npy"
    np.save(blob, np.zeros(D - 2, np.float32))  # truncated
    with pytest.raises(RegistryError, match="manifest says"):
        reg.load("default", v)


def test_registry_gc_honors_keep_last_n_and_pins(tmp_path):
    """keep_last_n NEVER deletes a version a live replica has pinned —
    the lease-style refcount the rolling deploy stands on — and an
    EXPIRED pin no longer protects (a crashed replica cannot block GC
    forever)."""
    reg = ModelRegistry(str(tmp_path))
    for i in range(5):
        _publish(reg, float(i), step=i)
    reg.pin("default", 2, "serve0", ttl_s=60.0)
    deleted = reg.gc("default", keep_last_n=2)
    assert deleted == [1, 3]  # v2 pinned, v4/v5 retained by keep_last_n
    assert reg.versions("default") == [2, 4, 5]
    assert reg.pinned_by("default", 2) == ["serve0"]
    # Unpin -> the next gc reclaims it.
    reg.unpin("default", 2, "serve0")
    assert reg.gc("default", keep_last_n=2) == [2]
    # Expired pins do not protect.
    reg.pin("default", 4, "serve1", ttl_s=0.05)
    time.sleep(0.1)
    assert reg.gc("default", keep_last_n=1) == [4]
    assert reg.versions("default") == [5]
    with pytest.raises(RegistryError):
        reg.gc("default", keep_last_n=0)


def test_pins_are_tenant_namespaced_regression(tmp_path):
    """The r20 fix: two tenants' replicas sharing a snapshot AND a role
    name hold two DISTINCT pins — one tenant's unpin (or pin expiry)
    must never unprotect the version out from under the other's live
    replica.  Pre-fix both wrote pins/serve0.json and the second unpin
    deleted the first tenant's protection."""
    reg = ModelRegistry(str(tmp_path))
    for i in range(3):
        _publish(reg, float(i), step=i)
    reg.pin("default", 1, "serve0", ttl_s=60.0, tenant="runa")
    reg.pin("default", 1, "serve0", ttl_s=60.0, tenant="runb")
    owners = reg.pinned_by("default", 1)
    assert sorted(owners) == ["t.runa.serve0", "t.runb.serve0"]
    # Tenant A releases; tenant B's pin must still protect v1.
    reg.unpin("default", 1, "serve0", tenant="runa")
    assert reg.pinned_by("default", 1) == ["t.runb.serve0"]
    # keep_last_n=1 keeps v3; v1 survives on runb's pin alone; v2 goes.
    assert reg.gc("default", keep_last_n=1) == [2]
    assert reg.versions("default") == [1, 3]
    # An untagged pin is the default tenant: three namespaces coexist.
    reg.pin("default", 1, "serve0", ttl_s=60.0)
    assert sorted(reg.pinned_by("default", 1)) == [
        "serve0", "t.runb.serve0"
    ]


def test_registry_publish_from_checkpoint_bridge(tmp_path):
    """The train/checkpoint.py bridge: the newest checkpoint's params
    flatten with the shared leaf order and publish as a version."""
    import jax

    from distributed_tensorflow_examples_tpu.train.checkpoint import (
        flat_params_of,
    )

    params = {"b": np.arange(3, dtype=np.float32),
              "a": np.ones((2, 2), np.float32)}
    flat = flat_params_of(params)
    # jax.tree order: sorted keys — "a" leaves first.
    assert np.array_equal(flat[:4], np.ones(4, np.float32))
    assert flat.shape == (7,)

    class FakeManager:
        def restore_latest(self, template):
            return params

        def latest_step(self):
            return 17

    reg = ModelRegistry(str(tmp_path))
    v = reg.publish_from_checkpoint(FakeManager(), params, "ckpt-model")
    step, got, man = reg.load("ckpt-model", v)
    assert step == 17 and np.array_equal(got, flat)
    assert man["source"] == "checkpoint"
    del jax  # imported for parity with the shared flatten convention


# ----------------------------------------------------------------------------
# Wire: the r19 msrv code points + HELLO version word
# ----------------------------------------------------------------------------


def test_wire_decode_code_points_and_version_word():
    # The stream code points exist, in the msrv range, disjoint from
    # every other service's ops (dtxlint pins the full matrix; this is
    # the direct unit pin).
    for name in ("DECODE_OPEN", "DECODE_NEXT", "DECODE_CLOSE"):
        code = wire.SRV_OPS[name]
        assert code not in wire.PS_OPS.values()
        assert code not in wire.DSVC_OPS.values()
    assert wire.SRV_STATUS["BAD_SESSION"] == -9
    assert wire.SRV_STATUS["NO_DECODER"] == -10
    # HELLO version word round trip; a bare tag reads as version 0.
    tag = wire.SERVICE_TAGS["msrv"]
    t4, ver = wire.unpack_hello_tag(tag + wire.HELLO_VERSION_TAIL.pack(7))
    assert t4 == tag and ver == 7
    assert wire.unpack_hello_tag(tag) == (tag, 0)
    assert wire.unpack_hello_tag(None) == (None, 0)
    # hello_failure accepts both payload shapes as success.
    assert wire.hello_failure(
        wire.WIRE_VERSION, tag + wire.HELLO_VERSION_TAIL.pack(3),
        service="msrv", host="h", port=1,
    ) is None


# ----------------------------------------------------------------------------
# Pin-mode replicas
# ----------------------------------------------------------------------------


def test_pinned_replica_serves_version_and_stamps_everything(tmp_path):
    reg = ModelRegistry(str(tmp_path))
    v = _publish(reg, 2.0, step=42)
    srv = serve.ModelReplicaServer(
        _init_fn, _predict_fn, [], registry_dir=str(tmp_path),
        model_version=v, role="pin0", max_wait_ms=2.0,
    )
    try:
        c = serve.ServeClient("127.0.0.1", srv.port, role="pin_sv")
        # The HELLO version word: known BEFORE any predict routes.
        assert c.server_model_version == 1
        x = np.ones((2, D), np.float32)
        step, out = c.predict({"x": x})
        assert step == 42  # the manifest's training step, not a PS head
        np.testing.assert_allclose(out["x" if "x" in out else "output"], 2.0 * x)
        # The per-response stamp, stripped before the caller sees fields.
        assert c.last_model_version == 1
        assert wire.SRV_VERSION_FIELD not in out
        st = c.stats()
        assert st["model_version"] == 1 and st["pinned"] is True
        assert st["model_step"] == 42
        # The replica's pin protects its version from GC for its lifetime.
        _publish(reg, 3.0, step=50)
        assert reg.gc("default", keep_last_n=1) == []
        assert reg.versions("default") == [1, 2]
        c.close()
    finally:
        srv.stop()
    # stop() released the pin: retention may reclaim now.
    assert reg.pinned_by("default", 1) == []
    assert reg.gc("default", keep_last_n=1) == [1]


def test_pinned_replica_without_version_fails_loudly(tmp_path):
    with pytest.raises(RegistryError):
        serve.ModelReplicaServer(
            _init_fn, _predict_fn, [], registry_dir=str(tmp_path),
            model_version=3, role="pinx",
        )
    # And a PS-free replica WITHOUT a pin is a config error, not a hang.
    with pytest.raises(ValueError, match="ps_addrs"):
        serve.ModelReplicaServer(_init_fn, _predict_fn, [], role="piny")


# ----------------------------------------------------------------------------
# Canary routing (deterministic, pool-level)
# ----------------------------------------------------------------------------


def _fake_pool(versions):
    pool = serve.ServePool(
        [("127.0.0.1", 10000 + i) for i in range(len(versions))],
        role="cw_sv",
    )
    pool._ver = list(versions)
    return pool


def test_canary_weight_is_honored_deterministically():
    pool = _fake_pool([1, 1, 1, 2])
    pool.set_canary(2, 0.25)
    picks = [pool._pick() for _ in range(400)]
    frac = sum(1 for i in picks if i == 3) / len(picks)
    assert frac == pytest.approx(0.25, abs=0.01)
    # The stable lane round-robins across its members.
    stable_counts = [picks.count(i) for i in range(3)]
    assert max(stable_counts) - min(stable_counts) <= 1
    # Weight change applies immediately.
    pool.set_canary(2, 0.5)
    picks = [pool._pick() for _ in range(400)]
    assert sum(1 for i in picks if i == 3) / len(picks) == pytest.approx(
        0.5, abs=0.01
    )
    pool.close()


def test_canary_routing_survives_replica_ejection():
    """The ejection matrix: a benched canary degrades the canary lane to
    the stable rotation (never a blackhole), a benched stable member
    redistributes within its lane at the SAME canary weight, and an
    un-ejection restores the split — the 'canary routing weights under
    replica ejection' coverage."""
    pool = _fake_pool([1, 1, 2, 2])
    pool.set_canary(2, 0.3)
    t_far = time.monotonic() + 60.0
    # Bench one canary replica: the other carries the whole 0.3.
    pool._eject_until[2] = t_far
    picks = [pool._pick() for _ in range(300)]
    assert all(i != 2 for i in picks)
    assert sum(1 for i in picks if i == 3) / len(picks) == pytest.approx(
        0.3, abs=0.02
    )
    # Bench the WHOLE canary lane: picks degrade to the stable rotation
    # (no None, no starvation) — a dead canary must not fail requests.
    pool._eject_until[3] = t_far
    picks = [pool._pick() for _ in range(100)]
    assert None not in picks and all(i in (0, 1) for i in picks)
    # Un-eject: the split restores.
    pool._eject_until[2] = pool._eject_until[3] = 0.0
    picks = [pool._pick() for _ in range(300)]
    canary_frac = sum(1 for i in picks if i in (2, 3)) / len(picks)
    assert canary_frac == pytest.approx(0.3, abs=0.02)
    # Bench a STABLE member: the canary weight holds, the remaining
    # stable member takes the whole stable share.
    pool._eject_until[0] = t_far
    picks = [pool._pick() for _ in range(300)]
    assert all(i != 0 for i in picks)
    assert sum(1 for i in picks if i in (2, 3)) / len(picks) == pytest.approx(
        0.3, abs=0.02
    )
    assert sum(1 for i in picks if i == 1) / len(picks) == pytest.approx(
        0.7, abs=0.02
    )
    pool.close()


def test_canary_verdict_policy():
    ok = {"ok": 100, "err": 0, "latency_p99_ms": 10.0}
    assert serve.canary_verdict(ok, None) == "hold"
    assert serve.canary_verdict(ok, {"ok": 3, "err": 0}) == "hold"  # evidence
    assert serve.canary_verdict(
        ok, {"ok": 100, "err": 0, "latency_p99_ms": 12.0}
    ) == "promote"
    assert serve.canary_verdict(
        ok, {"ok": 90, "err": 10, "latency_p99_ms": 12.0}
    ) == "rollback"
    assert serve.canary_verdict(
        ok, {"ok": 100, "err": 0, "latency_p99_ms": 100.0}
    ) == "rollback"
    # No stable evidence: latency gate degrades, errors still decide.
    assert serve.canary_verdict(
        None, {"ok": 100, "err": 0, "latency_p99_ms": 100.0}
    ) == "promote"


# ----------------------------------------------------------------------------
# RollingDeploy: the acceptance flip
# ----------------------------------------------------------------------------


def test_rolling_deploy_flip_zero_failures_and_rollback(tmp_path):
    """THE acceptance: a 3-replica pool flips stable→canary→promoted
    under closed-loop load with zero failed predicts and a monotone
    served model_version; the rollback path is exercised and is also
    zero-failure."""
    reg = ModelRegistry(str(tmp_path))
    v1 = _publish(reg, 1.0, step=10)
    v2 = _publish(reg, 2.0, step=20)
    pool = serve.ServePool(
        [("127.0.0.1", 1)], role="rd_sv", op_timeout_s=5.0, deadline_s=30.0
    )
    make = serve.make_pinned_factory(
        _init_fn, _predict_fn, [], registry_dir=str(tmp_path),
        membership=False, max_wait_ms=1.0,
    )
    dep = serve.RollingDeploy(
        make, replicas=3, version=v1, on_change=pool.set_addrs
    )
    x = np.ones((1, D), np.float32)
    stop = threading.Event()
    failures: list[str] = []
    versions_seen: list[int] = []

    def loadgen():
        while not stop.is_set():
            try:
                step, _out = pool.predict({"x": x})
                versions_seen.append(pool.last_version)
            except Exception as e:  # noqa: BLE001 — every failure counted
                failures.append(repr(e))
                return

    th = threading.Thread(target=loadgen)
    th.start()
    try:
        time.sleep(0.3)
        # Canary: one v2 replica, 25% of traffic, verdict from the
        # pool's own per-version accounting.
        dep.canary(v2)
        pool.set_canary(v2, 0.25)
        time.sleep(1.0)
        vs = pool.version_stats()
        assert vs.get(v2, {}).get("ok", 0) > 0, vs
        assert serve.canary_verdict(vs.get(v1), vs.get(v2)) == "promote"
        pool.clear_canary()
        assert dep.promote(v2) == 3
        time.sleep(0.5)
        assert set(dep.versions().values()) == {v2}
        # Rollback leg: canary v3, then roll it back — zero failures too.
        v3 = _publish(reg, 3.0, step=30)
        dep.canary(v3)
        pool.set_canary(v3, 0.5)
        time.sleep(0.6)
        pool.clear_canary()
        assert dep.rollback(v3) == 1
        time.sleep(0.4)
    finally:
        stop.set()
        th.join(timeout=30)
    assert not failures, failures
    assert set(dep.versions().values()) == {v2}
    # Monotone THROUGH the promote: once v2 fully serves, no v1 answer
    # ever reappears (the flip never goes backward).
    last1 = max(i for i, v in enumerate(versions_seen) if v == v1)
    first_all2 = versions_seen.index(v2)
    assert first_all2 <= last1  # overlap existed (canary window)
    tail = versions_seen[last1 + 1:]
    assert tail and all(v in (v2, v3) for v in tail)
    assert versions_seen[-1] == v2
    assert len(versions_seen) > 100  # the load loop genuinely ran
    dep.close()
    pool.close()
    # Every pin released: retention reclaims everything but the latest.
    assert reg.gc("default", keep_last_n=1) == [1, 2]


def test_rolling_deploy_rollback_never_empties_pool(tmp_path):
    reg = ModelRegistry(str(tmp_path))
    v1 = _publish(reg, 1.0, step=1)
    make = serve.make_pinned_factory(
        _init_fn, _predict_fn, [], registry_dir=str(tmp_path),
        membership=False,
    )
    dep = serve.RollingDeploy(make, replicas=1, version=v1)
    try:
        # Rolling back the ONLY version refuses to stop the last replica.
        assert dep.rollback(v1) == 0
        assert len(dep.addrs()) == 1
    finally:
        dep.close()


# ----------------------------------------------------------------------------
# Registry GC vs live pins under churn (the refcount race)
# ----------------------------------------------------------------------------


def test_gc_during_live_serving_never_breaks_the_replica(tmp_path):
    """A gc sweeping while a pinned replica serves must neither delete
    its version nor perturb its answers."""
    reg = ModelRegistry(str(tmp_path))
    v1 = _publish(reg, 5.0, step=3)
    for i in range(4):
        _publish(reg, float(i), step=10 + i)
    srv = serve.ModelReplicaServer(
        _init_fn, _predict_fn, [], registry_dir=str(tmp_path),
        model_version=v1, role="gc0", max_wait_ms=1.0,
    )
    try:
        c = serve.ServeClient("127.0.0.1", srv.port, role="gc_sv")
        x = np.ones((1, D), np.float32)
        for _ in range(3):
            deleted = reg.gc("default", keep_last_n=1)
            assert v1 not in deleted
            step, out = c.predict({"x": x})
            assert step == 3
            np.testing.assert_allclose(out[next(iter(out))], 5.0 * x)
        assert reg.versions("default")[0] == v1
        c.close()
    finally:
        srv.stop()


# ----------------------------------------------------------------------------
# dtxtop: per-version rollup
# ----------------------------------------------------------------------------


def test_dtxtop_serve_version_rollup(tmp_path):
    from tools import dtxtop

    reg = ModelRegistry(str(tmp_path))
    v1 = _publish(reg, 1.0, step=10)
    v2 = _publish(reg, 2.0, step=20)
    srvs = [
        serve.ModelReplicaServer(
            _init_fn, _predict_fn, [], registry_dir=str(tmp_path),
            model_version=v, role=f"vt{i}", max_wait_ms=1.0,
        )
        for i, v in enumerate((v1, v1, v2))
    ]
    try:
        addrs = [("127.0.0.1", s.port) for s in srvs]
        c = serve.ServeClient("127.0.0.1", srvs[2].port, role="vt_sv")
        c.predict({"x": np.ones((1, D), np.float32)})
        c.close()
        snap = dtxtop.snapshot(serve_addrs=addrs)
        su = snap["summary"]["serve"]
        assert sorted(su["model_versions"]) == [1, 1, 2]
        bv = su["by_version"]
        assert bv["1"]["replicas"] == 2 and bv["2"]["replicas"] == 1
        assert bv["2"]["predict_rows"] == 1
        # The per-replica version column renders.
        out = dtxtop.render(snap)
        assert "version=" in out and "serve versions:" in out
        assert json.dumps(snap)  # snapshot stays JSON-serializable
    finally:
        for s in srvs:
            s.stop()


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_a_tree_round_trips_in_the_type_it_is_held_in(tmp_path, dtype):
    """``flat_params_of`` -> ``publish`` -> ``load`` -> ``unflatten``: a tree
    held in bfloat16 comes back bfloat16, equal bit for bit (``np.save``
    alone writes an extension type as bytes of no type), and a float32 tree
    as it always did; a tree of mixed types is flattened to float32."""
    import jax
    import jax.numpy as jnp

    from distributed_tensorflow_examples_tpu.parallel import ps_shard
    from distributed_tensorflow_examples_tpu.train.checkpoint import (
        flat_params_of,
    )

    k1, k2 = jax.random.split(jax.random.key(2))
    tree = {
        "emb": {"table": jax.random.normal(k1, (5, 3)).astype(dtype)},
        "layer_0": {"A_log": jnp.log(jnp.arange(1.0, 8.0)).astype(dtype),
                    "w": (1e-3 * jax.random.normal(k2, (3, 4))).astype(dtype)},
    }
    flat = flat_params_of(tree)
    assert str(flat.dtype) == dtype and flat.shape == (15 + 7 + 12,)
    reg = ModelRegistry(str(tmp_path))
    v = reg.publish("typed", flat, step=3)
    step, back, man = reg.load("typed", v)
    assert step == 3 and man["dtype"] == dtype and str(back.dtype) == dtype
    assert back.tobytes() == flat.tobytes()
    total, unflatten = ps_shard.flat_param_spec(tree)
    assert total == flat.size
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a).view(np.uint8), np.asarray(b).view(np.uint8)),
        tree, unflatten(back),
    )
    mixed = flat_params_of({"a": np.ones(2, np.float32), "b": jnp.ones(3, jnp.bfloat16)})
    assert mixed.dtype == np.float32
    if dtype == "float32":
        # To the byte what it was before: the leaves, concatenated.
        assert flat.tobytes() == b"".join(
            np.asarray(l).tobytes() for l in jax.tree.leaves(tree))
