"""Observability-plane acceptance on a live mini cluster, run by an operator
by hand; tier-1 (``tests/test_observability.py``) holds its counter check.

Boots a small train-and-serve cluster IN THIS PROCESS (2 PS shard
servers, a data server over in-RAM splits, one serve replica on the tiny
MLP), drives real load over every wire (publishes, predicts, batch
pulls), then takes a ``tools/dtxtop.py`` snapshot and FAILS on any
missing role or any role whose STATS table lacks its required counters —
the "one scraper sees the whole cluster" contract the loadsim SLO gate
(ROADMAP item 5) will stand on.  Accelerator-free (JAX on CPU).

The last stdout line is compact JSON.
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# A host-side rig: this process and every child it starts run JAX on the
# CPU, whatever the machine's own JAX_PLATFORMS says (the chip machine sets
# "tpu,cpu", and a chip belongs to one process at a time).
os.environ["JAX_PLATFORMS"] = "cpu"

#: Counters every role's scrape must carry — a missing key means the
#: instrumentation regressed, and the step fails naming it.
REQUIRED_KEYS = {
    "ps": (
        "requests", "incarnation", "shard_id", "shard_count", "live_conns",
        "fwd_ok", "fwd_refused", "repl_syncs_served", "mirror_applies",
        "acc_deduped", "gq_deduped", "diverged",
        # r20 multi-tenancy: the per-tenant object/lease footprint.
        "tenants",
        # r18 admission control: the shed counters every service exports
        # in the same top-level shape (dtxtop + the overload SLO read
        # them uniformly).
        "shed_total", "queue_deadline_drops",
    ),
    "dsvc": (
        "requests", "incarnation", "epoch", "batches_served",
        "assigned_total", "acks", "reassigned", "registry",
        "shed_total", "queue_deadline_drops",
        # r20 multi-tenancy: the per-tenant dispatcher-job breakdown.
        "tenants",
    ),
    "serve": (
        "requests", "incarnation", "model_step", "predict_rows",
        "batcher_batch_rows_p50", "batcher_queue_depth_p99",
        "serve/latency_p99_ms", "registry",
        "shed_total", "queue_deadline_drops",
        # r19 versioned serving: the per-replica registry version (0 =
        # hot-tracking) dtxtop's version column and per-version rollup
        # key off — pinned here so the stamp cannot silently vanish.
        "model_version",
        # r20 multi-tenancy: the per-tenant admission counters.
        "tenants",
    ),
}


def missing_counters(snap: dict) -> list[str]:
    out = []
    for r in snap["roles"]:
        if not r.get("ok"):
            out.append(f"{r['role']}: DOWN ({r.get('error')})")
            continue
        for k in REQUIRED_KEYS[r["kind"]]:
            if k not in r["stats"]:
                out.append(f"{r['role']}: missing counter {k!r}")
    return out


def main() -> int:
    import numpy as np
    import jax

    from distributed_tensorflow_examples_tpu import models, serve
    from distributed_tensorflow_examples_tpu.data import data_service
    from distributed_tensorflow_examples_tpu.parallel import (
        ps_service,
        ps_shard,
    )
    from distributed_tensorflow_examples_tpu.serve import model_server
    from tools import dtxtop

    CFG = models.mlp.Config(hidden=(8,), compute_dtype="float32")
    ports = [ps_service.start_server(0, shard_id=i, shard_count=2) for i in range(2)]
    ps_addrs = [("127.0.0.1", p) for p in ports]
    rng = np.random.default_rng(0)
    splits = [
        {
            "image": rng.normal(size=(8, 784)).astype(np.float32),
            "label": rng.integers(0, 10, size=8).astype(np.int32),
        }
        for _ in range(4)
    ]
    dsvc = data_service.DataServiceServer(splits, batch_size=4)
    group = ps_shard.ShardedPSClients(ps_addrs, role="obs_pub")
    params = models.mlp.init(CFG, jax.random.key(0))
    total, _ = ps_shard.flat_param_spec(params)
    store = ps_shard.ShardedParamStore(
        group, "params", ps_shard.ShardLayout(total, 2)
    )
    flat = np.concatenate(
        [np.asarray(l).reshape(-1) for l in jax.tree.leaves(params)]
    ).astype(np.float32)
    srv = model_server.ModelReplicaServer(
        lambda r: models.mlp.init(CFG, r),
        lambda p, batch: models.mlp.apply(CFG, p, batch["image"]),
        ps_addrs, max_batch=8, refresh_ms=20.0,
    )
    ok = False
    try:
        # Load on every wire: publishes, predicts, split pulls.
        for step in range(1, 6):
            store.set(step, flat)
        assert srv.wait_for_model(60), "serve replica never pulled params"
        sc = serve.ServeClient(
            "127.0.0.1", srv.port, role="obs_load_sv",
            reconnect_deadline_s=0.0,
        )
        x = np.zeros((4, 784), np.float32)
        for _ in range(25):
            sc.predict({"image": x})
        dc = data_service.DataServiceClient(
            "127.0.0.1", dsvc.port, worker_id=0, reconnect_deadline_s=0.0,
        )
        status, _ = dc.call(
            data_service.DSVC_GET_SPLIT, name="epoch=0", a=0, b=-1
        )
        if status >= 0:
            dc.call(
                data_service.DSVC_GET_BATCH, name="0", a=status, b=0,
                batch=True,
            )
        snap = dtxtop.snapshot(
            ps_addrs, ps_shards=2,
            dsvc_addrs=[("127.0.0.1", dsvc.port)],
            serve_addrs=[("127.0.0.1", srv.port)],
        )
        problems = missing_counters(snap)
        su = snap["summary"]
        # The aggregated per-tenant section (r20) must exist and carry
        # the default tenant this single-tenant boot ran as.
        if "default" not in su.get("tenants", {}):
            problems.append("summary: missing tenants rollup")
        ok = not problems and su["roles_ok"] == su["roles_total"]
        for p in problems:
            print(f"obs_snapshot: {p}", file=sys.stderr)
        print(json.dumps({
            "ok": ok,
            "roles_ok": su["roles_ok"],
            "roles_total": su["roles_total"],
            "problems": problems,
            "summary": su,
        }))
        sc.close()
        dc.close()
    finally:
        try:
            srv.stop()
            dsvc.stop()
            group.close()
            ps_service.stop_server()
        except Exception:
            pass
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
