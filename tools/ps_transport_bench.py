"""Host-side PS transport microbenchmark (r7 tentpole measurement).

Spawns the REAL native PS server in-process plus N client threads and
measures the socket hot path the cross-process PS emulation lives on:
set/get/push round-trip latency and MB/s at small and large payloads, f32
vs bf16 wire encoding, and cold full pulls vs unchanged-step
``get_if_newer`` pulls.  Runs on any CPU box — no accelerator, no jax; a
host metric, never a device one.

Throughputs are also reported normalized by the host's memcpy bandwidth
(``*_frac_memcpy``): a copy-per-send regression costs a fixed multiple of
memcpy, so the normalized number is comparable across hosts of very
different speed — that is what ``tools/perf_gate.py`` gates on.

Usage:
  python tools/ps_transport_bench.py                 # full (64 MB large)
  python tools/ps_transport_bench.py --quick         # CI-sized (8 MB)
  python tools/ps_transport_bench.py --json out.json # also write a file
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from distributed_tensorflow_examples_tpu.parallel import (  # noqa: E402
    ps_service,
    ps_shard,
)


def _time(fn, reps: int) -> float:
    t0 = time.perf_counter()
    for _ in range(reps):
        fn()
    return time.perf_counter() - t0


def memcpy_mbs(nbytes: int) -> float:
    """Host memcpy bandwidth at the large-payload size — the normalizer
    that makes throughput rows comparable across hosts."""
    src = np.ones(nbytes // 4, np.float32)
    dst = np.empty_like(src)
    np.copyto(dst, src)  # warm
    reps = 8
    dt = _time(lambda: np.copyto(dst, src), reps)
    return reps * nbytes / dt / 1e6


def bench_dtype(
    host: str, port: int, dtype: str, *, large_elems: int, small_elems: int,
    reps_large: int, reps_small: int,
) -> dict:
    c = ps_service.PSClient(host, port, timeout_s=60.0, wire_dtype=dtype)
    tag = f"{dtype}"
    large_mb = large_elems * 4 / 1e6  # f32-equivalent payload (what moves)
    flat = (np.arange(large_elems, dtype=np.float32) % 251) - 125.0
    small = np.arange(small_elems, dtype=np.float32)
    row: dict = {}

    # -- param store: publish (set) and cold full pulls (get) ---------------
    ps = ps_service.RemoteParamStore(c, f"p_{tag}", large_elems, cache_pulls=False)
    ps.set(0, flat)
    ps.get()
    dt = _time(lambda: ps.set(1, flat), reps_large)
    row["set_mbs_large"] = reps_large * large_mb / dt
    dt = _time(ps.get, reps_large)
    row["get_mbs_large"] = reps_large * large_mb / dt
    # Combined set+get (the acceptance metric: one publish + one pull).
    def set_get():
        ps.set(2, flat)
        ps.get()
    dt = _time(set_get, reps_large)
    row["set_get_mbs_large"] = reps_large * 2 * large_mb / dt

    # -- gradient path: push + pop round trip -------------------------------
    gq = ps_service.RemoteGradientQueue(c, f"g_{tag}", large_elems, capacity=4)
    def push_pop():
        gq.push(0, flat)
        gq.pop()
    push_pop()
    dt = _time(push_pop, reps_large)
    row["push_pop_mbs_large"] = reps_large * 2 * large_mb / dt

    # -- small-payload round-trip latency -----------------------------------
    pss = ps_service.RemoteParamStore(c, f"ps_{tag}", small_elems, cache_pulls=False)
    pss.set(0, small)
    pss.get()
    dt = _time(lambda: pss.set(1, small), reps_small)
    row["set_rtt_us_small"] = dt / reps_small * 1e6
    dt = _time(pss.get, reps_small)
    row["get_rtt_us_small"] = dt / reps_small * 1e6

    # -- versioned pull: unchanged step moves O(header), not O(params) ------
    psc = ps_service.RemoteParamStore(c, f"p_{tag}", large_elems)
    psc.get()  # fills the cache
    dt = _time(psc.get, reps_small)
    row["if_newer_rtt_us"] = dt / reps_small * 1e6
    row["if_newer_wire_bytes"] = 12 + 2 + len(f"p_{tag}") + 20  # resp + req hdrs
    c.close()
    return row


def bench_shards(
    host: str, *, counts: list[int], elems: int, reps: int, trials: int = 3,
) -> dict:
    """Shard-scaling axis (r9 tentpole measurement): the SAME total bytes
    pulled/pushed through 1/2/4 local shard servers via the sharded
    scatter/gather client (``parallel/ps_shard``).  Each count gets its own
    fresh in-process servers (multi-server support, per-port stop), so the
    rows are independent.  Every row is the BEST of ``trials`` timing
    passes — on small/shared hosts the loopback rows are hostage to
    scheduler noise (single-trial spread exceeds the effect under test),
    and the max is the standard noise-floor estimator for a
    throughput microbench.  ``sharded_pull_speedup`` is the cold-pull MB/s
    over the shards=1 row — the number ``tools/perf_gate.py`` gates
    (>= 1.3x at shards=2, 64 MB, on hosts with the cores to express it)."""
    rows: dict = {}
    mb = elems * 4 / 1e6
    for n in counts:
        ports = [
            ps_service.start_server(0, shard_id=i, shard_count=n)
            for i in range(n)
        ]
        try:
            group = ps_shard.ShardedPSClients(
                [(host, p) for p in ports], role="bench0", timeout_s=120.0
            )
            layout = ps_shard.ShardLayout(elems, n)
            # cache_pulls=False: every get is a COLD full gather — the
            # worker-pulls-fresh-params hot path this axis prices.
            st = ps_shard.ShardedParamStore(
                group, "p_sh", layout, cache_pulls=False
            )
            flat = (np.arange(elems, dtype=np.float32) % 251) - 125.0
            st.set(0, flat)
            st.get()
            row: dict = {"shards": n, "set_mbs": 0.0, "get_mbs": 0.0}
            for _ in range(max(1, trials)):
                dt = _time(lambda: st.set(1, flat), reps)
                row["set_mbs"] = max(row["set_mbs"], reps * mb / dt)
                dt = _time(st.get, reps)
                row["get_mbs"] = max(row["get_mbs"], reps * mb / dt)
            rows[str(n)] = row
            group.close()
        finally:
            for p in ports:
                ps_service.stop_server(p)
    # Speedups are relative to the shards=1 row SPECIFICALLY — with a
    # custom --shards axis that omits 1, the ratio has no baseline and the
    # rows carry none (perf_gate skips a missing speedup) rather than a
    # bogus 1.0 pinned to whichever count happened to run first.
    base_get = rows.get("1", {}).get("get_mbs")
    if base_get:
        for row in rows.values():
            row["sharded_pull_speedup"] = row["get_mbs"] / base_get
    return rows


def bench_replication(
    host: str, *, counts: list[int], elems: int, reps: int, trials: int = 3,
) -> dict:
    """Replication axis (r12 tentpole measurement): the SAME publish/push
    traffic against an unreplicated server (replicas=1) vs a local
    primary/backup pair with forwarding on (replicas=2).  Publishes carry
    their payload to the backup (streamed concurrently with the client
    read); tagged gradient pushes mirror header-only.
    ``replicated_set_overhead`` / ``replicated_push_overhead`` are the
    latency multipliers over the replicas=1 row — ``tools/perf_gate.py``
    bounds the PUSH overhead (<= 1.6x at 64 MB: the dedup mirror is one
    extra header-sized round trip, never a payload) and gives the
    payload-carrying set a no-catastrophe tripwire at 2x that bound.
    Best-of-``trials``, like the shard axis."""
    rows: dict = {}
    mb = elems * 4 / 1e6
    for n in counts:
        ports = [ps_service.start_server(0) for _ in range(n)]
        if n > 1:
            ps_service.set_server_peer(ports[0], (host, ports[1]))
            ps_service.set_server_peer(ports[1], (host, ports[0]))
            ps_service.resync_server(ports[1], wait_s=10.0)
        try:
            c = ps_service.PSClient(
                host, ports[0], timeout_s=120.0, worker_tag=1,
                addrs=[(host, p) for p in ports] if n > 1 else None,
            )
            st = ps_service.RemoteParamStore(
                c, "p_rep", elems, cache_pulls=False
            )
            flat = (np.arange(elems, dtype=np.float32) % 251) - 125.0
            st.set(0, flat)
            st.get()
            gq = ps_service.RemoteGradientQueue(c, "g_rep", elems, capacity=4)

            def push_pop():
                gq.push(0, flat)
                gq.pop()

            push_pop()
            row: dict = {"replicas": n, "set_mbs": 0.0, "push_pop_mbs": 0.0}
            for _ in range(max(1, trials)):
                dt = _time(lambda: st.set(1, flat), reps)
                row["set_mbs"] = max(row["set_mbs"], reps * mb / dt)
                dt = _time(push_pop, reps)
                row["push_pop_mbs"] = max(row["push_pop_mbs"], reps * 2 * mb / dt)
            rows[str(n)] = row
            c.close()
        finally:
            for p in ports:
                ps_service.stop_server(p)
    base = rows.get("1")
    if base:
        for row in rows.values():
            # Latency multipliers (>= ~1.0): baseline MB/s over this row's.
            row["replicated_set_overhead"] = base["set_mbs"] / row["set_mbs"]
            row["replicated_push_overhead"] = (
                base["push_pop_mbs"] / row["push_pop_mbs"]
            )
    return rows


def bench_concurrent_get(
    host: str, port: int, *, clients: int, elems: int, reps: int
) -> dict:
    """N client threads pulling the same published vector concurrently —
    the every-worker-pulls-before-every-gradient hot path."""
    setup = ps_service.PSClient(host, port, timeout_s=60.0)
    ps = ps_service.RemoteParamStore(setup, "p_conc", elems, cache_pulls=False)
    ps.set(0, np.ones(elems, np.float32))
    errs: list = []

    def worker():
        try:
            c = ps_service.PSClient(host, port, timeout_s=120.0)
            p = ps_service.RemoteParamStore(c, "p_conc", elems, cache_pulls=False)
            for _ in range(reps):
                p.get()
            c.close()
        except Exception as e:  # noqa: BLE001 — surfaced below
            errs.append(e)

    threads = [threading.Thread(target=worker) for _ in range(clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    dt = time.perf_counter() - t0
    setup.close()
    if errs:
        raise errs[0]
    mb = elems * 4 / 1e6
    return {"clients": clients, "get_mbs_aggregate": clients * reps * mb / dt}


def run(args) -> dict:
    large_elems = int(args.large_mb * 1e6 / 4)
    small_elems = max(1, int(args.small_kb * 1024 / 4))
    port = ps_service.start_server(0)
    try:
        detail: dict = {
            "large_mb": args.large_mb,
            "small_kb": args.small_kb,
            "memcpy_mbs": memcpy_mbs(large_elems * 4),
            # Loopback sharding is CPU-parallelism: the gate needs to know
            # whether this host can physically express a speedup (a 2-core
            # box saturates its loopback with ONE stream — server writer +
            # client reader — leaving no idle core for shard 2).
            "cpus": os.cpu_count() or 1,
        }
        for dtype in args.dtypes:
            detail[dtype] = bench_dtype(
                "127.0.0.1", port, dtype,
                large_elems=large_elems, small_elems=small_elems,
                reps_large=args.reps_large, reps_small=args.reps_small,
            )
            for k in ("set_mbs_large", "get_mbs_large", "set_get_mbs_large",
                      "push_pop_mbs_large"):
                detail[dtype][k + "_frac_memcpy"] = (
                    detail[dtype][k] / detail["memcpy_mbs"]
                )
        detail["concurrent"] = bench_concurrent_get(
            "127.0.0.1", port, clients=args.clients, elems=large_elems,
            reps=max(2, args.reps_large // 2),
        )
    finally:
        ps_service.stop_server()
    # Shard-scaling axis AFTER the main server is down (its own servers,
    # same total bytes per row).
    detail["shards"] = bench_shards(
        "127.0.0.1", counts=getattr(args, "shards_axis", [1, 2]),
        elems=large_elems, reps=args.reps_large,
    )
    # Replication axis (r12): unreplicated vs forwarded primary/backup
    # pair, same traffic — fresh servers per row like the shard axis.
    detail["replicas"] = bench_replication(
        "127.0.0.1", counts=getattr(args, "replicas_axis", [1, 2]),
        elems=large_elems, reps=args.reps_large,
    )
    return detail


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--large-mb", type=float, default=64.0,
                    help="large payload size (f32-equivalent MB)")
    ap.add_argument("--small-kb", type=float, default=4.0)
    ap.add_argument("--clients", type=int, default=4,
                    help="threads in the concurrent-get row")
    ap.add_argument("--reps-large", type=int, default=8)
    ap.add_argument("--reps-small", type=int, default=200)
    ap.add_argument("--dtypes", default="f32,bf16")
    ap.add_argument("--shards", default="1,2,4",
                    help="shard-scaling axis: local shard-server counts "
                    "(same total bytes per row)")
    ap.add_argument("--replicas", default="1,2",
                    help="replication axis (r12): 1 = unreplicated, 2 = "
                    "forwarded primary/backup pair, same traffic")
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized: 8 MB large payload, 2 clients, few reps")
    ap.add_argument("--json", default="", help="also write the record here")
    args = ap.parse_args()
    if args.quick:
        args.large_mb = min(args.large_mb, 8.0)
        args.clients = min(args.clients, 2)
        args.reps_large = min(args.reps_large, 4)
        args.reps_small = min(args.reps_small, 50)
    args.dtypes = [d for d in args.dtypes.split(",") if d]
    args.shards_axis = [int(s) for s in args.shards.split(",") if s]
    args.replicas_axis = [int(s) for s in args.replicas.split(",") if s]

    detail = run(args)
    headline = detail[args.dtypes[0]]["set_get_mbs_large"]
    rec = {
        "metric": "ps_transport_set_get_mbs",
        "value": round(headline, 1),
        "unit": "MB/s",
        "detail": {
            k: ({kk: round(vv, 4) if isinstance(vv, float) else vv
                 for kk, vv in v.items()} if isinstance(v, dict)
                else round(v, 4) if isinstance(v, float) else v)
            for k, v in detail.items()
        },
    }
    if args.json:
        with open(args.json, "w") as f:
            json.dump(rec, f, indent=1)
    print(json.dumps(rec))


if __name__ == "__main__":
    main()
