"""Serving-plane microbenchmark (r10 satellite).

Prices the online inference plane end to end on loopback: an in-process
(sharded) parameter store publishes a small row-wise model, one
``serve.ModelReplicaServer`` tracks it, and client threads drive predict
load through the full stack — wire framing, micro-batcher, padded jitted
apply, per-request scatter.  Two regimes per row set:

- **single** — ONE client, requests strictly one at a time: every request
  pays the full round trip + its own apply window (the micro-batcher's
  ``max_wait_ms`` included) — the no-coalescing floor.
- **batched** — N concurrent clients hammering the same replica: requests
  arriving while an apply runs coalesce into the next batch, so the apply
  cost amortizes over up to ``max_batch`` requests.

A third regime since r17 (the unified server core):

- **concurrency** — ``--clients=64,256`` connections, each issuing
  requests at a FIXED per-client rate (paced, open-loop per client).
  Load scales with the connection count, so the p99 ratio between the
  widest and narrowest counts prices the PER-CONNECTION cost of the
  server runtime — the C10k claim the selector core makes.  Gated by
  ``perf_gate``'s ``concurrent_p99_ratio`` rule (p99 at 256 <= 3x p99
  at 64, from the result alone).

Acceptance contract (ISSUE 5): ``batched_speedup = batched.qps /
single.qps >= 3.0`` at ``max_batch=32`` — enforced by ``tools/perf_gate.py``
from the result file alone, plus the usual memcpy-normalized throughput
floor vs the checked-in ``tools/serving_baseline.json``.  Rows are
best-of-3 trials; MB/s counts request+response payload bytes so the
``*_frac_memcpy`` normalization is comparable across hosts (same
convention as the transport/data benches).

Runs on any CPU box — JAX on CPU, no accelerator.

Usage:
  python tools/serving_bench.py                  # full rows
  python tools/serving_bench.py --quick          # CI-sized
  python tools/serving_bench.py --json out.json  # also write a file
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

# A host-side rig: this process and every child it starts run JAX on the
# CPU, whatever the machine's own JAX_PLATFORMS says (the chip machine sets
# "tpu,cpu", and a chip belongs to one process at a time).
os.environ["JAX_PLATFORMS"] = "cpu"

from distributed_tensorflow_examples_tpu import serve  # noqa: E402
from distributed_tensorflow_examples_tpu.parallel import (  # noqa: E402
    ps_service, ps_shard,
)


def memcpy_mbs(nbytes: int) -> float:
    """Host memcpy bandwidth — the normalizer that makes throughput rows
    comparable across hosts (same definition as ps_transport_bench)."""
    src = np.ones(nbytes // 4, np.float32)
    dst = np.empty_like(src)
    np.copyto(dst, src)  # warm
    reps = 8
    t0 = time.perf_counter()
    for _ in range(reps):
        np.copyto(dst, src)
    return reps * nbytes / (time.perf_counter() - t0) / 1e6


# A serving-shaped model: a 2-layer MLP whose padded 32-row apply costs a
# few ms on a CPU dev box — enough compute that coalescing has something
# real to amortize (a trivially cheap apply measures only wire/thread
# overhead, which batching deliberately does NOT amortize).
D_IN, D_HID, D_OUT = 512, 512, 128
NUM_ELEMS = D_IN * D_HID + D_HID + D_HID * D_OUT


def make_model():
    import jax.numpy as jnp

    def init_fn(rng):
        return {
            "w1": jnp.zeros((D_IN, D_HID), jnp.float32),
            "b1": jnp.zeros((D_HID,), jnp.float32),
            "w2": jnp.zeros((D_HID, D_OUT), jnp.float32),
        }

    def predict_fn(params, batch):
        h = jnp.maximum(batch["x"] @ params["w1"] + params["b1"], 0.0)
        return h @ params["w2"]

    return init_fn, predict_fn


def publish_params(addrs, num_elems: int, step: int = 1):
    group = ps_shard.ShardedPSClients(addrs, role="bench_pub", op_timeout_s=10.0)
    layout = ps_shard.ShardLayout(num_elems, len(addrs))
    pstore = ps_shard.ShardedParamStore(group, "params", layout)
    rng = np.random.default_rng(0)
    pstore.set(step, rng.normal(size=num_elems).astype(np.float32) * 0.05)
    return group, pstore


def drive(
    addr, *, clients: int, n_requests: int, rows: int, seconds_cap: float,
) -> dict:
    """``n_requests`` predicts split over ``clients`` threads (each thread
    strictly one-at-a-time on its own connection); returns qps + latency
    percentiles across every request."""
    per = max(1, n_requests // clients)
    lat: list[list[float]] = [[] for _ in range(clients)]
    errors: list = []
    x = np.random.default_rng(7).normal(size=(rows, D_IN)).astype(np.float32)
    start = threading.Barrier(clients + 1)

    def body(ci: int) -> None:
        try:
            c = serve.ServeClient(*addr, role=f"bench{ci}_sv")
            c.predict({"x": x})  # warm (connect + jit outside the window)
            start.wait()
            t_end = time.perf_counter() + seconds_cap
            for _ in range(per):
                t0 = time.perf_counter()
                c.predict({"x": x})
                lat[ci].append(time.perf_counter() - t0)
                if time.perf_counter() > t_end:
                    break
            c.close()
        except BaseException as e:  # noqa: BLE001 — surfaced below
            errors.append(e)
            try:
                start.wait(timeout=1.0)
            except Exception:
                pass

    threads = [threading.Thread(target=body, args=(i,)) for i in range(clients)]
    for t in threads:
        t.start()
    start.wait()
    t0 = time.perf_counter()
    for t in threads:
        t.join()
    dt = time.perf_counter() - t0
    if errors:
        raise errors[0]
    all_lat = np.concatenate([np.asarray(l) for l in lat if l])
    n = int(all_lat.size)
    return {
        "clients": clients,
        "requests": n,
        "qps": n / dt,
        "p50_ms": float(np.percentile(all_lat, 50) * 1e3),
        "p99_ms": float(np.percentile(all_lat, 99) * 1e3),
    }


def drive_paced(
    addr, *, clients: int, rate_per_client: float, duration_s: float,
    rows: int,
) -> dict:
    """The r17 concurrency axis: ``clients`` connections each issuing
    requests at a FIXED per-client rate (a paced, open-loop-per-client
    load), latency measured per request.  Holding per-client behavior
    constant while the connection count scales 4x is what isolates the
    per-connection cost of the server runtime: under the selector core,
    p99 stays bounded as connections multiply; a regression back to
    per-connection threads/convoys (or an O(conns) selector pass) shows
    up directly as the p99 ratio blowing past the gate."""
    per = max(1, int(rate_per_client * duration_s))
    lat: list[list[float]] = [[] for _ in range(clients)]
    errors: list = []
    x = np.random.default_rng(7).normal(size=(rows, D_IN)).astype(np.float32)
    start = threading.Barrier(clients + 1)
    period = 1.0 / rate_per_client

    def body(ci: int) -> None:
        try:
            c = serve.ServeClient(*addr, role=f"bench{ci}_sv")
            c.predict({"x": x})  # warm (connect + jit outside the window)
        except BaseException as e:  # noqa: BLE001 — surfaced below
            # ABORT the barrier rather than wait it with a timeout: a
            # timed compensation can itself break the barrier when 255
            # peers warm slowly, surfacing BrokenBarrierError instead of
            # the real failure.  Aborting releases everyone immediately
            # and the main thread re-raises errors[0].
            errors.append(e)
            start.abort()
            return
        try:
            start.wait()
        except threading.BrokenBarrierError:
            c.close()
            return
        try:
            # Deterministic per-client phase spreads arrivals uniformly.
            next_t = time.perf_counter() + (ci % 16) * period / 16
            for _ in range(per):
                now = time.perf_counter()
                if now < next_t:
                    time.sleep(next_t - now)
                next_t += period
                t0 = time.perf_counter()
                c.predict({"x": x})
                lat[ci].append(time.perf_counter() - t0)
            c.close()
        except BaseException as e:  # noqa: BLE001 — surfaced below
            errors.append(e)

    threads = [threading.Thread(target=body, args=(i,)) for i in range(clients)]
    for t in threads:
        t.start()
    try:
        start.wait()
    except threading.BrokenBarrierError:
        pass  # a warm-up failed; errors[0] carries the cause
    t0 = time.perf_counter()
    for t in threads:
        t.join()
    dt = time.perf_counter() - t0
    if errors:
        raise errors[0]
    all_lat = np.concatenate([np.asarray(l) for l in lat if l])
    return {
        "clients": clients,
        "rate_per_client": rate_per_client,
        "requests": int(all_lat.size),
        "qps": all_lat.size / dt,
        "p50_ms": float(np.percentile(all_lat, 50) * 1e3),
        "p99_ms": float(np.percentile(all_lat, 99) * 1e3),
    }


def best_of(trials: int, fn) -> dict:
    rows = [fn() for _ in range(trials)]
    return max(rows, key=lambda r: r["qps"])


def run(args) -> dict:
    init_fn, predict_fn = make_model()
    ports = [
        ps_service.start_server(0, shard_id=i, shard_count=args.ps_shards)
        for i in range(args.ps_shards)
    ]
    addrs = [("127.0.0.1", p) for p in ports]
    group, _ = publish_params(addrs, NUM_ELEMS)
    server = serve.ModelReplicaServer(
        init_fn, predict_fn, addrs,
        max_batch=args.max_batch, max_wait_ms=args.max_wait_ms,
        queue_depth=max(256, 4 * args.max_batch), role="bench_serve",
    )
    try:
        if not server.wait_for_model(30.0):
            raise RuntimeError("replica never pulled the published params")
        addr = ("127.0.0.1", server.port)
        # Payload bytes per request: input rows + output rows (the bytes
        # the wire actually moves), for the memcpy normalization.
        payload_bytes = args.rows * (D_IN + D_OUT) * 4
        detail: dict = {
            "max_batch": args.max_batch,
            "max_wait_ms": args.max_wait_ms,
            "rows_per_request": args.rows,
            "ps_shards": args.ps_shards,
            "payload_bytes": payload_bytes,
            "cpus": os.cpu_count() or 1,
            "memcpy_mbs": memcpy_mbs(1 << 24),
        }
        detail["single"] = best_of(
            args.trials,
            lambda: drive(
                addr, clients=1, n_requests=args.n_single, rows=args.rows,
                seconds_cap=args.seconds_cap,
            ),
        )
        sweep = {}
        for nc in args.client_sweep:
            sweep[str(nc)] = best_of(
                args.trials,
                lambda nc=nc: drive(
                    addr, clients=nc, n_requests=args.n_batched,
                    rows=args.rows, seconds_cap=args.seconds_cap,
                ),
            )
        detail["client_sweep"] = sweep
        # The headline batched row: the sweep's widest client count (the
        # regime that can actually fill max_batch).
        detail["batched"] = sweep[str(max(args.client_sweep))]
        # The r17 concurrency axis (--clients=64,256): paced per-client
        # load, p99 vs connection count.  The perf_gate rule
        # ``concurrent_p99_ratio`` bounds p99 at the widest count to 3x
        # the narrowest — the "bounded p99 under C10k-style connection
        # scaling" acceptance of the unified server core.
        if args.clients:
            conc_rows = {}
            for nc in args.clients:
                conc_rows[str(nc)] = drive_paced(
                    addr, clients=nc,
                    rate_per_client=args.concurrency_rate,
                    duration_s=args.concurrency_secs, rows=args.rows,
                )
            ratio = None
            lo, hi = min(args.clients), max(args.clients)
            if lo != hi and conc_rows[str(lo)]["p99_ms"] > 0:
                ratio = (
                    conc_rows[str(hi)]["p99_ms"] / conc_rows[str(lo)]["p99_ms"]
                )
            detail["concurrency"] = {
                "rate_per_client": args.concurrency_rate,
                "duration_s": args.concurrency_secs,
                "clients": conc_rows,
                "p99_ratio": ratio,
            }
        for row in ("single", "batched"):
            detail[row]["stream_mbs"] = (
                detail[row]["qps"] * payload_bytes / 1e6
            )
            detail[row]["stream_mbs_frac_memcpy"] = (
                detail[row]["stream_mbs"] / detail["memcpy_mbs"]
            )
        detail["batched_speedup"] = (
            detail["batched"]["qps"] / detail["single"]["qps"]
        )
        detail["server_stats"] = {
            k: v
            for k, v in server.stats().items()
            if k.startswith(("batcher_", "serve/")) or k in (
                "requests", "predict_rows", "overloads",
            )
        }
        return detail
    finally:
        server.stop()
        group.close()
        ps_service.stop_server()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--max-batch", type=int, default=32,
                    help="micro-batcher row budget (the acceptance bound "
                    "applies at >= 32)")
    ap.add_argument("--max-wait-ms", type=float, default=10.0,
                    help="coalescing window, applied to BOTH regimes (the "
                    "single row pays it in full; the batched row amortizes "
                    "it).  Must exceed the host's request-arrival jitter "
                    "or nothing coalesces — on a 2-core box ~10 ms is the "
                    "floor at which 32 clients fill real batches")
    ap.add_argument("--rows", type=int, default=1,
                    help="rows per predict request")
    ap.add_argument("--ps-shards", type=int, default=2)
    ap.add_argument("--client-sweep", type=int, nargs="+",
                    default=[4, 16, 32],
                    help="concurrent-client counts for the batched rows")
    ap.add_argument("--clients", type=int, nargs="+", default=[64, 256],
                    help="connection counts for the r17 concurrency axis "
                    "(paced per-client load; p99 at max(clients) is gated "
                    "to <= 3x p99 at min(clients)).  Empty list skips the "
                    "axis")
    ap.add_argument("--concurrency-rate", type=float, default=2.0,
                    help="per-client request rate (req/s) on the "
                    "concurrency axis — load scales WITH the connection "
                    "count, so the ratio isolates per-connection runtime "
                    "cost, not saturation queueing")
    ap.add_argument("--concurrency-secs", type=float, default=10.0,
                    help="per-row wall time on the concurrency axis")
    ap.add_argument("--n-single", type=int, default=300,
                    help="single-client measured requests")
    ap.add_argument("--n-batched", type=int, default=2000,
                    help="total measured requests per batched row")
    ap.add_argument("--trials", type=int, default=3, help="best-of-N")
    ap.add_argument("--seconds-cap", type=float, default=20.0,
                    help="per-trial wall cap (slow boxes finish early "
                    "with fewer requests instead of stalling CI)")
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized: fewer requests, 1 trial, small sweep")
    ap.add_argument("--json", default="", help="also write the record here")
    args = ap.parse_args()
    if args.quick:
        args.client_sweep = [4, 32]
        args.n_single = min(args.n_single, 80)
        args.n_batched = min(args.n_batched, 600)
        args.trials = 1
        args.seconds_cap = min(args.seconds_cap, 10.0)
        args.concurrency_secs = min(args.concurrency_secs, 5.0)

    detail = run(args)

    def _round(v):
        # 6 decimals: the *_frac_memcpy rows are tiny (1 KB payloads vs
        # GB/s memcpy) and must not round to a vacuous 0.0 baseline.
        if isinstance(v, dict):
            return {k: _round(x) for k, x in v.items()}
        return round(v, 6) if isinstance(v, float) else v

    rec = {
        "metric": "serving_qps",
        "value": round(detail["batched"]["qps"], 1),
        "unit": "req/s",
        "detail": _round(detail),
    }
    if args.json:
        with open(args.json, "w") as f:
            json.dump(rec, f, indent=1)
    print(json.dumps(rec))


if __name__ == "__main__":
    main()
