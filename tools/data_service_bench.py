"""Data-service streaming microbenchmark (r8 satellite).

Prices the disaggregation tax: the SAME shard directory is consumed once
through the local in-process loader (``data/filestream.py``, the `.npz`
path every training host runs today) and once through the remote data
service (``data/data_service.py``) over loopback — server-side decode,
split dispatch, and the zero-copy batch wire included.  Row format matches
``tools/ps_transport_bench.py``: MB/s of decoded batch bytes delivered,
plus ``*_frac_memcpy`` normalized by the host's own memcpy bandwidth so
``tools/perf_gate.py`` can compare across hosts.

Acceptance contract (ISSUE 3): remote streaming stays within 2x of the
local filestream at 1 MB+ batches — the gate enforces
``remote.stream_mbs >= 0.5 * local.stream_mbs`` from the result file
alone, plus the usual normalized-throughput floor vs the checked-in
``tools/data_service_baseline.json``.

Runs on any CPU box — no accelerator, no jax — like the transport bench.

Usage:
  python tools/data_service_bench.py                 # 512-row (~1.5 MB raw) batches
  python tools/data_service_bench.py --quick         # CI-sized
  python tools/data_service_bench.py --json out.json # also write a file
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from distributed_tensorflow_examples_tpu.data import (  # noqa: E402
    data_service, filestream,
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


def batch_nbytes(b: dict) -> int:
    return sum(np.asarray(v).nbytes for v in b.values())


def make_shards(d: str, *, rows: int, rows_per_shard: int, hw: int) -> None:
    rng = np.random.default_rng(0)
    filestream.write_array_shards(
        d,
        {
            "image": rng.integers(0, 255, size=(rows, hw, hw, 3)).astype(np.uint8),
            "label": rng.integers(0, 10, size=rows).astype(np.int64),
        },
        rows_per_shard=rows_per_shard,
    )


def drain(it, n_batches: int) -> tuple[float, float]:
    """(seconds, decoded MB) for ``n_batches`` pulled from ``it``."""
    first = next(it)  # warmup outside the window (connect/cache fill)
    mb_per = batch_nbytes(first) / 1e6
    t0 = time.perf_counter()
    for _ in range(n_batches):
        next(it)
    return time.perf_counter() - t0, n_batches * mb_per


def bench_local(shard_dir: str, *, batch_rows: int, n_batches: int, seed: int) -> dict:
    pipe = filestream.FileStreamPipeline(
        shard_dir,
        batch_size=batch_rows,
        decode_fn=filestream.image_decode_fn(augment=True, seed=seed),
        seed=seed,
        process_index=0,
        process_count=1,
    )
    it = iter(pipe)
    dt, mb = drain(it, n_batches)
    return {"stream_mbs": mb / dt, "batches_per_s": n_batches / dt}


def bench_remote(
    shard_dir: str, *, batch_rows: int, n_batches: int, seed: int
) -> dict:
    server = data_service.DataServiceServer(
        filestream.list_shards(shard_dir),
        batch_size=batch_rows,
        decode_fn=filestream.image_decode_fn(augment=True, seed=seed),
        seed=seed,
    )
    try:
        src = data_service.RemoteDatasetSource(
            f"dsvc://127.0.0.1:{server.port}", worker_id=0, role="bench_ds"
        )
        row = {}
        # Small-payload round trip (the dispatcher's small-op floor) —
        # measured BEFORE the batch stream starts: the prefetch thread
        # shares the lock-serialized client, so heartbeats issued while
        # multi-MB pulls are in flight would measure queueing, not RTT.
        src._client.heartbeat()  # warm
        t0 = time.perf_counter()
        reps = 50
        for _ in range(reps):
            src._client.heartbeat()
        row["heartbeat_rtt_us"] = (time.perf_counter() - t0) / reps * 1e6
        it = src.batches(repeat=True)
        dt, mb = drain(it, n_batches)
        row.update({"stream_mbs": mb / dt, "batches_per_s": n_batches / dt})
        src.close()
        return row
    finally:
        server.stop()


def run(args) -> dict:
    d = tempfile.mkdtemp(prefix="dtx_dsvc_bench_")
    try:
        make_shards(
            d, rows=args.shards * args.rows_per_shard,
            rows_per_shard=args.rows_per_shard, hw=args.hw,
        )
        raw_batch_mb = args.batch_rows * args.hw * args.hw * 3 / 1e6
        detail: dict = {
            "batch_rows": args.batch_rows,
            "raw_batch_mb": round(raw_batch_mb, 3),
            "shards": args.shards,
            "memcpy_mbs": memcpy_mbs(max(1 << 22, int(raw_batch_mb * 4e6))),
        }
        detail["local"] = bench_local(
            d, batch_rows=args.batch_rows, n_batches=args.n_batches,
            seed=args.seed,
        )
        detail["remote"] = bench_remote(
            d, batch_rows=args.batch_rows, n_batches=args.n_batches,
            seed=args.seed,
        )
        for row in ("local", "remote"):
            detail[row]["stream_mbs_frac_memcpy"] = (
                detail[row]["stream_mbs"] / detail["memcpy_mbs"]
            )
        detail["remote_over_local"] = (
            detail["remote"]["stream_mbs"] / detail["local"]["stream_mbs"]
        )
        return detail
    finally:
        shutil.rmtree(d, ignore_errors=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch-rows", type=int, default=512,
                    help="rows per batch (512 x 32x32x3 uint8 = 1.5 MB raw, "
                    "6 MB decoded f32 — the 1 MB+ acceptance regime)")
    ap.add_argument("--hw", type=int, default=32, help="image height/width")
    ap.add_argument("--rows-per-shard", type=int, default=2048)
    ap.add_argument("--shards", type=int, default=6)
    ap.add_argument("--n-batches", type=int, default=40,
                    help="measured batches per source (after 1 warmup)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized: smaller shards, fewer batches")
    ap.add_argument("--json", default="", help="also write the record here")
    args = ap.parse_args()
    if args.quick:
        args.batch_rows = min(args.batch_rows, 256)
        args.rows_per_shard = min(args.rows_per_shard, 1024)
        args.shards = min(args.shards, 4)
        args.n_batches = min(args.n_batches, 12)

    detail = run(args)
    rec = {
        "metric": "data_service_stream_mbs",
        "value": round(detail["remote"]["stream_mbs"], 1),
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
