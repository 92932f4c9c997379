"""Batching, per-host sharding, and device infeed.

Replaces the reference's input stack (SURVEY.md T7 ``tf.data`` +
D14 ``DistributedDataset``): each host materialises only its 1/num_hosts shard
of the stream (``Dataset.shard`` analog), batches are device_put as *global*
arrays sharded over the mesh's data axes, and a small background thread keeps
``prefetch`` batches in flight so the host->HBM copy overlaps the previous
step's compute (the ``Dataset.prefetch``/host-infeed analog).
"""

from __future__ import annotations

import queue
import threading
from typing import Any, Callable, Iterable, Iterator

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..parallel.sharding import batch_sharding
from ..utils import telemetry

# ``prefetch_to_mesh``'s leaf spans (handles resolved once, here).
_SPAN_NEXT = telemetry.span("input/next")
_SPAN_TO_DEVICE = telemetry.span("input/to_device")
_SPAN_WAIT = telemetry.span("input/wait")


class InMemoryPipeline:
    """Shuffled, sharded, infinitely-repeating batch stream over in-memory
    numpy arrays (every reference workload's dataset fits in host RAM).

    ``batch_size`` is the GLOBAL batch size; each host yields its local
    ``batch_size // num_processes`` rows, and ``as_global`` assembles them
    into one mesh-sharded ``jax.Array`` per field.
    """

    def __init__(
        self,
        arrays: dict[str, np.ndarray],
        *,
        batch_size: int,
        shuffle: bool = True,
        seed: int = 0,
        process_index: int | None = None,
        process_count: int | None = None,
        drop_remainder: bool = True,
    ):
        lengths = {k: len(v) for k, v in arrays.items()}
        if len(set(lengths.values())) != 1:
            raise ValueError(f"mismatched field lengths {lengths}")
        self.fields = dict(arrays)
        self.n = next(iter(lengths.values()))
        self.global_batch = batch_size
        self.pidx = jax.process_index() if process_index is None else process_index
        self.pcount = jax.process_count() if process_count is None else process_count
        if batch_size % self.pcount:
            raise ValueError(
                f"global batch {batch_size} not divisible by {self.pcount} hosts"
            )
        self.local_batch = batch_size // self.pcount
        self.shuffle = shuffle
        self.seed = seed
        self.drop_remainder = drop_remainder

    def __iter__(self) -> Iterator[dict[str, np.ndarray]]:
        """Yields local (per-host) batches forever; reshuffles each epoch with
        a deterministic per-epoch seed so every host agrees on the permutation
        (the determinism knob of SURVEY.md section 5.2)."""
        epoch = 0
        while True:
            if self.shuffle:
                order = np.random.default_rng((self.seed, epoch)).permutation(self.n)
            else:
                order = np.arange(self.n)
            # Host shard (Dataset.shard analog). Truncate to a multiple of the
            # host count first so every host's shard has the SAME length —
            # otherwise hosts would cross epoch boundaries at different steps
            # and global batches would silently mix epoch permutations.
            order = order[: self.n - (self.n % self.pcount)]
            local = order[self.pidx :: self.pcount]
            steps = len(local) // self.local_batch
            for s in range(steps):
                idx = local[s * self.local_batch : (s + 1) * self.local_batch]
                yield {k: v[idx] for k, v in self.fields.items()}
            epoch += 1


def as_global(
    batch: dict[str, np.ndarray],
    mesh: Mesh,
    *,
    spec: PartitionSpec | None = None,
) -> dict[str, jax.Array]:
    """Assemble per-host local batches into global mesh-sharded arrays.

    ``spec`` overrides the default leading-dim-over-data-axis layout (e.g.
    ``P(None, 'data')`` for [unroll, batch, ...] super-batches).
    """
    if spec is None:
        sharding = batch_sharding(mesh)
    else:
        sharding = NamedSharding(mesh, spec)
    out = {}
    for k, v in batch.items():
        out[k] = jax.make_array_from_process_local_data(sharding, np.asarray(v))
    return out


def prefetch_to_mesh(
    it: Iterable[dict[str, np.ndarray]],
    mesh: Mesh,
    *,
    depth: int = 2,
    spec: PartitionSpec | None = None,
    transform: Callable[[dict[str, np.ndarray]], Any] | None = None,
) -> Iterator[Any]:
    """Background-thread infeed: keeps ``depth`` global device batches queued
    ahead of the consumer, overlapping host->HBM DMA with step compute.

    Three leaf spans say where its time goes: ``input/next`` (the source's
    ``next``: the host gather), ``input/to_device`` (``as_global``) on the
    producer's thread, ``input/wait`` (the consumer blocked on the queue)."""
    q: queue.Queue = queue.Queue(maxsize=depth)
    stop = threading.Event()
    _SENTINEL = object()

    def _producer():
        try:
            source = iter(it)
            while not stop.is_set():
                with _SPAN_NEXT:
                    batch = next(source, _SENTINEL)
                if batch is _SENTINEL:
                    return
                if transform is not None:
                    batch = transform(batch)
                with _SPAN_TO_DEVICE:
                    batch = as_global(batch, mesh, spec=spec)
                q.put(batch)
        except Exception as e:  # surface producer errors at the consumer
            q.put(e)
        finally:
            q.put(_SENTINEL)

    t = threading.Thread(target=_producer, daemon=True, name="infeed-prefetch")
    t.start()
    try:
        while True:
            with _SPAN_WAIT:
                item = q.get()
            if item is _SENTINEL:
                return
            if isinstance(item, Exception):
                raise item
            yield item
    finally:
        stop.set()
        # Drain so the producer's blocked put() can observe stop and exit.
        try:
            while True:
                q.get_nowait()
        except queue.Empty:
            pass


def stack_for_unroll(
    it: Iterator[dict[str, np.ndarray]], k: int
) -> Iterator[dict[str, np.ndarray]]:
    """Group k consecutive local batches into one [k, ...] super-batch for
    multi-step-unrolled train steps (amortises dispatch for tiny models —
    SURVEY.md section 7 'hard parts' #2)."""
    while True:
        group = [next(it) for _ in range(k)]
        yield {key: np.stack([g[key] for g in group]) for key in group[0]}
