"""Named-axis collectives: the TPU-native replacement for the reference's
native communication layer.

The reference's collective stack is hand-written C++ — ring all-reduce
(``ring_reducer.h``), ring gather, hierarchical broadcast, permuter, NCCL
bindings, plus a gRPC Send/Recv rendezvous data plane (SURVEY.md section 2b,
D10/D11).  On TPU every one of those algorithms is *emitted by XLA* and
scheduled onto ICI links; almost all of the framework therefore never calls a
collective by name — the sharded ``jit`` train step (train/step.py) makes
GSPMD insert the all-reduces/gathers/reduce-scatters that the reference's
C++ performs (verified at the HLO level by tests/test_hlo_sharding.py).

Role mapping (reference C++ -> TPU-native):
- ring_reducer.h / NcclAllReduce   -> GSPMD all-reduce from the sharded step
- ring_gatherer.h                  -> GSPMD all-gather from sharding constraints
- reduce-scatter ring phase        -> GSPMD reduce-scatter likewise
- permuter.h                       -> ``ring_permute`` below (hand-scheduled
                                      ring attention is the one consumer that
                                      genuinely needs an explicit schedule)
- hierarchical_tree_broadcaster.h  -> jax.device_put / GSPMD replication

This module keeps only the vocabulary that hand-scheduled ``shard_map`` code
actually consumes (ops/attention.py ring, models/transformer.py flash
sharding); everything XLA emits automatically was deliberately removed rather
than exporting dead parity shims.
"""

from __future__ import annotations

import jax
from jax import lax


def axis_index(axis_name: str):
    """This device's position along the named mesh axis."""
    return lax.axis_index(axis_name)


def ring_permute(x, axis_name: str, *, shift: int = 1):
    """Send to the neighbor ``shift`` hops around the axis ring; the building
    block of ring attention / pipelined collectives (permuter.h role).  XLA
    lowers ``ppermute`` to neighbor ICI transfers."""
    n = lax.axis_size(axis_name)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis_name, perm=perm)


def shard_map(
    fn, mesh, *, in_specs, out_specs, check_vma: bool = False,
    axis_names=None,
):
    """Project-standard wrapper over ``jax.shard_map`` (manual SPMD
    regions) — the ONE place the project calls the jax symbol, with the
    project's default of ``check_vma=False``.  ``axis_names``: mesh axes
    the region is manual over (None = all of them)."""
    kw = {} if axis_names is None else {"axis_names": set(axis_names)}
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check_vma, **kw,
    )
