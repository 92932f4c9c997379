"""Device-mesh construction: the TPU-native cluster topology layer.

The reference describes its cluster as job-name -> host:port lists
(``tf.train.ClusterSpec``, see SURVEY.md section 2b component D1) and starts a
gRPC server per process (D2).  On TPU the topology is instead a named
``jax.sharding.Mesh`` over all addressable chips; "jobs" become *mesh axes*:

- ``data``   — pure data parallelism (the PS/worker "worker" job's role)
- ``model``  — tensor parallelism (the PS-sharded-variable role, D3/D4)
- ``seq``    — sequence/context parallelism (ring attention; no reference
               analog — long-context growth axis)
- ``expert`` — expert parallelism (MoE; no reference analog)
- ``pipe``   — pipeline parallelism

ICI vs DCN: when a mesh spans multiple slices/hosts, the outermost axis
(``data`` by default) is laid across DCN while inner axes stay on ICI — this
is what ``mesh_utils.create_hybrid_device_mesh`` encodes.  Collectives along
inner axes then ride ICI links.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Sequence

import numpy as np

import jax
from jax.experimental import mesh_utils
from jax.sharding import Mesh

AXIS_SLICE = "slice"
AXIS_DATA = "data"
AXIS_SEQ = "seq"
AXIS_PIPE = "pipe"
AXIS_MODEL = "model"
AXIS_EXPERT = "expert"

#: Canonical axis order, outermost (DCN-friendly, infrequent comms) first and
#: innermost (ICI-hungry, per-layer comms) last.  Tensor-parallel collectives
#: fire most often, so ``model`` sits innermost where ICI is densest.
#: ``slice`` (r4) makes the DCN slice boundary an EXPLICIT outermost axis
#: when a workload wants to scope collectives slice-locally (ghost-batch BN
#: statistics — models/resnet.Config.bn_ghost_slices); batch then shards
#: over ('slice', 'data') jointly.
DEFAULT_AXES: tuple[str, ...] = (
    AXIS_SLICE, AXIS_DATA, AXIS_PIPE, AXIS_EXPERT, AXIS_SEQ, AXIS_MODEL
)


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Logical parallelism layout.  ``-1`` on exactly one axis means "all
    remaining devices" (like the reference's implicit worker count from
    ``--worker_hosts`` length).

    Replaces: ``ClusterSpec({"ps": [...], "worker": [...]})`` — but instead of
    naming processes it names parallelism dimensions.
    """

    data: int = -1
    pipe: int = 1
    expert: int = 1
    seq: int = 1
    model: int = 1
    slice: int = 1

    def sizes(self) -> dict[str, int]:
        return {
            AXIS_SLICE: self.slice,
            AXIS_DATA: self.data,
            AXIS_PIPE: self.pipe,
            AXIS_EXPERT: self.expert,
            AXIS_SEQ: self.seq,
            AXIS_MODEL: self.model,
        }

    def resolved(self, n_devices: int) -> dict[str, int]:
        """Resolve the single ``-1`` axis against the device count."""
        sizes = self.sizes()
        unknown = [a for a, s in sizes.items() if s == -1]
        if len(unknown) > 1:
            raise ValueError(f"at most one mesh axis may be -1, got {unknown}")
        fixed = math.prod(s for s in sizes.values() if s != -1)
        if unknown:
            if n_devices % fixed:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes product {fixed}"
                )
            sizes[unknown[0]] = n_devices // fixed
        if math.prod(sizes.values()) != n_devices:
            raise ValueError(
                f"mesh {sizes} needs {math.prod(sizes.values())} devices, "
                f"have {n_devices}"
            )
        return sizes

    @staticmethod
    def parse(text: str) -> "MeshSpec":
        """Parse ``"data=8,model=2"`` (axes omitted default to 1, data to -1)."""
        if not text:
            return MeshSpec()
        kwargs: dict[str, int] = {}
        for part in text.split(","):
            name, _, value = part.partition("=")
            name = name.strip()
            if name not in DEFAULT_AXES:
                raise ValueError(f"unknown mesh axis {name!r}; valid: {DEFAULT_AXES}")
            kwargs[name] = int(value)
        return MeshSpec(**kwargs)


def _num_slices(devices: Sequence[jax.Device]) -> int:
    slice_ids = {getattr(d, "slice_index", 0) for d in devices}
    return len(slice_ids)


def build_mesh(
    spec: MeshSpec | None = None,
    *,
    devices: Sequence[jax.Device] | None = None,
    allow_split_physical_axes: bool = False,
) -> Mesh:
    """Build an ICI-topology-aware ``Mesh`` from a logical spec.

    Single-slice: ``mesh_utils.create_device_mesh`` orders devices so that
    innermost mesh axes map to physically adjacent chips (ring-friendly).
    Multi-slice (v5e-64 = 8 hosts over DCN): a hybrid mesh lays the outermost
    non-trivial axis across slices over DCN, the rest within-slice over ICI —
    the TPU-native analog of the reference's "NCCL within node, gRPC across
    nodes" split (SURVEY.md section 5.8).
    """
    spec = spec or MeshSpec()
    devices = list(devices if devices is not None else jax.devices())
    sizes = spec.resolved(len(devices))
    axis_names = tuple(sizes)
    shape = tuple(sizes[a] for a in axis_names)

    n_slices = _num_slices(devices)
    if n_slices > 1:
        per_slice = len(devices) // n_slices
        # Put the DCN dimension on the outermost axis whose size it divides;
        # typically `data`.
        dcn_shape = [1] * len(shape)
        ici_shape = list(shape)
        for i, s in enumerate(shape):
            if s % n_slices == 0:
                dcn_shape[i] = n_slices
                ici_shape[i] = s // n_slices
                break
        else:
            raise ValueError(
                f"no mesh axis in {sizes} divisible by slice count {n_slices}"
            )
        if math.prod(ici_shape) != per_slice:
            raise ValueError(
                f"per-slice mesh {ici_shape} != {per_slice} devices per slice"
            )
        mesh_devices = mesh_utils.create_hybrid_device_mesh(
            tuple(ici_shape),
            tuple(dcn_shape),
            devices=devices,
            allow_split_physical_axes=allow_split_physical_axes,
        )
    else:
        try:
            mesh_devices = mesh_utils.create_device_mesh(
                shape,
                devices=devices,
                allow_split_physical_axes=allow_split_physical_axes,
            )
        except (ValueError, NotImplementedError):
            # Topology-unaware reshape for CPU device lists only (odd virtual
            # device counts in tests have no topology to respect); on an
            # accelerator a refused shape is an error, not a silent
            # topology-blind mesh.
            if any(d.platform != "cpu" for d in devices):
                raise
            mesh_devices = np.asarray(devices).reshape(shape)
    return Mesh(mesh_devices, axis_names)


def local_mesh_for_testing(
    sizes: dict[str, int] | None = None, *, platform: str = "cpu"
) -> Mesh:
    """Fake multi-chip mesh on host devices — the analog of the reference's
    in-process fake cluster (``multi_worker_test_base.create_in_process_cluster``,
    SURVEY.md section 4).  Requires
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
    """
    sizes = dict(sizes or {})
    unknown = set(sizes) - set(DEFAULT_AXES)
    if unknown:
        raise ValueError(f"unknown mesh axes {sorted(unknown)}; valid: {DEFAULT_AXES}")
    devices = jax.devices(platform)
    if not sizes:
        sizes = {AXIS_DATA: len(devices)}
    for axis in DEFAULT_AXES:
        sizes.setdefault(axis, 1)
    ordered = {a: sizes[a] for a in DEFAULT_AXES}
    n = math.prod(ordered.values())
    if n > len(devices):
        raise ValueError(f"need {n} {platform} devices, have {len(devices)}")
    arr = np.asarray(devices[:n]).reshape(tuple(ordered.values()))
    return Mesh(arr, tuple(ordered))
