"""W3: ResNet-50 — the reference's MirroredStrategy/NCCL workload
(SURVEY.md section 2a W3, BASELINE.json:9; ref model:
``keras.applications.ResNet50``, keras/src/applications/resnet.py:391).

ResNet-50 v1.5 (stride-2 in the 3x3 of each downsampling bottleneck — the
variant every modern benchmark reports), built TPU-first:

- NHWC activations x HWIO kernels: the layout XLA tiles best onto the MXU.
- bf16 conv compute with f32 accumulation (``preferred_element_type``).
- BatchNorm over the *global* batch (sharded batch => XLA inserts the
  cross-replica reduction; SyncBN semantics — see layers.batchnorm).
- Mutable BN running stats thread through ``model_state``, mirroring the
  params tree — the framework's analog of TF's update-ops collection.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from . import layers


@dataclasses.dataclass(frozen=True)
class Config:
    num_classes: int = 1000
    stage_sizes: tuple[int, ...] = (3, 4, 6, 3)  # ResNet-50
    width: int = 64
    compute_dtype: str = "bfloat16"
    bn_momentum: float = 0.9
    #: "s2d": space-to-depth stem — the 7x7/s2 conv on 3 channels is the
    #: worst-tiling op in the network (3 input channels against the MXU's
    #: 128 lanes); reshaping the input to [H/2, W/2, 12] and running the
    #: *exactly equivalent* 4x4/s1 conv (kernel re-indexed, see _stem) is
    #: the standard TPU ResNet transform.  "conv7": the literal stem.
    stem: str = "s2d"
    #: Ghost-batch BN for multi-slice meshes (r4): >0 scopes every BN's
    #: batch statistics to a slice-local sub-axis of data — the mesh must
    #: carry an outermost 'slice' axis of this size, and the batch shards
    #: over ('slice', 'data').  All 98 per-layer statistics reductions
    #: then ride ICI; only the gradient all-reduce crosses DCN
    #: (layers._batchnorm_ghost; hybrid evidence in BASELINE.md).
    bn_ghost_slices: int = 0

    @property
    def dtype(self):
        return jnp.dtype(self.compute_dtype)


def _bottleneck_init(rng, cin: int, mid: int, *, downsample: bool, ghost: int = 0):
    """One bottleneck: 1x1 reduce -> 3x3 -> 1x1 expand (+ projection)."""
    cout = 4 * mid
    ks = jax.random.split(rng, 4)
    p, s = {}, {}
    p["conv1"] = layers.conv_init(ks[0], 1, 1, cin, mid, use_bias=False)
    p["bn1"], s["bn1"] = layers.batchnorm_init(mid, ghost_slices=ghost)
    p["conv2"] = layers.conv_init(ks[1], 3, 3, mid, mid, use_bias=False)
    p["bn2"], s["bn2"] = layers.batchnorm_init(mid, ghost_slices=ghost)
    p["conv3"] = layers.conv_init(ks[2], 1, 1, mid, cout, use_bias=False)
    p["bn3"], s["bn3"] = layers.batchnorm_init(cout, ghost_slices=ghost)
    if downsample or cin != cout:
        p["proj"] = layers.conv_init(ks[3], 1, 1, cin, cout, use_bias=False)
        p["bn_proj"], s["bn_proj"] = layers.batchnorm_init(cout, ghost_slices=ghost)
    return p, s


def _bottleneck_apply(cfg, p, s, x, *, stride: int, train: bool, mesh=None):
    new_s = {}
    shortcut = x
    bn = lambda name, t, relu=False: layers.batchnorm(
        p[name], s[name], t, train=train, momentum=cfg.bn_momentum, mesh=mesh,
        relu=relu, ghost_slices=cfg.bn_ghost_slices,
    )
    y = layers.conv2d(p["conv1"], x, stride=1, dtype=cfg.dtype)
    y, new_s["bn1"] = bn("bn1", y, relu=True)
    # v1.5: the stride lives on the 3x3, not the 1x1.
    y = layers.conv2d(p["conv2"], y, stride=stride, dtype=cfg.dtype)
    y, new_s["bn2"] = bn("bn2", y, relu=True)
    y = layers.conv2d(p["conv3"], y, stride=1, dtype=cfg.dtype)
    y, new_s["bn3"] = bn("bn3", y)
    if "proj" in p:
        shortcut = layers.conv2d(p["proj"], x, stride=stride, dtype=cfg.dtype)
        shortcut, new_s["bn_proj"] = bn("bn_proj", shortcut)
    return jax.nn.relu(y + shortcut), new_s


def init(cfg: Config, rng: jax.Array, *, in_channels: int = 3):
    rngs = jax.random.split(rng, 2 + sum(cfg.stage_sizes))
    params: dict = {}
    state: dict = {}
    params["stem"] = layers.conv_init(rngs[0], 7, 7, in_channels, cfg.width, use_bias=False)
    params["bn_stem"], state["bn_stem"] = layers.batchnorm_init(
        cfg.width, ghost_slices=cfg.bn_ghost_slices
    )
    cin = cfg.width
    k = 1
    for stage, n_blocks in enumerate(cfg.stage_sizes):
        mid = cfg.width * (2 ** stage)
        for block in range(n_blocks):
            down = stage > 0 and block == 0
            p, s = _bottleneck_init(
                rngs[k], cin, mid, downsample=down or cin != 4 * mid,
                ghost=cfg.bn_ghost_slices,
            )
            params[f"stage{stage}/block{block}"] = p
            state[f"stage{stage}/block{block}"] = s
            cin = 4 * mid
            k += 1
    params["head"] = layers.dense_init(rngs[-1], cin, cfg.num_classes)
    return params, state


def _stem_conv(cfg: Config, kernel, x):
    """The 7x7/s2 stem conv, optionally as its space-to-depth equivalent.

    s2d: input [B,H,W,C] -> [B,H/2,W/2,4C] (2x2 blocks into channels); the
    7x7/s2 conv becomes an EXACTLY equivalent 4x4/s1 conv whose kernel is the
    7x7 kernel zero-padded to 8x8 and re-indexed by (tap, parity):
    ``K_s2d[a,b,(dy,dx,c)] = K8[2a+dy, 2b+dx, c]`` with padding lo=1, hi=2
    (derivation: output row i of the original reads input rows 2i-2..2i+4 =
    s2d rows i-1..i+2).  Params stay the 7x7 kernel, so init/checkpoints are
    layout-independent; the re-index is 12k FLOPs, folded by XLA into the
    weight path.  Why: a 3-input-channel conv tiles at 3/128 MXU lane
    occupancy — the single worst op in the network (~15% of fwd measured).
    """
    B, H, W, C = x.shape
    if cfg.stem == "conv7" or H % 2 or W % 2:
        return layers.conv2d({"kernel": kernel}, x, stride=2, dtype=cfg.dtype)
    xb = x.astype(cfg.dtype)
    xs = (
        xb.reshape(B, H // 2, 2, W // 2, 2, C)
        .transpose(0, 1, 3, 2, 4, 5)
        .reshape(B, H // 2, W // 2, 4 * C)
    )
    k8 = jnp.pad(kernel, ((0, 1), (0, 1), (0, 0), (0, 0)))
    cout = k8.shape[-1]
    ks = (
        k8.reshape(4, 2, 4, 2, C, cout)
        .transpose(0, 2, 1, 3, 4, 5)
        .reshape(4, 4, 4 * C, cout)
    ).astype(cfg.dtype)
    return jax.lax.conv_general_dilated(
        xs,
        ks,
        window_strides=(1, 1),
        padding=((1, 2), (1, 2)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def apply(cfg: Config, params, model_state, x, *, train: bool, mesh=None):
    """x: [B, H, W, 3] -> (logits [B, num_classes], new_model_state).

    ``mesh``: what the ghost-batch BatchNorms (``cfg.bn_ghost_slices``) pin
    their shardings with; unused otherwise."""
    new_state: dict = {}
    y = _stem_conv(cfg, params["stem"]["kernel"], x)
    y, new_state["bn_stem"] = layers.batchnorm(
        params["bn_stem"], model_state["bn_stem"], y, train=train,
        momentum=cfg.bn_momentum, mesh=mesh, relu=True,
        ghost_slices=cfg.bn_ghost_slices,
    )
    # Explicit (1,1) pad + VALID, NOT "SAME": for even H (112), SAME pads
    # (lo=0, hi=1), which shifts every pooling window by one pixel.
    y = jax.lax.reduce_window(
        y,
        -jnp.inf,
        jax.lax.max,
        (1, 3, 3, 1),
        (1, 2, 2, 1),
        ((0, 0), (1, 1), (1, 1), (0, 0)),
    )
    for stage, n_blocks in enumerate(cfg.stage_sizes):
        for block in range(n_blocks):
            key = f"stage{stage}/block{block}"
            stride = 2 if (stage > 0 and block == 0) else 1
            y, new_state[key] = _bottleneck_apply(
                cfg, params[key], model_state[key], y, stride=stride,
                train=train, mesh=mesh,
            )
    y = jnp.mean(y.astype(jnp.float32), axis=(1, 2))  # global average pool
    return layers.dense(params["head"], y, dtype=cfg.dtype), new_state


def loss_fn(cfg: Config, *, l2: float = 1e-4, mesh=None):
    """Softmax CE + L2 weight decay on conv/dense kernels (the tutorial-
    standard ResNet objective).  ``mesh``: for ghost-batch BN (see apply)."""

    def f(params, model_state, batch, rng):
        logits, new_state = apply(
            cfg, params, model_state, batch["image"], train=True, mesh=mesh
        )
        ce = layers.softmax_cross_entropy(logits, batch["label"])
        reg = 0.0
        if l2:
            sq = [
                jnp.sum(jnp.square(p["kernel"].astype(jnp.float32)))
                for p in jax.tree.leaves(
                    params, is_leaf=lambda n: isinstance(n, dict) and "kernel" in n
                )
                if isinstance(p, dict) and "kernel" in p
            ]
            reg = l2 * sum(sq)
        loss = ce + reg
        acc = layers.accuracy(logits, batch["label"])
        return loss, (new_state, {"loss": loss, "ce": ce, "accuracy": acc})

    return f


#: Data-parallel: all variables mirrored (MirroredStrategy analog).  On large
#: meshes the optimizer state could be sharded ZeRO-style over 'data'; kept
#: mirrored for reference parity.
SHARDING_RULES: tuple = ()


def sharding_rules(cfg: Config) -> tuple:
    """Ghost-batch BN keeps its per-slice running stats [S, C] SHARDED over
    the 'slice' axis — replicated stats would force a per-layer cross-slice
    all-gather in the EMA update, putting BN right back on DCN."""
    if cfg.bn_ghost_slices > 0:
        from jax.sharding import PartitionSpec as P

        return ((r".*/bn[^/]*/(mean|var)$", P("slice", None)),)
    return SHARDING_RULES
