"""Decoder-only transformer LM: the framework's growth-path flagship.

No reference analog (the reference's five workloads predate attention —
SURVEY.md section 5.7); this model exists to exercise the parallelism axes
the blueprint requires beyond reference parity:

- ``data``  — batch sharding (as every workload),
- ``model`` — tensor parallelism: attention heads and MLP hidden dim sharded
              (Megatron-style column->row pairs, gathers/reduces emitted by
              XLA from the sharding constraints),
- ``seq``   — sequence/context parallelism: activations sharded over the
              sequence dim; attention runs as a ``ppermute`` ring
              (ops/attention.py) so no device holds the full sequence.

Pre-norm blocks, learned positional embedding, GELU MLP, weight-tied softmax
optional.  Params stay f32; compute in bf16 on the MXU.

Checkpoint-format note: the qkv kernel's output columns are interpreted
head-major — (H, 3, head_dim) — so a TP shard owns whole heads (round-2
change; round-1 checkpoints used (3, H, head_dim) and are incompatible:
they restore without error but produce garbage attention).  The same
caveat applies across ``n_heads`` changes at fixed dim (e.g. the r3
flagship default moved 16 -> 8 heads): shapes match, column meaning does
not — a checkpoint is only valid for the Config it was trained with.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..ops import attention as attn_ops
from . import decoding, layers


@dataclasses.dataclass(frozen=True)
class Config:
    vocab_size: int = 32000
    dim: int = 512
    n_layers: int = 6
    n_heads: int = 8
    mlp_ratio: int = 4
    max_seq_len: int = 2048
    causal: bool = True
    #: "auto" (flash on TPU) | "xla" | "flash"; with a seq-sharded mesh
    #: these select the ring impl, and "ulysses" selects all-to-all CP
    #: (ops/attention.ulysses_attention) instead of the ring.
    attention: str = "auto"
    compute_dtype: str = "bfloat16"
    #: >1 enables pipeline parallelism: blocks are STACKED (params carry a
    #: leading layer dim sharded P('pipe')) and run under the GPipe schedule
    #: of parallel.pipeline.  Requires n_layers % pipeline_stages == 0 and a
    #: mesh whose 'pipe' axis == pipeline_stages.  Attention inside the
    #: pipeline uses XLA mha (a Pallas call cannot sit on an auto axis of a
    #: partial-manual shard_map); seq-axis ring attention likewise stays on
    #: the non-pipelined path.
    pipeline_stages: int = 1
    #: GPipe microbatches per step (bubble = (S-1)/(M+S-1)).
    microbatches: int = 4
    #: >0 replaces every block's dense MLP with a mixture-of-experts FFN
    #: (ops/moe.py): experts shard over the mesh 'expert' axis (GShard
    #: dispatch -> all_to_all), top-k routing, Switch load-balance aux loss
    #: added by loss_fn.  Not composable with pipeline_stages>1 (v1).
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 1e-2
    #: GShard routing-group size G (ops/moe.py): dispatch/combine einsum
    #: cost per token scales ~linearly with G (contract dim g x output
    #: [E, C_g], C_g ~ k*G/E), so G is THE dispatch-share knob — smaller G
    #: cuts dispatch FLOPs but shrinks the expert matmul tiles and changes
    #: routing semantics (capacity is per-group).  1024 = GShard's default
    #: regime; sweep it if the profiled dispatch share exceeds the ~15%%
    #: budget (VERDICT r3/r4).
    moe_group_size: int = 1024
    #: Rematerialise each block in the backward pass (jax.checkpoint): trades
    #: ~1/3 more FLOPs for activation memory ~O(n_layers) smaller — the knob
    #: that fits bigger batches / longer context in HBM.  (Pipeline mode
    #: always remats its stages — parallel/pipeline.py.)
    remat: bool = False
    #: >1 chunks the LM head + cross-entropy over the sequence dim inside
    #: ``loss_fn`` (lax.scan of jax.checkpoint'd chunks): the [B, T, V]
    #: logits tensor — the single largest activation (batch 8 x 2048 x 32k
    #: = 2 GB f32, with backward copies on top) — is never materialised;
    #: each chunk's logits are recomputed in the backward pass.  Identical
    #: math (same bf16 matmul -> f32 logsumexp, different summation
    #: grouping); requires T % loss_chunks == 0, falls back to the dense
    #: path under seq sharding (chunking T would fight the 'seq' axis).
    loss_chunks: int = 0

    @property
    def dtype(self):
        return jnp.dtype(self.compute_dtype)

    @property
    def head_dim(self):
        return self.dim // self.n_heads

    @property
    def data_axes(self):
        """Mesh axes the batch dim shards over.  MoE mode shards the batch
        over ``('data','expert')`` JOINTLY — experts live on the 'expert'
        axis, so tokens must physically leave their home rank to reach
        their expert: that redistribution is the GShard ``all_to_all``.
        (With the batch on 'data' alone, activations replicate over the
        expert axis and GSPMD serves dispatch with all-gathers instead —
        the round-2 HLO tables' finding.)"""
        return ("data", "expert") if self.moe_experts > 0 else ("data",)


def _layernorm_init(d):
    return {"scale": jnp.ones((d,), jnp.float32), "bias": jnp.zeros((d,), jnp.float32)}


def _layernorm(p, x, eps=1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"] + p["bias"]).astype(x.dtype)


def _use_flash(cfg: Config, seq_len: int) -> bool:
    if cfg.attention == "flash":
        return True
    if cfg.attention in ("auto", "ulysses"):
        # Ulysses without a seq-sharded mesh degenerates to local
        # attention — same flash-if-viable policy as auto.
        from ..ops.flash_attention import flash_viable

        return flash_viable(seq_len)
    return False


def _flash_sharded(mesh: Mesh, q, k, v, *, causal: bool, batch_axes=("data",)):
    """Flash attention under a mesh: a Mosaic custom call cannot be
    partitioned by XLA SPMD, so shard_map it — batch over ``batch_axes``
    (('data','expert') in MoE mode, matching Config.data_axes so the
    constraint established upstream isn't resharded away), heads over
    ``model``, sequence local (the seq>1 case routes to the ring
    instead)."""
    h_entry = "model" if mesh.shape.get("model", 1) > 1 else None
    spec = P(batch_axes, h_entry, None, None)

    from ..ops.flash_attention import flash_attention
    from ..parallel import collectives

    fn = lambda q, k, v: flash_attention(q, k, v, causal=causal)
    return collectives.shard_map(
        fn, mesh, in_specs=(spec, spec, spec), out_specs=spec
    )(q, k, v)


def _moe_cfg(cfg: Config):
    from ..ops import moe as moe_ops

    return moe_ops.MoEConfig(
        n_experts=cfg.moe_experts,
        top_k=cfg.moe_top_k,
        capacity_factor=cfg.moe_capacity_factor,
        group_size=cfg.moe_group_size,
    )


def init(cfg: Config, rng: jax.Array):
    n = cfg.n_layers
    if cfg.pipeline_stages > 1 and n % cfg.pipeline_stages:
        raise ValueError(
            f"n_layers={n} not divisible by pipeline_stages={cfg.pipeline_stages}"
        )
    if cfg.moe_experts > 0 and cfg.pipeline_stages > 1:
        raise ValueError("moe_experts and pipeline_stages>1 do not compose (v1)")
    rngs = jax.random.split(rng, 4 * n + 3)
    params: dict = {
        "emb": layers.embedding_init(rngs[0], cfg.vocab_size, cfg.dim),
        "pos": {"table": 0.02 * jax.random.normal(rngs[1], (cfg.max_seq_len, cfg.dim))},
        "ln_f": _layernorm_init(cfg.dim),
        "head": layers.dense_init(rngs[2], cfg.dim, cfg.vocab_size, use_bias=False),
    }
    h = cfg.dim * cfg.mlp_ratio
    blocks = []
    for i in range(n):
        r = rngs[3 + 4 * i : 3 + 4 * (i + 1)]
        b = {
            "ln1": _layernorm_init(cfg.dim),
            "qkv": layers.dense_init(r[0], cfg.dim, 3 * cfg.dim, use_bias=False),
            "proj": layers.dense_init(r[1], cfg.dim, cfg.dim, use_bias=False),
            "ln2": _layernorm_init(cfg.dim),
        }
        if cfg.moe_experts > 0:
            from ..ops import moe as moe_ops

            b["moe"] = moe_ops.init(r[2], cfg.dim, h, _moe_cfg(cfg))
        else:
            b["mlp_in"] = layers.dense_init(r[2], cfg.dim, h)
            b["mlp_out"] = layers.dense_init(r[3], h, cfg.dim)
        blocks.append(b)
    if cfg.pipeline_stages > 1:
        # Pipeline mode: one stacked pytree (leading layer dim, sharded
        # P('pipe') per sharding_rules) instead of per-layer keys.
        from ..parallel import pipeline as pipeline_lib

        params["blocks"] = pipeline_lib.stack_stages(blocks)
    else:
        for i, b in enumerate(blocks):
            params[f"block_{i}"] = b
    return params


def _attention(cfg: Config, mesh, q, k, v, *, allow_custom: bool):
    """Attention dispatch: seq-ring / flash / XLA mha (see apply)."""
    T = q.shape[2]
    if allow_custom and mesh is not None and mesh.shape.get("seq", 1) > 1:
        # Sequence sharded: ring attention over the seq axis; per-hop block
        # compute is the Pallas flash kernel when requested (or on TPU by
        # default) — ring SP and the flash kernel COMPOSE (ops/attention.py
        # ring_flash_attention).
        # cfg.attention values map 1:1 onto ring impls — an explicit "xla"
        # must NOT silently upgrade to the flash ring.
        return attn_ops.sequence_parallel_attention(
            mesh, q, k, v, causal=cfg.causal, impl=cfg.attention,
            batch_axis=cfg.data_axes,
        )
    if allow_custom and _use_flash(cfg, T):
        if mesh is not None:
            return _flash_sharded(
                mesh, q, k, v, causal=cfg.causal, batch_axes=cfg.data_axes
            )
        from ..ops.flash_attention import flash_attention

        return flash_attention(q, k, v, causal=cfg.causal)
    return attn_ops.mha(q, k, v, causal=cfg.causal)


def _block(cfg: Config, p, h, *, mesh, constrain, allow_custom_attn=True):
    """One pre-norm decoder block: attention + (dense | MoE) FFN.

    Returns ``(h, aux)``; ``aux`` is the MoE load-balance loss contribution
    (0.0 for the dense MLP).
    """
    B, T = h.shape[0], h.shape[1]
    y = _layernorm(p["ln1"], h)
    qkv = layers.dense(p["qkv"], y, dtype=cfg.dtype)  # [B,T,3D]
    # Interpret the 3D output columns as (H, 3, hd) — head-major — so a
    # 'model'-axis shard of the column-parallel qkv kernel owns WHOLE
    # heads (its q, k and v slices for those heads).  The (3, H, hd)
    # layout would give a TP shard all of q plus part of k, forcing GSPMD
    # to reshard every layer to satisfy P('data','model','seq',None).
    qkv = qkv.reshape(B, T, cfg.n_heads, 3, cfg.head_dim)
    q, k, v = [
        jnp.moveaxis(qkv[:, :, :, j], 2, 1) for j in range(3)
    ]  # [B,H,T,hd], heads shardable over 'model'
    da = cfg.data_axes
    q = constrain(q, P(da, "model", "seq", None))
    k = constrain(k, P(da, "model", "seq", None))
    v = constrain(v, P(da, "model", "seq", None))
    o = _attention(cfg, mesh, q, k, v, allow_custom=allow_custom_attn)
    o = jnp.moveaxis(o, 1, 2).reshape(B, T, cfg.dim)
    h = h + layers.dense(p["proj"], o, dtype=cfg.dtype)
    h = constrain(h, P(da, "seq", None))

    aux = jnp.float32(0.0)
    if "moe" in p:
        h, aux = _moe_tail(cfg, p, h, constrain, mesh)
    else:
        h = _mlp_tail(cfg, p, h, constrain)
    return h, aux


def _moe_tail(cfg: Config, p, h, constrain, mesh):
    """ln2 -> GShard MoE FFN -> residual.  Shared by the training block and
    the KV-cache decode block so the two paths cannot drift (decode's
    ``constrain`` maps the 'seq' entry to None and discards the aux
    loss)."""
    from ..ops import moe as moe_ops

    y = _layernorm(p["ln2"], h)
    y, aux = moe_ops.apply(p["moe"], y, _moe_cfg(cfg), dtype=cfg.dtype, mesh=mesh)
    return constrain(h + y, P(cfg.data_axes, "seq", None)), aux


def _mlp_tail(cfg: Config, p, h, constrain):
    """ln2 -> column-parallel dense -> GELU -> row-parallel dense, residual.
    Shared by the training block and the KV-cache decode block so the two
    paths cannot drift."""
    y = _layernorm(p["ln2"], h)
    y = layers.dense(p["mlp_in"], y, dtype=cfg.dtype)
    y = constrain(y, P(cfg.data_axes, "seq", "model"))
    y = jax.nn.gelu(y)
    h = h + layers.dense(p["mlp_out"], y, dtype=cfg.dtype)
    return constrain(h, P(cfg.data_axes, "seq", None))


def apply(cfg: Config, params, x, *, mesh: Mesh | None = None, return_aux=False):
    """x: [B, T] int32 -> logits [B, T, V] (or (logits, moe_aux) with
    ``return_aux``).

    With ``mesh``: activations carry sharding constraints
    ([B,T,D] -> P('data','seq',None)) so XLA partitions every dense op, and
    attention routes through the seq-axis ring when the mesh shards ``seq``.
    With ``cfg.pipeline_stages > 1``: the block stack runs under the GPipe
    schedule of ``parallel.pipeline`` over the mesh 'pipe' axis.
    """
    h, aux_total = _trunk(cfg, params, x, mesh=mesh)
    logits = layers.dense(params["head"], h, dtype=cfg.dtype)
    if return_aux:
        return logits, aux_total
    return logits


def _trunk(cfg: Config, params, x, *, mesh: Mesh | None):
    """Everything up to and including ln_f: x [B, T] -> (h [B, T, D], aux).
    Split from ``apply`` so ``loss_fn``'s chunked head+CE path (see
    ``Config.loss_chunks``) can consume hidden states without the [B, T, V]
    logits ever existing."""
    B, T = x.shape

    def constrain(y, spec):
        if mesh is None:
            return y
        return jax.lax.with_sharding_constraint(
            y, jax.sharding.NamedSharding(mesh, spec)
        )

    h = layers.embedding_lookup(params["emb"], x, dtype=cfg.dtype)
    h = h + params["pos"]["table"][:T].astype(cfg.dtype)[None]
    h = constrain(h, P(cfg.data_axes, "seq", None))

    if cfg.pipeline_stages > 1:
        from ..parallel import pipeline as pipeline_lib

        if mesh is not None and mesh.shape.get("pipe", 1) != cfg.pipeline_stages:
            raise ValueError(
                f"pipeline_stages={cfg.pipeline_stages} needs a mesh whose "
                f"'pipe' axis is exactly that size; got "
                f"{dict(mesh.shape)} (pass e.g. --mesh "
                f'"data=...,pipe={cfg.pipeline_stages}")'
            )

        def constrain_in_manual(y, spec):
            # Inside the partial-manual shard_map the context mesh marks
            # 'pipe' Manual; a NamedSharding built from the concrete mesh
            # (all-Auto) is rejected there.  The bare-PartitionSpec form
            # resolves against the context mesh and constrains only the
            # auto axes — exactly what the TP/DP specs name.
            if mesh is None:
                return y
            return jax.lax.with_sharding_constraint(y, spec)

        def stage_fn(rank_blocks, x):
            # rank_blocks: this rank's layer slice (leading dim L/S); inside
            # the partial-manual shard_map a Pallas call can't sit on an
            # auto axis, so blocks use XLA attention here.  (MoE is barred
            # from pipeline mode at init, so aux is always 0 here.)
            def body(x, p):
                x, _ = _block(
                    cfg, p, x, mesh=mesh, constrain=constrain_in_manual,
                    allow_custom_attn=False,
                )
                return x, None

            x, _ = jax.lax.scan(body, x, rank_blocks)
            return x

        if mesh is None:
            h = stage_fn(params["blocks"], h)
        else:
            h = pipeline_lib.pipeline_apply(
                mesh, stage_fn, params["blocks"], h,
                microbatches=cfg.microbatches,
            )
        aux_total = jnp.float32(0.0)
    else:
        aux_total = jnp.float32(0.0)

        def block_fn(p, h):
            return _block(cfg, p, h, mesh=mesh, constrain=constrain)

        if cfg.remat:
            block_fn = jax.checkpoint(block_fn)
        for i in range(cfg.n_layers):
            h, aux = block_fn(params[f"block_{i}"], h)
            aux_total = aux_total + aux

    h = _layernorm(params["ln_f"], h)
    return h, aux_total


# ----------------------------------------------------------------------------
# Autoregressive decoding (KV cache) — the inference path
# ----------------------------------------------------------------------------


def collapse_pipeline(cfg: Config, params):
    """Pipeline-trained checkpoint -> the flat serving layout: the stacked
    ``blocks`` pytree (leading layer dim, GPipe training layout) becomes
    per-layer ``block_i`` keys and ``pipeline_stages`` drops to 1, so the
    result decodes through the ordinary KV-cache path (decode_step /
    generate).  Rationale: a pipelined DECODE would bubble O(stages) per
    token — at T=1 there are no microbatches to fill the pipe — so serving
    collapses the stages instead (weights are identical; parity tested).

    Works on host or device pytrees; re-shard the result for the serving
    mesh (e.g. ``shard_pytree`` with the dense rules) as needed."""
    if cfg.pipeline_stages <= 1:
        return cfg, params
    from ..parallel import pipeline as pipeline_lib

    flat = {k: v for k, v in params.items() if k != "blocks"}
    for i, b in enumerate(
        pipeline_lib.unstack_stages(params["blocks"], cfg.n_layers)
    ):
        flat[f"block_{i}"] = b
    return dataclasses.replace(cfg, pipeline_stages=1, microbatches=1), flat


def init_cache(cfg: Config, batch: int, max_len: int, *, mesh: Mesh | None = None):
    """Per-layer K/V cache [B, H, max_len, hd] (bf16 like the compute).

    With ``mesh``: born sharded P('data', 'model', None, None) — heads on
    the TP axis, so a model that needs TP to fit in HBM decodes with each
    rank holding only its heads' cache (r2 verdict missing #6)."""
    shape = (batch, cfg.n_heads, max_len, cfg.head_dim)
    if mesh is None:
        one = lambda: jnp.zeros(shape, cfg.dtype)
    else:
        # Born sharded: zeros created UNDER jit with out_shardings, so the
        # full replicated cache never materialises on one device (a model
        # whose cache only fits sharded must not OOM in its own init).
        sh = jax.sharding.NamedSharding(mesh, P(cfg.data_axes, "model", None, None))
        one = jax.jit(
            lambda: jnp.zeros(shape, cfg.dtype), out_shardings=sh
        )
    return {
        f"block_{i}": {"k": one(), "v": one()} for i in range(cfg.n_layers)
    }


def _decode_constrain(mesh: Mesh | None, drop: tuple = ("seq",)):
    """Constraint fn for the decode path: same specs as training, except
    any entry in ``drop`` becomes None — 'seq' always (the decode T dim is
    1, a prefill chunk's is one slot's, and neither may be forced onto the
    sequence axis); a prefill chunk drops the data axes too (its batch dim
    is the ONE slot it fills)."""
    if mesh is None:
        return lambda y, spec: y

    def constrain(y, spec):
        spec = P(*(None if e in drop else e for e in spec))
        return jax.lax.with_sharding_constraint(
            y, jax.sharding.NamedSharding(mesh, spec)
        )

    return constrain


#: Cache positions one trip of the decode step's attention loop reads (or
#: the whole cache, where that is shorter).  The step reads whole blocks up
#: to its deepest row, so the size trades positions read past that row
#: (half a block on average, of every slot) against trips of a loop whose
#: body is a dozen small operations a layer.  Chosen on one v5e chip with
#: Cerebras-GPT-1.3B, 8 slots x 2048 (my chip run, PR 28; PERF.md section
#: 6): with the logits fetched every step, the bare step with its deepest
#: row at 200 / 367 / 900 / 2040 takes 11.7 / 12.2 / 14.3 / 17.7 ms at
#: 128, 11.7 / 12.3 / 13.6 / 16.4 at 256 and 12.5 / 12.4 / 13.2 / 15.5 at
#: 512 (23.4 for the step that read it all): 256 is best where chat
#: sessions stand, 512 gains only on a cache that is nearly full.
DECODE_BLOCK = 256


def decode_rows_read(pos, live, max_len: int):
    """Cache positions of EVERY slot that one decode step reads with its
    rows at the host's ``pos [S]``: whole blocks of :data:`DECODE_BLOCK` up
    to the one that holds the deepest position, at most the cache, whichever
    rows are ``live`` (a row that is not stands at 0 or where its session
    does).  The host's count of what :func:`_decode_attention`'s loop does
    on the device."""
    del live
    blk = min(DECODE_BLOCK, max_len)
    return min(max_len, (int(pos.max()) // blk + 1) * blk)


def _write_rows(cache, new, pos):
    """cache [B, H, T, hd] with ``new[b]`` ([B, H, 1, hd]) written at
    position ``pos[b]`` of row ``b`` and nothing else changed: one
    ``dynamic_update_slice`` a row (``B`` is static), each in place in a
    donated cache.  On one v5e chip with Cerebras-GPT-1.3B, 8 slots x 2048,
    the 384 of a step cost 1.0 ms less than the same rows written as one
    batched scatter a layer, which the compiler turns into a loop of 8 (my
    chip run, PR 28: the bare step 9.2 against 10.2 ms)."""
    for b in range(cache.shape[0]):
        cache = jax.lax.dynamic_update_slice(
            cache, new[b:b + 1], (b, 0, pos[b], 0)
        )
    return cache


def _decode_attention(cfg: Config, q, ck, cv, pos):
    """One query per row against that row's cache: q [B, H, 1, hd], ck / cv
    [B, H, T, hd], ``pos`` [B] -> [B, H, 1, hd]; row ``b`` attends over its
    positions ``<= pos[b]``.

    The cache is read a block of positions at a time as a running softmax
    (maximum, sum and weighted values carried in float32), in a loop whose
    trip count ``max(pos) // block + 1`` is computed in the program: one
    compiled step reads no further than its deepest row, whatever the
    cache's length.  A block that lies wholly past a row's position is an
    exact no-op for that row (weights 0, maximum unchanged, rescale by
    ``exp(0) = 1``), so a row's result is the same to the bit whatever the
    other rows' positions.  Block ``i`` holds positions ``[i * block,
    (i + 1) * block)``; where the cache's length is no multiple of the
    block the last one is read shifted back inside the cache and what it
    shares with the block before is masked."""
    B, H, T, hd = ck.shape
    blk = min(DECODE_BLOCK, T)

    def body(i, carry):
        m, l, acc = carry
        start = jnp.minimum(i * blk, T - blk)
        kb = jax.lax.dynamic_slice_in_dim(ck, start, blk, axis=2)
        vb = jax.lax.dynamic_slice_in_dim(cv, start, blk, axis=2)
        s = jnp.einsum(
            "bhqd,bhtd->bhqt", q, kb, preferred_element_type=jnp.float32
        ) / math.sqrt(hd)
        t = start + jnp.arange(blk)
        own = (t >= i * blk)[None, :] & (t[None, :] <= pos[:, None])
        s = jnp.where(own[:, None, None, :], s, -jnp.inf)
        # Block 0 holds position 0, which every row owns: the maximum is
        # finite from the first trip on.
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        w = jnp.exp(s - m_new)
        r = jnp.exp(m - m_new)
        l = l * r + w.sum(axis=-1, keepdims=True)
        acc = acc * r + jnp.einsum(
            "bhqt,bhtd->bhqd", w.astype(cfg.dtype), vb,
            preferred_element_type=jnp.float32,
        )
        return m_new, l, acc

    stat = jnp.zeros((B, H, 1, 1), jnp.float32)
    _, l, acc = jax.lax.fori_loop(
        0, jnp.max(pos) // blk + 1, body,
        (stat - jnp.inf, stat, jnp.zeros((B, H, 1, hd), jnp.float32)),
    )
    return (acc / l).astype(cfg.dtype)


def decode_step(cfg: Config, params, cache, token, pos, *, mesh: Mesh | None = None):
    """token [B] int32 at position ``pos`` -> (logits [B, V], new cache):
    :func:`decode_step_batch` with every row at the ONE position ``pos``
    (what :func:`generate`'s scan runs).

    With ``mesh``: runs TP-sharded — KV cache and attention heads on the
    'model' axis, Megatron dense sharding via the weight shardings +
    constraints (per-position parity with the replicated path is tested).
    MoE models decode through the same GShard dispatch as training on a
    data x expert mesh (batch over ``cfg.data_axes``, expert FFN weights
    staying on their ranks); only pipelined models remain out of scope
    (a pipelined decode would bubble O(stages) per token — serve those
    with the stages collapsed).
    """
    return decode_step_batch(
        cfg, params, cache, token,
        jnp.full(token.shape, pos, jnp.int32), mesh=mesh,
    )


def _block_decode_batch(cfg: Config, p, h, layer_cache, pos, *, constrain, mesh=None):
    """One block for ONE new token PER ROW at per-row positions: h
    [B, 1, D], ``pos`` [B] int32 — the sequence-slot serving shape
    (models/transformer.py's half of serve/batcher.SlotBatcher): each row
    is an independent decode session at its own depth.

    What a step touches of the cache.  It WRITES row ``b``'s new key and
    value into ``cache[b, :, pos[b], :]`` and into no other element
    (:func:`_write_rows`; in place when the caller donates the cache, as
    the serve engine does - a cache that is not donated is copied whole by
    the runtime first).  It READS whole blocks of positions up to its
    deepest row and no further (:func:`_decode_attention`,
    :func:`decode_rows_read`), and the causal mask bounds each row at ITS
    ``pos`` — so a session's row depends only on cache positions that
    session wrote itself, to the bit whatever the other rows hold or where
    they stand, which is what lets a freed slot be reseated with no cache
    reset and keeps batched decode byte-identical to a session running
    alone (tested).

    Static shapes throughout, so the jitted step never recompiles as
    decoding advances.  ``constrain`` pins activations/cache to the decode
    shardings (heads on 'model', batch on the data axes — ('data','expert')
    for MoE; the T=1 dim never touches 'seq') — identity without a mesh.

    MoE blocks route their single position through the SAME GShard
    dispatch/combine einsums as training (ops/moe.py; aux loss unused at
    inference).  Decode capacity is per-step — with only B tokens in
    flight nothing realistically drops, whereas a training forward at full
    T may drop overflow tokens; per-position parity therefore holds
    whenever training capacity is not exceeded (tested)."""
    B = h.shape[0]
    da = cfg.data_axes
    y = _layernorm(p["ln1"], h)
    qkv = layers.dense(p["qkv"], y, dtype=cfg.dtype)
    qkv = qkv.reshape(B, 1, cfg.n_heads, 3, cfg.head_dim)
    q, k, v = [jnp.moveaxis(qkv[:, :, :, j], 2, 1) for j in range(3)]  # [B,H,1,hd]
    q = constrain(q, P(da, "model", None, None))
    ck = constrain(_write_rows(layer_cache["k"], k, pos), P(da, "model", None, None))
    cv = constrain(_write_rows(layer_cache["v"], v, pos), P(da, "model", None, None))
    o = _decode_attention(cfg, q, ck, cv, pos)
    o = jnp.moveaxis(o, 1, 2).reshape(B, 1, cfg.dim)
    h = h + layers.dense(p["proj"], o, dtype=cfg.dtype)
    h = constrain(h, P(da, None, None))
    if "moe" in p:
        h, _ = _moe_tail(cfg, p, h, constrain, mesh)
    else:
        h = _mlp_tail(cfg, p, h, constrain)
    return h, {"k": ck, "v": cv}


def decode_step_batch(
    cfg: Config, params, cache, token, pos, *, mesh: Mesh | None = None,
):
    """token [B] int32, pos [B] int32 (PER-ROW positions) -> (logits
    [B, V], new cache) — the sequence-slot batched decode step: row b
    advances its own session at position ``pos[b]``.  The serving engine
    jits this once at the fixed slot shape, donates it the cache, and
    every active session rides one apply; what a step reads and writes of
    the cache is in :func:`_block_decode_batch`."""
    if cfg.pipeline_stages > 1:
        raise NotImplementedError(
            "decode supports the non-pipelined model (dense or MoE)"
        )
    constrain = _decode_constrain(mesh)
    da = cfg.data_axes
    h = layers.embedding_lookup(params["emb"], token[:, None], dtype=cfg.dtype)
    h = h + params["pos"]["table"][pos].astype(cfg.dtype)[:, None]
    h = constrain(h, P(da, None, None))
    new_cache = {}
    for i in range(cfg.n_layers):
        h, new_cache[f"block_{i}"] = _block_decode_batch(
            cfg, params[f"block_{i}"], h, cache[f"block_{i}"], pos,
            constrain=constrain, mesh=mesh,
        )
    h = _layernorm(params["ln_f"], h)
    return layers.dense(params["head"], h, dtype=cfg.dtype)[:, 0], new_cache


def _block_prefill(
    cfg: Config, p, h, layer_cache, slot, offset, n_valid, *, constrain,
):
    """One dense block for ``C`` consecutive tokens of ONE slot: h
    [1, C, D] holds the slot's positions ``offset .. offset + C - 1``, of
    which the first ``n_valid`` are real.  Their K/V land in the slot's
    cache rows ``[offset, offset + n_valid)`` and in no other row; the
    chunk's queries attend over the slot's rows ``<=`` their own position
    (what earlier chunks wrote plus the chunk itself, causally) — the same
    masked einsum attention as :func:`_block_decode_batch`, ``C`` rows of
    it at once.

    The write is a ``C``-row window ``dynamic_update_slice``d into the
    cache (in place when the caller donates it).  The window starts at
    ``min(offset, T - C)``: a chunk whose padded tail would run past the
    cache's end is shifted back INSIDE the window instead (``roll`` +
    the validity mask keep every row the chunk does not own as it was),
    because ``dynamic_update_slice`` clamps a start that overruns and
    would silently overwrite earlier rows."""
    C = h.shape[1]
    H, T, hd = layer_cache["k"].shape[1:]
    y = _layernorm(p["ln1"], h)
    qkv = layers.dense(p["qkv"], y, dtype=cfg.dtype)
    qkv = qkv.reshape(1, C, cfg.n_heads, 3, cfg.head_dim)
    q, k, v = [jnp.moveaxis(qkv[:, :, :, j], 2, 1) for j in range(3)]  # [1,H,C,hd]
    q = constrain(q, P(None, "model", None, None))
    start = jnp.clip(offset, 0, T - C)
    shift = offset - start  # > 0 only for a chunk that would pass the end
    i = jnp.arange(C) - shift  # chunk index of each window row
    own = ((i >= 0) & (i < n_valid))[None, None, :, None]
    at = (slot, 0, start, 0)

    def write(cache, new):
        old = jax.lax.dynamic_slice(cache, at, (1, H, C, hd))
        win = jnp.where(own, jnp.roll(new, shift, axis=2), old)
        cache = jax.lax.dynamic_update_slice(cache, win, at)
        cache = constrain(cache, P(cfg.data_axes, "model", None, None))
        rows = jax.lax.dynamic_slice_in_dim(cache, slot, 1, axis=0)
        return cache, constrain(rows, P(None, "model", None, None))

    ck, sk = write(layer_cache["k"], k)
    cv, sv = write(layer_cache["v"], v)
    s = jnp.einsum(
        "bhqd,bhtd->bhqt", q, sk, preferred_element_type=jnp.float32
    ) / math.sqrt(cfg.head_dim)
    q_pos = offset + jnp.arange(C)
    s = jnp.where(
        jnp.arange(T)[None, None, None, :] <= q_pos[None, None, :, None],
        s, -jnp.inf,
    )
    w = jax.nn.softmax(s, axis=-1).astype(cfg.dtype)
    o = jnp.einsum("bhqt,bhtd->bhqd", w, sv)
    o = jnp.moveaxis(o, 1, 2).reshape(1, C, cfg.dim)
    h = h + layers.dense(p["proj"], o, dtype=cfg.dtype)
    h = constrain(h, P(None, None, None))
    return _mlp_tail(cfg, p, h, constrain), {"k": ck, "v": cv}


def prefill_chunk(
    cfg: Config, params, cache, tokens, slot, offset, n_valid, *,
    mesh: Mesh | None = None,
):
    """tokens [C] int32 — ONE slot's prompt tokens at positions ``offset ..
    offset + C - 1``, the first ``n_valid`` real, the rest padding — ->
    new cache: one forward pass writes the K/V of the valid tokens into
    ``cache[...][slot, :, offset:offset + n_valid]`` and touches no other
    row of any slot.  What :func:`decode_step_batch` does for a prompt in
    ``n_valid`` launches, row for row the same mathematics in the same
    precision (summation order apart); no final norm, LM head or logits —
    the caller decodes the prompt's LAST token the ordinary way and takes
    the first new token from that step.  ``C`` is the static length of
    ``tokens`` (at most the cache's ``max_len``); ``slot``, ``offset`` and
    ``n_valid`` are traced scalars, so one program serves every chunk of
    every prompt.  Dense blocks only: GShard capacity is per call, and a
    ``C``-token call may drop tokens a one-token call keeps."""
    if cfg.pipeline_stages > 1 or cfg.moe_experts > 0:
        raise NotImplementedError(
            "prefill_chunk supports the non-pipelined dense model"
        )
    C = tokens.shape[0]
    constrain = _decode_constrain(mesh, drop=("seq", cfg.data_axes))
    h = layers.embedding_lookup(params["emb"], tokens[None], dtype=cfg.dtype)
    # Padding past the table's end reads a clamped row; nothing keeps it.
    h = h + params["pos"]["table"][offset + jnp.arange(C)].astype(cfg.dtype)[None]
    h = constrain(h, P(None, None, None))
    new_cache = {}
    for i in range(cfg.n_layers):
        # The last block's projection and MLP feed nothing that is
        # returned; the compiler drops them.
        h, new_cache[f"block_{i}"] = _block_prefill(
            cfg, params[f"block_{i}"], h, cache[f"block_{i}"], slot, offset,
            n_valid, constrain=constrain,
        )
    return new_cache


def serve_decode_fns(cfg: Config, *, mesh: Mesh | None = None):
    """What a serving replica's decode engine is told of this model
    (``serve.ModelReplicaServer(decode_fns=...)``, ``decoding.DecodeFns``):
    slot-shaped KV cache, the per-row-position batched step, which is not
    told which rows are live (a row that is not computes an inert row), how
    far that step reads (:func:`decode_rows_read`) and, for dense blocks,
    :func:`prefill_chunk`, with which the engine puts a seated prompt into
    the cache a chunk per forward pass.  An MoE model hands no ``prefill``:
    the engine then feeds the prompt a token a step (see
    :func:`prefill_chunk`)."""
    init, step, chunk = (
        functools.partial(f, mesh=mesh)
        for f in (init_cache, decode_step_batch, prefill_chunk))
    return decoding.serve_fns(
        cfg, init, step, None if cfg.moe_experts > 0 else chunk,
        wants_live=False, step_rows_read=decode_rows_read)


def generate(
    cfg: Config,
    params,
    prompt,
    *,
    max_new_tokens: int,
    temperature: float = 0.0,
    rng: jax.Array | None = None,
    mesh: Mesh | None = None,
):
    """Autoregressive generation: prompt [B, Tp] -> [B, Tp + max_new_tokens].

    One jitted program with a static-shape KV cache: each row's prompt but
    its last token enters the cache through :func:`prefill_chunk` (one
    chunk a row, the path a serving replica takes), then a ``lax.scan``
    over the remaining positions decodes greedily (temperature 0) or by
    temperature sampling.  An MoE model has no prefill and teacher-forces
    its prompt through the scan instead (logits discarded).  The
    framework's inference surface; no reference analog (the reference
    trains only).
    """
    prompt = jnp.asarray(prompt, jnp.int32)  # numpy prompts: traced indexing
    B, Tp = prompt.shape
    total = Tp + max_new_tokens
    if total > cfg.max_seq_len:
        raise ValueError(f"{total} tokens > max_seq_len={cfg.max_seq_len}")
    rng = jax.random.key(0) if rng is None else rng

    cache = init_cache(cfg, B, total, mesh=mesh)
    run = _generate_loop(cfg, Tp, total, float(temperature), mesh)
    toks = run(params, cache, jnp.asarray(prompt), rng)
    # ``toks`` are the tokens after the scan's first position.
    return jnp.concatenate(
        [prompt[:, : total - toks.shape[0]], toks.T], axis=1
    )  # [B, total]


@functools.lru_cache(maxsize=32)
def _generate_loop(cfg: Config, Tp: int, total: int, temperature: float, mesh):
    """Compiled decode loop, cached by (cfg, prompt len, total, temperature,
    mesh): params/cache/prompt/rng are ARGUMENTS, so repeated generation
    (eval loops sampling every checkpoint) reuses one executable instead of
    retracing a fresh closure per call."""

    def step(params, carry, pos):
        cache, tok, rng, prompt = carry
        logits, cache = decode_step(cfg, params, cache, tok, pos, mesh=mesh)
        rng, sub = jax.random.split(rng)
        if temperature > 0:
            sampled = jax.random.categorical(
                sub, logits.astype(jnp.float32) / temperature
            )
        else:
            sampled = jnp.argmax(logits, axis=-1)
        # Teacher-force while still inside the prompt.
        nxt = jnp.where(pos + 1 < Tp, prompt[:, jnp.minimum(pos + 1, Tp - 1)], sampled)
        return (cache, nxt.astype(jnp.int32), rng, prompt), nxt.astype(jnp.int32)

    # Where the scan starts: at the prompt's last token once the prefill
    # has cached the tokens before it, else at position 0.
    start = Tp - 1 if cfg.moe_experts == 0 else 0

    def run(params, cache, prompt, rng):
        if start > 0:
            cache = jax.lax.fori_loop(
                0, prompt.shape[0],
                lambda b, c: prefill_chunk(
                    cfg, params, c, prompt[b, :start], b, 0, start, mesh=mesh
                ),
                cache,
            )
        (_, _, _, _), toks = jax.lax.scan(
            lambda c, p: step(params, c, p),
            (cache, prompt[:, start], rng, prompt),
            jnp.arange(start, total - 1),
        )
        return toks

    # One jitted program for the whole decode loop: with a mesh this is the
    # SPMD path (decode_step's constraints partition every step); eagerly
    # it would dispatch per-op.
    return jax.jit(run)


def _chunked_ce(cfg: Config, head_p, h, y):
    """Mean CE from hidden states WITHOUT materialising [B, T, V] logits:
    lax.scan over ``cfg.loss_chunks`` sequence chunks, each chunk's
    (bf16 head matmul -> f32 logsumexp - gold) under jax.checkpoint so the
    backward recomputes chunk logits instead of storing them.  Same math as
    dense softmax_cross_entropy (the global mean is just regrouped); peak
    logits memory drops by the chunk count."""
    B, T, D = h.shape
    c = cfg.loss_chunks
    hc = jnp.moveaxis(h.reshape(B, c, T // c, D), 1, 0)  # [c, B, Tc, D]
    yc = jnp.moveaxis(y.reshape(B, c, T // c), 1, 0)  # [c, B, Tc]

    def one(tot, hy):
        hcb, ycb = hy
        logits = layers.dense(head_p, hcb, dtype=cfg.dtype).astype(jnp.float32)
        lz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, ycb[..., None].astype(jnp.int32), axis=-1
        )[..., 0]
        return tot + jnp.sum(lz - gold), None

    tot, _ = jax.lax.scan(jax.checkpoint(one), jnp.float32(0.0), (hc, yc))
    return tot / (B * T)


def loss_fn(cfg: Config, *, mesh: Mesh | None = None):
    def f(params, model_state, batch, rng):
        T = batch["x"].shape[1]
        chunked = (
            cfg.loss_chunks > 1
            and T % cfg.loss_chunks == 0
            and (mesh is None or mesh.shape.get("seq", 1) == 1)
        )
        if chunked:
            h, aux = _trunk(cfg, params, batch["x"], mesh=mesh)
            ce = _chunked_ce(cfg, params["head"], h, batch["y"])
        else:
            logits, aux = apply(cfg, params, batch["x"], mesh=mesh, return_aux=True)
            ce = layers.softmax_cross_entropy(
                logits.reshape(-1, cfg.vocab_size), batch["y"].reshape(-1)
            )
        metrics = {"loss": ce, "perplexity": jnp.exp(ce)}
        loss = ce
        if cfg.moe_experts > 0:
            loss = ce + cfg.moe_aux_weight * aux
            metrics["moe_aux"] = aux
        return loss, (model_state, metrics)

    return f


def batch_spec(cfg: Config | None = None) -> P:
    """[B, T] batches shard batch over 'data' AND sequence over 'seq' —
    plus 'expert' on the batch dim in MoE mode (see Config.data_axes)."""
    return P(cfg.data_axes if cfg is not None else "data", "seq")


#: Megatron-style TP rules for ONE block: qkv/mlp_in column-sharded (output
#: dim), proj/mlp_out row-sharded (input dim).  Patterns are block-relative;
#: both layouts below derive from this single table.
_BLOCK_RULES: tuple = (
    (r"qkv/kernel", P(None, "model")),
    (r"proj/kernel", P("model", None)),
    (r"mlp_in/kernel", P(None, "model")),
    (r"mlp_in/bias", P("model")),
    (r"mlp_out/kernel", P("model", None)),
)

_TOP_RULES: tuple = (
    (r"emb/table", P("model", None)),
    (r"pos/table", P(None, None)),
    (r"head/kernel", P(None, "model")),
)

#: Per-layer storage (block_0, block_1, ...).
SHARDING_RULES: tuple = (
    tuple((rf"block_\d+/{pat}", spec) for pat, spec in _BLOCK_RULES) + _TOP_RULES
)


def _pipeline_rules() -> tuple:
    # Stacked-block storage: leading layer dim shards over 'pipe' (each rank
    # holds its stage's layers in HBM), inner dims keep the Megatron specs.
    from ..parallel import pipeline as pipeline_lib

    return pipeline_lib.stage_sharding_rules(_BLOCK_RULES, "blocks") + _TOP_RULES


def sharding_rules(cfg: Config) -> tuple:
    if cfg.pipeline_stages > 1:
        return _pipeline_rules()
    if cfg.moe_experts > 0:
        from ..ops import moe as moe_ops

        return moe_ops.SHARDING_RULES + SHARDING_RULES
    return SHARDING_RULES
