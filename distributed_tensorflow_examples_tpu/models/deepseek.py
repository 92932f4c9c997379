"""DeepSeek-V2: latent attention (MLA) with scaled rotary positions, a
leading dense layer, then expert layers whose choice is limited to groups
and which have shared experts beside the routed ones (deepseek-ai's
DeepSeek-V2; the published ``config.json`` keys are this module's
``Config``, ``rope_scaling``'s six flattened to ``rope_*``).

Every layer, with ``N`` an RMSNorm (two a layer):

    x1 = x  + MLA(N(x))
    y  = x1 + F(N(x1))

``F`` is a dense gated-SiLU feed-forward ``hidden -> intermediate_size ->
hidden`` in the first ``first_k_dense_replace`` layers and the expert layer
in every ``moe_layer_freq``-th layer after them (:attr:`Config.layer_kinds`).

MLA is models/mla.py's sub-layer (its equations and its two forms are
there), here with 128 heads, NO rank scales, the softmax's scale ``(nope +
rope)^-0.5 x m(mscale_all_dim)^2`` and rotary frequencies scaled the YaRN
way (``layers.yarn_frequencies``: of the 32 pairs the first 11 keep their
frequency, the last 9 turn 40 times slower, those between are blended; ``cos``
and ``sin`` carry ``m(mscale) / m(mscale_all_dim)``, which is 1 as
published and is asserted, not multiplied in).  What a position leaves
behind is 576 values where expanded keys and values are 40960; the cache is
``[slots, max_len, 576]`` a layer in ``param_dtype``.  The step ABSORBS,
the chunk and the full forward EXPAND (measured both ways at 128 heads:
:data:`PREFILL_BLOCK`); the blocks are this shape's own.

The expert layer, for ``u = N(x1)``:

    m = sum_i w_i E_i(u) + S(u)

the routed part ops/moe.py ``apply_share``'s: softmax router in float32 over
``n_routed_experts``, no bias; expert ``e`` is of group ``e //
(n_routed_experts / n_group)`` - a DEVICE of the published deployment; a
token keeps the ``topk_group`` groups whose best score is largest and chooses
its ``num_experts_per_tok`` among them; weights ``routed_scaling_factor x
s``, not renormalised.  ``S`` is ONE gated-SiLU feed-forward of width
``n_shared_experts x moe_intermediate_size`` (the shared experts side by
side), on every token with weight 1.  It is the MODEL's, not the share's -
every chip of the deployment computes it alike - and is issued after the
routed part in program order, as a product of its own (``moe/shared``): the
compiler is free to run it while the routed rows are gathered.

THE SHARE: ``experts_held`` routed experts from ``expert_first`` on live
here (0 = all) - whole groups, or ``ShareConfig`` raises - and a choice on
any other adds nothing; attention, the shared experts and the dense layer
are whole on every chip.  ``vocab_rows`` (0 = all) is the slice of the
vocabulary whose table rows and head columns live here.

Precision: parameters in ``param_dtype`` (bfloat16); products in it with
float32 accumulation; residual stream, norms, rotary, router and softmax in
float32.

What a session owns in the cache: its rows of every layer's latents; the
engine's key/value contract holds as in models/longcat.py, and the step
asks for ``live`` because its attention reads the cache of the live rows
only (models/mla.py) and because it COUNTS: the cache tree's ``counters`` entry
holds int32 sums over layers and launches (``moe_*``: :data:`COUNTS` of
ops/moe.py ``SHARE_COUNTS``; the chunk's part of three of them once more as
``moe_chunk_*``), and only live rows are counted or get expert rows.

Serving only: no loss (``seq_aux`` and the balance losses are training's),
no mesh.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from ..ops import moe as moe_ops
from . import decoding, layers, mla

#: Cache positions the step's attention reads at a time
#: (``mla.Spec.decode_block``): one block of ONE live slot an item of the
#: kernel's grid (ops/latent_decode.py).  Chosen on one v5e chip at the
#: served widths - 128 heads, 64 slots x 4096, every row live (my chip run,
#: PR 35; PERF.md section 6): with all rows at 1000 / 2500 / 3900 the step
#: takes 17.2 / 19.3 / 21.3 ms at 512 and 17.4 / 19.5 / 20.6 at 1024 (the
#: loop this kernel replaced: 18.1 / 21.4 / 24.3 at 512, same run); at
#: depths drawn like the cell's (mean 1,740, the deepest at 2,970 or 3,500)
#: 18.46 / 18.48 at 512 and 18.59 / 18.61 at 1024 (the loop: 22.25 /
#: 23.30): level where the cell stands, 512 reads less past a slot's row.
DECODE_BLOCK = 512
#: Cached positions a prefill chunk expands and attends over at a time
#: (``mla.Spec.prefill_block``): an item of the kernel's grid
#: (ops/latent_prefill.py) takes one block for a group of heads, the CPU's
#: loop one a trip.  Chosen on one v5e chip at the served widths, 128 heads,
#: 64 slots x 4096 (my chip runs, PR 38; PERF.md section 6): a whole chunk
#: at offsets 0 / 512 / 1536 / 3072 takes 30.3 / 30.4 / 34.2 / 41.4 ms at
#: 1024 and 29.7 / 32.3 / 37.1 / 44.1 at 512 (the loop this kernel replaced,
#: at its best block of 128, where wider scores spilled: 31.0 / 36.1 / 45.9 /
#: 60.6); one sub-layer's attention at 1536 / 3072 takes 1.26 / 2.28 ms at
#: 1024 and 1.61 / 2.63 at 512 (the loop: 3.35 / 5.61).  A block of 1024
#: under a chunk at 0 or 1024 expands 512 positions no query sees, and still
#: the cell's prompts (256-2048: a chunk at 0 / 512 / 1024 / 1536 in 100 /
#: 85 / 57 / 29 of a hundred) come out 1.7 % shorter at 1024.  ABSORBED, as
#: loops, 37.6 / 61.4 / 85.4 ms at 0 / 1536 / 3072 (my chip run, PR 33).
PREFILL_BLOCK = 1024


@dataclasses.dataclass(frozen=True)
class Config:
    """The published keys (DeepSeek-V2's values as defaults) and the share.
    Fixed by the family and not keys here: no bias anywhere, gated SiLU,
    softmax scoring, ``group_limited_greedy``, ``norm_topk_prob`` false,
    untied head."""

    vocab_size: int = 102400
    hidden_size: int = 5120
    intermediate_size: int = 12288
    moe_intermediate_size: int = 1536
    num_hidden_layers: int = 60
    first_k_dense_replace: int = 1
    moe_layer_freq: int = 1
    num_attention_heads: int = 128
    kv_lora_rank: int = 512
    q_lora_rank: int = 1536
    qk_rope_head_dim: int = 64
    qk_nope_head_dim: int = 128
    v_head_dim: int = 128
    n_routed_experts: int = 160
    n_shared_experts: int = 2
    num_experts_per_tok: int = 6
    n_group: int = 8
    topk_group: int = 3
    routed_scaling_factor: float = 16.0
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e4
    #: ``rope_scaling`` (``type`` yarn), key by key.
    rope_factor: float = 40.0
    rope_original_max_position_embeddings: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 0.707
    rope_mscale_all_dim: float = 0.707
    #: The share (module docstring); 0 = everything.
    experts_held: int = 0
    expert_first: int = 0
    vocab_rows: int = 0
    param_dtype: str = "bfloat16"

    def __post_init__(self):
        if self.expert_first + self.held > self.n_routed_experts:
            raise ValueError("the held experts run past n_routed_experts")
        if self.rope_mscale != self.rope_mscale_all_dim:
            raise ValueError(
                "cos and sin would carry m(mscale) / m(mscale_all_dim) != 1, "
                "which this module does not multiply in")
        self.share  # a held range that is not whole groups raises here

    @property
    def dtype(self):
        return jnp.dtype(self.param_dtype)

    @property
    def held(self) -> int:
        return self.experts_held or self.n_routed_experts

    @property
    def vocab(self) -> int:
        return self.vocab_rows or self.vocab_size

    @property
    def latent(self) -> int:
        """Values a position leaves in a layer's cache."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def layer_kinds(self) -> tuple[str, ...]:
        """``"dense"`` or ``"moe"``, the feed-forward of each layer."""
        return tuple(
            "moe" if i >= self.first_k_dense_replace and i % self.moe_layer_freq == 0
            else "dense" for i in range(self.num_hidden_layers))

    @property
    def softmax_scale(self) -> float:
        m = layers.yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)
        return m * m / math.sqrt(self.qk_nope_head_dim + self.qk_rope_head_dim)

    def mla(self) -> mla.Spec:
        """The latent attention's spec (it holds an array: call it inside
        the traced program)."""
        return mla.Spec(
            heads=self.num_attention_heads, q_lora_rank=self.q_lora_rank,
            kv_lora_rank=self.kv_lora_rank, nope=self.qk_nope_head_dim,
            rope=self.qk_rope_head_dim, v_dim=self.v_head_dim,
            q_scale=1.0, kv_scale=1.0, softmax_scale=self.softmax_scale,
            inv_freq=layers.yarn_frequencies(
                self.qk_rope_head_dim, self.rope_theta, self.rope_factor,
                self.rope_original_max_position_embeddings,
                self.rope_beta_fast, self.rope_beta_slow),
            eps=self.rms_norm_eps, dtype=self.dtype,
            decode_block=DECODE_BLOCK, prefill_block=PREFILL_BLOCK,
        )

    @property
    def share(self) -> moe_ops.ShareConfig:
        return moe_ops.ShareConfig(
            n_experts=self.n_routed_experts, n_zero=0,
            top_k=self.num_experts_per_tok, scale=float(self.routed_scaling_factor),
            first=self.expert_first, held=self.held,
            n_group=self.n_group, top_groups=self.topk_group,
        )


# ----------------------------------------------------------------------------
# Parameters and cache
# ----------------------------------------------------------------------------


def init(cfg: Config, rng: jax.Array):
    """Kernels and table normal ``1 / sqrt(hidden)`` (``o`` and every
    ``down``, which write the residual stream, scaled by ``1 / sqrt(2 L)``:
    two writes a layer), norms 1; all in ``param_dtype``.  The held experts
    are stacked."""
    dt, D, F = cfg.dtype, cfg.hidden_size, cfg.moe_intermediate_size
    std = 1.0 / math.sqrt(D)
    res = std / math.sqrt(2 * cfg.num_hidden_layers)
    spec = cfg.mla()

    def normal(k, shape, s=std):
        return (s * jax.random.normal(k, shape)).astype(dt)

    keys = jax.random.split(rng, cfg.num_hidden_layers + 2)
    params = {
        "emb": {"table": normal(keys[-1], (cfg.vocab, D))},
        "norm_f": layers.rmsnorm_init(D, dt),
        "head": {"kernel": normal(keys[-2], (D, cfg.vocab))},
    }
    for i, kind in enumerate(cfg.layer_kinds):
        k = jax.random.split(keys[i], 6)
        layer = {
            "attn_norm": layers.rmsnorm_init(D, dt),
            "attn": mla.init(spec, D, k[0], std=std, out_std=res),
            "ffn_norm": layers.rmsnorm_init(D, dt),
        }
        if kind == "dense":
            layer["ffn"] = layers.gated_mlp_init(
                k[1], D, cfg.intermediate_size, std=std, out_std=res, dtype=dt)
        else:
            layer["moe"] = {
                "router": {"kernel": normal(k[1], (D, cfg.n_routed_experts))},
                "gate": normal(k[2], (cfg.held, D, F)),
                "up": normal(k[3], (cfg.held, D, F)),
                "down": normal(k[4], (cfg.held, F, D), res),
            }
            layer["shared"] = layers.gated_mlp_init(
                k[5], D, cfg.n_shared_experts * F, std=std, out_std=res, dtype=dt)
        params[f"layer_{i}"] = layer
    return params


#: What this model keeps of ops/moe.py ``SHARE_COUNTS``, as ``moe_<name>``
#: (there are no zero-compute experts to count).
COUNTS = ("choices", "choices_held", "experts_touched", "calls", "tokens_reaching")
#: Of the counts, those the chunk keeps a second time as ``moe_chunk_<name>``
#: (a reader that sets the kernel's time in a trace against its least has to
#: know what the chunk's calls did apart from the step's).
CHUNK_COUNTS = ("choices_held", "experts_touched", "calls")


def init_cache(cfg: Config, slots: int, max_len: int):
    """What ``slots`` sessions own - ``[slots, max_len, 576]`` a layer - and
    the counters (module docstring)."""
    cache = {
        f"layer_{i}": {"attn": jnp.zeros((slots, max_len, cfg.latent), cfg.dtype)}
        for i in range(cfg.num_hidden_layers)
    }
    cache["counters"] = moe_ops.share_counters(COUNTS, CHUNK_COUNTS)
    return cache


# ----------------------------------------------------------------------------
# The pieces the three paths share (the latent attention's: models/mla.py)
# ----------------------------------------------------------------------------


def _norm(cfg: Config, p, x):
    return layers.rmsnorm(p, x, cfg.rms_norm_eps)


def _layer(cfg: Config, p, kind: str, x, attn, routed):
    """One layer on ``x [.., D]`` float32.  ``attn(p_attn, h)`` is the
    attention of the normed ``h``; ``routed(u)`` the routed experts' part of
    an expert layer; ``routed`` None: the layer's feed-forward feeds nothing
    that is kept and is skipped whole."""
    x = x + attn(p["attn"], _norm(cfg, p["attn_norm"], x))
    if routed is None:
        return x
    u = _norm(cfg, p["ffn_norm"], x)
    if kind == "dense":
        with jax.named_scope("ffn/dense"):
            return x + layers.gated_mlp(p["ffn"], u, dtype=cfg.dtype)
    m = routed(u)
    with jax.named_scope("moe/shared"):
        return x + m + layers.gated_mlp(p["shared"], u, dtype=cfg.dtype)


def _embed(cfg: Config, params, tokens):
    return layers.embedding_lookup(params["emb"], tokens).astype(jnp.float32)


def _logits(cfg: Config, params, h):
    return layers.dense(params["head"], _norm(cfg, params["norm_f"], h).astype(cfg.dtype))


# ----------------------------------------------------------------------------
# Full forward
# ----------------------------------------------------------------------------


def apply(cfg: Config, params, tokens):
    """tokens ``[B, L]`` int32 -> logits ``[B, L, vocab]`` float32, causal;
    attention in the expanded form, a sequence at a time."""
    B, L = tokens.shape
    spec = cfg.mla()
    h = _embed(cfg, params, tokens)
    for i, kind in enumerate(cfg.layer_kinds):
        p = params[f"layer_{i}"]

        def routed(u):
            m, _ = moe_ops.apply_share(
                p["moe"], u.reshape(B * L, -1), cfg.share, dtype=cfg.dtype)
            return m.reshape(u.shape)

        h = _layer(cfg, p, kind, h, lambda pa, y: mla.forward(spec, pa, y), routed)
    return _logits(cfg, params, h)


# ----------------------------------------------------------------------------
# Serving: the one-token step and the prefill chunk
# ----------------------------------------------------------------------------


def decode_step_batch(cfg: Config, params, cache, token, pos, live):
    """token ``[S]`` int32, pos ``[S]`` int32 (per-row positions), live
    ``[S]`` bool -> (logits ``[S, vocab]``, new cache): every row advances
    its own session one position - writes its latent row at ``pos`` in place
    and attends over its slot's rows ``<= pos``.  A row that is not live is
    inert the key/value way (what it writes is written again by the
    session's first real step, its logits mean nothing); ``live`` keeps it
    out of the attention's read of the cache (it attends over nothing), out
    of the routed experts and out of the counters."""
    spec = cfg.mla()
    counters = cache["counters"]
    new_cache = {}
    h = _embed(cfg, params, token)
    for i, kind in enumerate(cfg.layer_kinds):
        p = params[f"layer_{i}"]
        written = {}

        def attn(pa, y):
            o, written["attn"] = mla.decode(
                spec, pa, y, cache[f"layer_{i}"]["attn"], pos, live)
            return o

        def routed(u):
            nonlocal counters
            m, counters = moe_ops.apply_share_counted(
                p["moe"], u, cfg.share, live, counters, dtype=cfg.dtype)
            return m

        h = _layer(cfg, p, kind, h, attn, routed)
        new_cache[f"layer_{i}"] = written
    new_cache["counters"] = counters
    return _logits(cfg, params, h), new_cache


def prefill_chunk(cfg: Config, params, cache, tokens, slot, offset, n_valid):
    """tokens ``[C]`` int32 - ONE slot's prompt tokens at positions ``offset
    .. offset + C - 1``, the first ``n_valid`` real, the rest padding -> new
    cache: one forward pass writes the valid tokens' latent rows into the
    slot's rows and touches no other slot; its attention expands the slot's
    rows a block at a time and reads no further than ``offset + C``.  No
    final norm, head or logits: the caller decodes the prompt's LAST token
    the ordinary way.  The LAST layer's feed-forward feeds no cache row and
    is not called (nor, once the compiler has looked, is that layer's
    attention past its latent row), so what the counters say ran did run.
    ``C`` is static; ``slot``, ``offset`` and ``n_valid`` are traced
    scalars, so one program serves every chunk."""
    spec = cfg.mla()
    valid = jnp.arange(tokens.shape[0]) < n_valid
    counters = cache["counters"]
    new_cache = {}
    last = cfg.num_hidden_layers - 1
    h = _embed(cfg, params, tokens)
    for i, kind in enumerate(cfg.layer_kinds):
        p = params[f"layer_{i}"]
        written = {}

        def attn(pa, y):
            o, written["attn"] = mla.prefill(
                spec, pa, y, cache[f"layer_{i}"]["attn"], slot, offset, n_valid)
            return o

        def routed(u):
            nonlocal counters
            m, counters = moe_ops.apply_share_counted(
                p["moe"], u, cfg.share, valid, counters,
                chunk_counts=CHUNK_COUNTS, dtype=cfg.dtype)
            return m

        h = _layer(cfg, p, kind, h, attn, routed if i < last else None)
        new_cache[f"layer_{i}"] = written
    new_cache["counters"] = counters
    return new_cache


def serve_decode_fns(cfg: Config):
    """What ``serve.ModelReplicaServer(decode_fns=...)`` is told of this
    model (``decoding.DecodeFns``): its step takes ``live`` (it reads and
    counts live rows only), and a step and a chunk read the cache as far as
    ``mla.decode_rows_read`` / ``mla.prefill_rows_read`` say at this model's
    blocks."""
    return decoding.serve_fns(
        cfg, init_cache, decode_step_batch, prefill_chunk, wants_live=True,
        step_rows_read=functools.partial(mla.decode_rows_read, DECODE_BLOCK),
        chunk_rows_read=functools.partial(mla.prefill_rows_read, PREFILL_BLOCK))


# ----------------------------------------------------------------------------
# Generation
# ----------------------------------------------------------------------------


def generate(cfg: Config, params, prompt, *, max_new_tokens: int,
             temperature: float = 0.0, rng: jax.Array | None = None):
    """prompt ``[B, Tp]`` -> ``[B, Tp + max_new_tokens]`` by
    :func:`prefill_chunk` and :func:`decode_step_batch`, the path a replica
    takes (models/decoding.py)."""
    return decoding.generate(
        cfg, params, prompt, init_cache=init_cache, prefill_chunk=prefill_chunk,
        decode_step_batch=decode_step_batch, max_new_tokens=max_new_tokens,
        temperature=temperature, rng=rng)
