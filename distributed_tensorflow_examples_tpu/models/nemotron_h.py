"""Nemotron-H: layers that are ONE sub-layer each - a Mamba-2 mixer whose
state is a matrix a head, grouped-query attention with no positional
encoding, or a LatentMoE feed-forward whose ungated squared-ReLU experts live
in a narrower latent than the router reads (NVIDIA's ``nemotron_h``; the
published ``config.json`` keys are this module's ``Config``).

Embedding (untied from the head); layer ``i`` is ``x <- x + part_i(N(x))``
with ``N`` an RMSNorm (``layer_norm_epsilon``) and ``part_i`` chosen by
``hybrid_override_pattern[i]``; a final RMSNorm; the head.  No bias anywhere
but the conv's.

``M``, Mamba-2 (``H = mamba_num_heads`` heads of ``P = mamba_head_dim``,
``d_inner = H P``, state ``N = ssm_state_size``, ``G = n_groups``):

    z, xBC, dt = split(in_proj(u), [d_inner, d_inner + 2 G N, H])
    xBC        = silu(causal depthwise conv_{conv_kernel}(xBC) + bias)
    x, B, C    = split(xBC, [d_inner, G N, G N])      x [H, P];  B, C [G, N];  head h of group h // (H / G)
    dt         = softplus(dt + dt_bias)   [H];        A = -exp(A_log)   [H]
    S_t[h]     = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] x_t[h] (outer) B_t[g(h)]        [P, N] a head
    y_t[h]     = S_t[h] C_t[g(h)] + D[h] x_t[h]
    y          = rmsnorm_grouped(y * silu(z))         the gate BEFORE the norm, the norm within each
                                                      group of d_inner / G channels, weight [d_inner]
    out        = out_proj(y)

``*``, attention: ``q = u Wq`` as ``[heads, head_dim]``, ``k, v = u Wk, u Wv``
as ``[kv, head_dim]``; NO positional encoding; ``o_t = softmax_j(q_t . k_j /
sqrt(head_dim)) v_j`` over ``j <= t``, query head ``g`` reading K/V head ``g
// (heads / kv)``; ``out = o Wo``.

``E``, LatentMoE:

    s      = sigmoid(u Wr)                         over all n_routed_experts, float32
    choice = the num_experts_per_tok largest of s + e_score_correction_bias
    w_i    = routed_scaling_factor s_i / (sum of the chosen s + 1e-20)
    l      = u W_in                                hidden_size -> moe_latent_size
    E_i(l) = W2_i relu(W1_i l)^2                   NO gate matrix; width moe_intermediate_size
    out    = (sum_i w_i E_i(l)) W_out  +  V2 relu(V1 u)^2        the shared expert on the full hidden,
                                                                 width moe_shared_expert_intermediate_size

The routed part is ops/moe.py's two halves, called apart because they read
two WIDTHS: ``share_plan`` on the normed ``u`` (what the router reads),
``apply_share_plan`` on the latent ``l`` (what the experts read), the ungated
form of ops/grouped_ffn.py under it.

THE SHARE.  ``held_layers`` names the PUBLISHED layers that live here (empty:
all), in order - a stage of a pipeline; of each ``E`` layer's experts
``experts_held`` from ``expert_first`` on (0: all) - a choice on an expert
that lives elsewhere adds nothing, and nothing stands in for the exchange,
which would carry ``moe_latent_size`` values a row; of the vocabulary its
first ``vocab_rows`` ids (0: all), table rows and head columns alike.  The
router, the latent projections, the shared expert, the Mamba and attention
matrices are whole on every chip.  Parameters and cache entries are keyed by
the published index (``layer_4``).

THE CACHE, per layer BY KIND: ``M`` the conv's tail ``[slots, conv_kernel -
1, d_inner + 2 G N]`` and the state ``[slots, H, P, N]``, float32 - ``N`` on
the lanes, ``P`` on the sublanes, so a head's state is whole registers
(ops/ssd.py); ``*`` full rows ``k, v [slots + 1, kv, max_len, head_dim]`` in
``param_dtype`` (models/ring_cache.py's full layer, a SPARE slot for the
step's rows that are not live; its two blocked attentions, no window); ``E``
nothing.  Beside them ``counters`` (below) and ``handoff``: the hidden states
``[handoff_rows, hidden_size]`` float32 the LAST chunk left after every layer
held - what a first stage hands to the second; the chunk computes all its
layers, the last ``E`` too, and this is where their result goes.  A state is
overwritten by every step, so the step is told which rows are LIVE and
leaves every other row's state, tail and rows as they were; a session starts
from the zero state and a zero tail - the chunk at ``offset == 0`` and the
step at ``pos == 0`` start there, whatever the slot held.

The chunk runs the recurrence in its chunked form (``mamba2_ssd_chunk``),
the step updates every live slot's state in place (``mamba2_state_step``);
the full forward is the chunk from the zero state.  Scopes in a trace:
``mamba2/conv``, ``mamba2/ssd``, ``mamba2/norm``, ``moe/route``,
``moe/latent_in``, ``moe/experts``, ``moe/latent_out``, ``moe/shared``,
``nemotron/attn``.

What the model counts on the device (``counters``): the ``moe_*`` and
``moe_chunk_*`` sums of ops/moe.py, models/ring_cache.py's ``attn_*`` rows,
and ``ssd_calls`` / ``ssd_positions``: the chunk kernel's calls and the
positions (padding in) they were dispatched at.

Precision: parameters in ``param_dtype`` (bfloat16); products in it with
float32 accumulation; residual stream, norms, router, softmax, conv, ``dt``,
decays and the state in float32.

Serving only: no loss, no mesh.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from ..ops import grouped_ffn, ssd
from ..ops import moe as moe_ops
from . import decoding, layers, ring_cache

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"
_PATTERN = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
            "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")


@dataclasses.dataclass(frozen=True)
class Config:
    """The published keys (NVIDIA-Nemotron-3-Super-120B-A12B's values as
    defaults) and the share.  Fixed by the family and not keys here: no bias
    but the conv's, ``relu2`` in every expert, SiLU in the mixer, the free
    choice (``n_group`` 1), normalised weights, one shared expert, untied
    head."""

    vocab_size: int = 131072
    hidden_size: int = 4096
    num_hidden_layers: int = 88
    hybrid_override_pattern: str = _PATTERN
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 128
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    n_routed_experts: int = 512
    num_experts_per_tok: int = 22
    moe_latent_size: int = 1024
    moe_intermediate_size: int = 2688
    moe_shared_expert_intermediate_size: int = 5376
    routed_scaling_factor: float = 5.0
    layer_norm_epsilon: float = 1e-5
    #: The share (module docstring): published layer indices, () = all; the
    #: routed experts held, 0 = all; the vocabulary's ids held, 0 = all.
    held_layers: tuple[int, ...] = ()
    experts_held: int = 0
    expert_first: int = 0
    vocab_rows: int = 0
    #: Cache rows the step's and the chunk's attention read at a time.
    attn_block: int = 512
    #: Rows of the ``handoff`` buffer: the widest chunk (the serve engine's
    #: ``PREFILL_CHUNK``).
    handoff_rows: int = 512
    param_dtype: str = "bfloat16"

    def __post_init__(self):
        pattern = self.hybrid_override_pattern
        if len(pattern) != self.num_hidden_layers or set(pattern) - {MAMBA, EXPERTS, ATTENTION}:
            raise ValueError(
                f"hybrid_override_pattern is not {self.num_hidden_layers} of M, E, *")
        if self.mamba_num_heads % self.n_groups:
            raise ValueError("Mamba heads must be a multiple of n_groups")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must be a multiple of K/V heads")
        held = self.held_layers
        if any(not 0 <= i < self.num_hidden_layers for i in held) or \
                list(held) != sorted(set(held)):
            raise ValueError(f"held_layers {held} are not published layers in order")
        if self.expert_first + self.held > self.n_routed_experts:
            raise ValueError("the held experts are not the model's")

    @property
    def dtype(self):
        return jnp.dtype(self.param_dtype)

    @property
    def layers(self) -> tuple[int, ...]:
        """The published indices of the layers that are here, in order."""
        return self.held_layers or tuple(range(self.num_hidden_layers))

    def kind(self, i: int) -> str:
        return self.hybrid_override_pattern[i]

    @property
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    def cache_rows(self, i: int, max_len: int) -> int:
        """Rows a slot has in attention layer ``i``'s cache
        (models/ring_cache.py): every position its own."""
        return max_len

    @property
    def held(self) -> int:
        return self.experts_held or self.n_routed_experts

    @property
    def vocab(self) -> int:
        return self.vocab_rows or self.vocab_size

    @property
    def share(self) -> moe_ops.ShareConfig:
        return moe_ops.ShareConfig(
            n_experts=self.n_routed_experts, n_zero=0, top_k=self.num_experts_per_tok,
            scale=self.routed_scaling_factor, first=self.expert_first, held=self.held,
            scoring="sigmoid", normalise=True, activation="relu2",
        )


# ----------------------------------------------------------------------------
# Parameters and cache
# ----------------------------------------------------------------------------


def init(cfg: Config, rng: jax.Array):
    """The table normal 1 (a row has unit rms, what every reader sees after
    its norm); kernels normal ``1 / sqrt(fan in)``, the projections that
    write the residual stream a third of that; conv taps normal 0.5, its
    bias 0; ``dt_bias`` the inverse softplus of steps log-spaced over the
    heads from 0.001 to 0.1 (Mamba's published range), ``A = -(1 + h % 16)``,
    ``D = 1``; router normal ``1 / sqrt(hidden)``, its bias 0; norms 1; all
    in ``param_dtype``."""
    dt = cfg.dtype
    D, Di, H = cfg.hidden_size, cfg.d_inner, cfg.mamba_num_heads
    A, KV, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    Lt, F, Fs, E = (cfg.moe_latent_size, cfg.moe_intermediate_size,
                    cfg.moe_shared_expert_intermediate_size, cfg.held)

    def normal(k, shape, fan_in, factor=1.0):
        return (factor / math.sqrt(fan_in) * jax.random.normal(k, shape)).astype(dt)

    kernel = lambda *a, **kw: {"kernel": normal(*a, **kw)}
    ones = lambda n: layers.rmsnorm_init(n, dt)

    def mamba(k):
        k = jax.random.split(k, 3)
        step = jnp.exp(jnp.linspace(math.log(0.001), math.log(0.1), H))
        return {
            "in_proj": kernel(k[0], (D, Di + cfg.conv_dim + H), D),
            "conv": {"kernel": (0.5 * jax.random.normal(k[1], (cfg.conv_kernel, cfg.conv_dim))).astype(dt),
                     "bias": jnp.zeros((cfg.conv_dim,), dt)},
            "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dt),
            "A_log": jnp.log(1.0 + jnp.arange(H) % 16).astype(dt),
            "D": jnp.ones((H,), dt),
            "norm": ones(Di),
            "out_proj": kernel(k[2], (Di, D), Di, 1 / 3),
        }

    def attention(k):
        k = jax.random.split(k, 4)
        return {"q": kernel(k[0], (D, A * hd), D), "k": kernel(k[1], (D, KV * hd), D),
                "v": kernel(k[2], (D, KV * hd), D), "o": kernel(k[3], (A * hd, D), A * hd, 1 / 3)}

    def experts(k):
        k = jax.random.split(k, 7)
        return {
            "router": {**kernel(k[0], (D, cfg.n_routed_experts), D),
                       "bias": jnp.zeros((cfg.n_routed_experts,), dt)},
            "latent_in": kernel(k[1], (D, Lt), D),
            "latent_out": kernel(k[2], (Lt, D), Lt, 1 / 3),
            "up": normal(k[3], (E, Lt, F), Lt), "down": normal(k[4], (E, F, Lt), F),
            "shared": {"up": kernel(k[5], (D, Fs), D), "down": kernel(k[6], (Fs, D), Fs, 1 / 3)},
        }

    make = {MAMBA: ("mamba", mamba), ATTENTION: ("attn", attention), EXPERTS: ("moe", experts)}
    keys = jax.random.split(rng, cfg.num_hidden_layers + 2)
    params = {
        "emb": {"table": normal(keys[-1], (cfg.vocab, D), 1)},
        "norm_f": ones(D),
        "head": kernel(keys[-2], (D, cfg.vocab), D),
    }
    for i in cfg.layers:
        name, fn = make[cfg.kind(i)]
        params[f"layer_{i}"] = {"norm": ones(D), name: fn(keys[i])}
    return params


#: What this model keeps of ops/moe.py ``SHARE_COUNTS``, as ``moe_<name>``,
#: and of them what the chunk keeps a second time as ``moe_chunk_<name>``
#: (models/deepseek.py has the reasons).
COUNTS = ("choices", "choices_held", "experts_touched", "calls", "tokens_reaching")
CHUNK_COUNTS = ("choices_held", "experts_touched", "calls")
#: The chunk kernel's calls and the positions they were dispatched at.
SSD_COUNTS = ("ssd_calls", "ssd_positions")


def init_cache(cfg: Config, slots: int, max_len: int):
    """What ``slots`` sessions own, per layer by kind, the counters and the
    hand-off buffer (module docstring)."""
    f32 = jnp.float32
    H, P, N = cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.ssm_state_size
    rows = lambda: jnp.zeros(
        (slots + 1, cfg.num_key_value_heads, max_len, cfg.head_dim), cfg.dtype)
    cache = {}
    for i in cfg.layers:
        if cfg.kind(i) == MAMBA:
            cache[f"layer_{i}"] = {
                "conv": jnp.zeros((slots, cfg.conv_kernel - 1, cfg.conv_dim), f32),
                "ssm": jnp.zeros((slots, H, P, N), f32),
            }
        elif cfg.kind(i) == ATTENTION:
            cache[f"layer_{i}"] = {"k": rows(), "v": rows()}
    cache["counters"] = {
        **moe_ops.share_counters(COUNTS, CHUNK_COUNTS),
        **{name: jnp.zeros((slots,), jnp.int32) for name in ring_cache.ATTN_COUNTS},
        **{name: jnp.zeros((), jnp.int32) for name in SSD_COUNTS},
    }
    cache["handoff"] = jnp.zeros((cfg.handoff_rows, cfg.hidden_size), f32)
    return cache


# ----------------------------------------------------------------------------
# The pieces the three paths share
# ----------------------------------------------------------------------------


def _norm(cfg: Config, p, x):
    return layers.rmsnorm(p, x, cfg.layer_norm_epsilon)


def _mm(cfg: Config, p, x):
    """``x @ kernel``: operands in ``param_dtype``, float32 out."""
    return layers.dense(p, x.astype(cfg.dtype))


#: The experts' activation, the shared expert's too (ops/grouped_ffn.py).
_relu2 = grouped_ffn.ACTIVATIONS["relu2"]


def _mamba_inputs(cfg: Config, p, u, tail):
    """From the normed ``u [B, C, D]`` and the conv's carried tail ``[B,
    conv_kernel - 1, conv_dim]`` to what the recurrence takes - the gate ``z
    [B, C, d_inner]``, ``x [B, C, H, P]``, ``B, C [B, C, G, N]`` (after conv
    and SiLU), ``dt [B, C, H]`` - and the conv's input window ``[B, C +
    conv_kernel - 1, conv_dim]``, from which the caller cuts the next tail."""
    C = u.shape[1]
    Di, G, N = cfg.d_inner, cfg.n_groups, cfg.ssm_state_size
    z, xbc, dt = jnp.split(_mm(cfg, p["in_proj"], u), [Di, Di + cfg.conv_dim], axis=-1)
    with jax.named_scope("mamba2/conv"):
        window = jnp.concatenate([tail, xbc], axis=1)
        w = p["conv"]["kernel"].astype(jnp.float32)
        xbc = jax.nn.silu(p["conv"]["bias"].astype(jnp.float32) + sum(
            w[k] * window[:, k:k + C] for k in range(cfg.conv_kernel)))
    x, b, c = jnp.split(xbc, [Di, Di + G * N], axis=-1)
    lead = x.shape[:2]
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(jnp.float32))
    return (z, x.reshape(lead + (cfg.mamba_num_heads, cfg.mamba_head_dim)),
            b.reshape(lead + (G, N)), c.reshape(lead + (G, N)), dt, window)


def _mamba_out(cfg: Config, p, y, z):
    """``out_proj(rmsnorm_grouped(y * silu(z)))`` for the heads' outputs ``y
    [.., H, P]`` and the gate ``z [.., d_inner]``: the gate before the norm,
    the norm within each of ``n_groups`` groups of channels."""
    with jax.named_scope("mamba2/norm"):
        G = cfg.n_groups
        g = (y.reshape(z.shape) * jax.nn.silu(z)).reshape(z.shape[:-1] + (G, -1))
        g = _norm(cfg, {"scale": p["norm"]["scale"].reshape(G, -1)}, g).reshape(z.shape)
    return _mm(cfg, p["out_proj"], g)


def _decay(p):
    return -jnp.exp(p["A_log"].astype(jnp.float32))


def _mamba_chunk(cfg: Config, p, u, tail, s0, n_valid):
    """The mixer over ``C`` consecutive tokens of each of ``B`` sequences:
    ``u [B, C, D]`` normed, of which the first ``n_valid`` positions are real;
    ``tail``, ``s0 [B, H, P, N]`` carried in.  Returns the mixer's output and
    the tail and state after the valid tokens."""
    z, x, b, c, dt, window = _mamba_inputs(cfg, p, u, tail)
    a, d = _decay(p), p["D"].astype(jnp.float32)
    with jax.named_scope("mamba2/ssd"):
        y, s = jax.lax.map(
            lambda row: ssd.ssd_chunk(
                row[0], row[1], a, row[2], row[3], d, row[4], n_valid,
                chunk_size=cfg.chunk_size, dtype=cfg.dtype),
            (x, dt, b, c, s0))
    tail = jax.lax.dynamic_slice_in_dim(window, n_valid, tail.shape[1], axis=1)
    return _mamba_out(cfg, p, y, z), tail, s


def _mamba_step(cfg: Config, p, u, layer, live, fresh):
    """The same mixer for ONE token of each row, ``u [S, D]``, on the cache's
    own arrays: every live row's tail and state advanced (the state in place,
    ops/ssd.py), every other row's left as they were."""
    mask = lambda m, like: m.reshape(m.shape + (1,) * (like.ndim - 1))
    tail0 = jnp.where(mask(fresh, layer["conv"]), 0.0, layer["conv"])
    z, x, b, c, dt, window = _mamba_inputs(cfg, p, u[:, None], tail0)
    z, x, b, c, dt = z[:, 0], x[:, 0], b[:, 0], c[:, 0], dt[:, 0]
    with jax.named_scope("mamba2/ssd"):
        y, state = ssd.state_step(layer["ssm"], x, dt, _decay(p), b, c, live, fresh)
        y = y + p["D"].astype(jnp.float32)[:, None] * x
    tail = jnp.where(mask(live, tail0), window[:, 1:], layer["conv"])
    return _mamba_out(cfg, p, y, z), {"conv": tail, "ssm": state}


def _qkv(cfg: Config, p, u):
    """``q [.., KV, G, hd]`` and ``kv [.., 2, KV, hd]`` (keys, values) in
    ``param_dtype`` - what the cache keeps - from the normed ``u [.., D]``."""
    KV, hd = cfg.num_key_value_heads, cfg.head_dim
    lead = u.shape[:-1]
    q = _mm(cfg, p["q"], u).reshape(lead + (KV, cfg.num_attention_heads // KV, hd))
    k = _mm(cfg, p["k"], u).reshape(lead + (KV, hd))
    v = _mm(cfg, p["v"], u).reshape(lead + (KV, hd))
    return q.astype(cfg.dtype), jnp.stack([k, v], axis=-3).astype(cfg.dtype)


def _attn_out(cfg: Config, p, o):
    """``o Wo`` for the heads' results ``o [.., KV, G, hd]`` float32."""
    return _mm(cfg, p["o"], o.reshape(o.shape[:-3] + (-1,)))


_ATTN_SCOPE = "nemotron/attn"


def _experts(cfg: Config, p, u, live, counters, chunk_counts=()):
    """The ``E`` part on the normed ``u [T, D]`` -> ``(out [T, D], counters)``:
    the plan from ``u``, the held experts' products on the latent, their
    weighted sum back through ``latent_out``, and the shared expert on ``u``
    itself."""
    plan = moe_ops.share_plan(p["router"], u, cfg.share, live)
    with jax.named_scope("moe/latent_in"):
        latent = _mm(cfg, p["latent_in"], u)
    m, counters = moe_ops.apply_share_counted(
        p, latent, cfg.share, live, counters, chunk_counts=chunk_counts,
        dtype=cfg.dtype, plan=plan)
    with jax.named_scope("moe/latent_out"):
        routed = _mm(cfg, p["latent_out"], m)
    with jax.named_scope("moe/shared"):
        shared = _mm(cfg, p["shared"]["down"], _relu2(_mm(cfg, p["shared"]["up"], u)))
    return routed + shared, counters


def _embed(cfg: Config, params, tokens):
    return layers.embedding_lookup(params["emb"], tokens).astype(jnp.float32)


def _logits(cfg: Config, params, h):
    return layers.dense(params["head"], _norm(cfg, params["norm_f"], h).astype(cfg.dtype))


# ----------------------------------------------------------------------------
# Full forward
# ----------------------------------------------------------------------------


def apply(cfg: Config, params, tokens):
    """tokens ``[B, L]`` int32 -> logits ``[B, L, vocab]`` float32, causal,
    every sequence from the zero state."""
    B, L = tokens.shape
    H, P, N = cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.ssm_state_size
    causal = jnp.tril(jnp.ones((L, L), bool))
    h = _embed(cfg, params, tokens)
    for i in cfg.layers:
        p = params[f"layer_{i}"]
        u = _norm(cfg, p["norm"], h)
        if cfg.kind(i) == MAMBA:
            tail = jnp.zeros((B, cfg.conv_kernel - 1, cfg.conv_dim), jnp.float32)
            s0 = jnp.zeros((B, H, P, N), jnp.float32)
            out, _, _ = _mamba_chunk(cfg, p["mamba"], u, tail, s0, L)
        elif cfg.kind(i) == ATTENTION:
            q, kv = _qkv(cfg, p["attn"], u)
            with jax.named_scope(_ATTN_SCOPE):
                s = jnp.einsum("bqkgd,btkd->bkgqt", q, kv[:, :, 0],
                               preferred_element_type=jnp.float32)
                s = jnp.where(causal, s / math.sqrt(cfg.head_dim), -jnp.inf)
                w = jax.nn.softmax(s, axis=-1).astype(cfg.dtype)
                o = jnp.einsum("bkgqt,btkd->bqkgd", w, kv[:, :, 1],
                               preferred_element_type=jnp.float32)
            out = _attn_out(cfg, p["attn"], o)
        else:
            out, _ = _experts(cfg, p["moe"], u.reshape(B * L, -1), None, {})
            out = out.reshape(B, L, -1)
        h = h + out
    return _logits(cfg, params, h)


# ----------------------------------------------------------------------------
# Serving: the one-token step and the prefill chunk
# ----------------------------------------------------------------------------


def decode_step_batch(cfg: Config, params, cache, token, pos, live):
    """token ``[S]`` int32, pos ``[S]`` int32 (per-row positions), live
    ``[S]`` bool -> (logits ``[S, vocab]``, new cache): every LIVE row
    advances its own session one position - its tails and states by one
    token, its key and value written at its row of every attention layer.  A
    row that is not live leaves everything its slot owns as it was, reads
    nothing, gets no expert row and no count; its logits mean nothing.  A row
    at ``pos == 0`` starts from the zero state and tail whatever its slot
    held."""
    counters = dict(cache["counters"])
    new_cache = {"handoff": cache["handoff"]}
    fresh = pos == 0
    h = _embed(cfg, params, token)
    for i in cfg.layers:
        p = params[f"layer_{i}"]
        u = _norm(cfg, p["norm"], h)
        if cfg.kind(i) == MAMBA:
            out, new_cache[f"layer_{i}"] = _mamba_step(
                cfg, p["mamba"], u, cache[f"layer_{i}"], live, fresh)
        elif cfg.kind(i) == ATTENTION:
            q, new = _qkv(cfg, p["attn"], u)
            o, new_cache[f"layer_{i}"] = ring_cache.step_attention(
                q, new, cache[f"layer_{i}"], pos, live, None, counters,
                attn_block=cfg.attn_block, scope=_ATTN_SCOPE)
            out = _attn_out(cfg, p["attn"], o)
        else:
            out, counters = _experts(cfg, p["moe"], u, live, counters)
        h = h + out
    new_cache["counters"] = counters
    return _logits(cfg, params, h), new_cache


def prefill_chunk(cfg: Config, params, cache, tokens, slot, offset, n_valid):
    """tokens ``[C]`` int32 - ONE slot's prompt tokens at positions ``offset
    .. offset + C - 1``, the first ``n_valid`` real, the rest padding -> new
    cache: one forward pass writes the valid tokens' keys and values into the
    slot's rows and advances the slot's conv tails and states by exactly the
    valid tokens, from what the chunk before left there - or from zero where
    ``offset == 0`` - and touches no other slot.  EVERY layer held is
    computed, the last ``E`` too: the hidden states after it go to
    ``handoff`` (module docstring).  No final norm, head or logits: the
    caller decodes the prompt's LAST token the ordinary way.  ``C`` is static
    (at most ``handoff_rows``); ``slot``, ``offset`` and ``n_valid`` are
    traced scalars, so one program serves every chunk."""
    C = tokens.shape[0]
    if C > cfg.handoff_rows:
        raise ValueError(f"a chunk of {C} tokens is wider than handoff_rows "
                         f"({cfg.handoff_rows})")
    valid = jnp.arange(C) < n_valid
    fresh = offset == 0
    counters = dict(cache["counters"])
    new_cache = {}
    h = _embed(cfg, params, tokens)
    for i in cfg.layers:
        p = params[f"layer_{i}"]
        u = _norm(cfg, p["norm"], h)
        if cfg.kind(i) == MAMBA:
            layer = cache[f"layer_{i}"]
            row = lambda a: jax.lax.dynamic_slice_in_dim(a, slot, 1, axis=0)
            tail0 = jnp.where(fresh, 0.0, row(layer["conv"]))
            s0 = jnp.where(fresh, 0.0, row(layer["ssm"]))
            out, tail, state = _mamba_chunk(cfg, p["mamba"], u[None], tail0, s0, n_valid)
            out = out[0]
            put = lambda a, r: jax.lax.dynamic_update_slice_in_dim(a, r, slot, axis=0)
            new_cache[f"layer_{i}"] = {
                "conv": put(layer["conv"], tail), "ssm": put(layer["ssm"], state)}
            counters["ssd_calls"] += 1
            counters["ssd_positions"] += C
        elif cfg.kind(i) == ATTENTION:
            q, new = _qkv(cfg, p["attn"], u)
            o, new_cache[f"layer_{i}"] = ring_cache.chunk_attention(
                q, new, cache[f"layer_{i}"], slot, offset, n_valid, None, slack=0,
                attn_block=cfg.attn_block, dtype=cfg.dtype, scope=_ATTN_SCOPE)
            out = _attn_out(cfg, p["attn"], o)
        else:
            out, counters = _experts(
                cfg, p["moe"], u, valid, counters, chunk_counts=CHUNK_COUNTS)
        h = h + out
    new_cache["counters"] = counters
    new_cache["handoff"] = jax.lax.dynamic_update_slice(cache["handoff"], h, (0, 0))
    return new_cache


def _attention_layers(cfg: Config) -> Config | None:
    """``cfg`` holding its attention layers alone - the layers that keep
    rows, which is what models/ring_cache.py's two readings walk - or None
    where it holds none."""
    rows = tuple(i for i in cfg.layers if cfg.kind(i) == ATTENTION)
    return dataclasses.replace(cfg, held_layers=rows) if rows else None


def decode_rows_read(cfg: Config, pos, live, max_len: int) -> float:
    """Cache positions one decode step reads a slot in the mean ATTENTION
    layer (the other kinds hold no rows; none held: 0)."""
    rows = _attention_layers(cfg)
    return ring_cache.decode_rows_read(rows, pos, live, max_len) if rows else 0.0


def prefill_rows_read(cfg: Config, offset: int, chunk: int, max_len: int) -> float:
    """... and the attention of one chunk of ``chunk`` queries at ``offset``."""
    rows = _attention_layers(cfg)
    return ring_cache.prefill_rows_read(rows, offset, chunk, max_len) if rows else 0.0


def serve_decode_fns(cfg: Config):
    """What ``serve.ModelReplicaServer(decode_fns=...)`` is told of this
    model (``decoding.DecodeFns``): its step takes ``live`` (a row that is
    not live must leave its state alone), and a step and a chunk read the
    attention layers' rows as far as :func:`decode_rows_read` /
    :func:`prefill_rows_read` say."""
    return decoding.serve_fns(
        cfg, init_cache, decode_step_batch, prefill_chunk, wants_live=True,
        step_rows_read=functools.partial(decode_rows_read, cfg),
        chunk_rows_read=functools.partial(prefill_rows_read, cfg))


# ----------------------------------------------------------------------------
# Generation
# ----------------------------------------------------------------------------


def generate(cfg: Config, params, prompt, *, max_new_tokens: int,
             temperature: float = 0.0, rng: jax.Array | None = None):
    """prompt ``[B, Tp]`` -> ``[B, Tp + max_new_tokens]`` by
    :func:`prefill_chunk` and :func:`decode_step_batch`, the path a replica
    takes (models/decoding.py).  Its one chunk a row is the whole prompt, so
    the hand-off buffer gets the rows that chunk needs."""
    rows = max(cfg.handoff_rows, jnp.shape(prompt)[1] - 1)
    return decoding.generate(
        dataclasses.replace(cfg, handoff_rows=rows), params, prompt,
        init_cache=init_cache, prefill_chunk=prefill_chunk,
        decode_step_batch=decode_step_batch, max_new_tokens=max_new_tokens,
        temperature=temperature, rng=rng)
