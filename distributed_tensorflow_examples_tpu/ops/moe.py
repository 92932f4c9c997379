"""Mixture-of-Experts FFN with expert parallelism over the ``expert`` axis.

No reference analog (SURVEY.md §2b strategy table: EP "not needed" for
parity) — provided because a complete TPU framework serves the axis, and
because MoE is where the ``expert`` mesh axis and ``all_to_all`` earn their
keep (the same role D11's ``collective_nccl_all_to_all.h`` plays in the
reference's native layer).

TPU-first formulation — the GShard/Mesh-TF einsum dispatch, not a gather
loop: token->expert routing materialises as STATIC-shaped one-hot dispatch/
combine tensors and three einsums, so XLA sees dense MXU work plus a
layout change it lowers to ``all_to_all`` over the expert axis when the
expert dim is sharded (dynamic shapes would fall off the MXU entirely).
Capacity-bounded: each expert processes at most C tokens per step;
overflow tokens are dropped (contribute zero) exactly as in Switch/GShard.

Components:
- top-k router (k=2 default) with renormalised gates,
- capacity C = ceil(k*N/E * capacity_factor),
- load-balance auxiliary loss (Switch eq. 4): E * sum_e f_e * p_e,
- expert FFN: per-expert GELU MLP, weights stacked [E, ...] and sharded
  ``P('expert', ...)`` so each rank holds only its experts (rules below).

A second layer beside it, :func:`apply_share`, is ONE CHIP'S SHARE of a
wide expert-parallel deployment, dropless: it is told which of the model's
experts it holds, routes over all of them, and computes the part of the
result that its own experts (and the zero-compute ones, which need no
exchange) give - ops/grouped_ffn.py does the products.  It is two halves, a
plan from what the router reads (:func:`share_plan`) and the products on
what the experts read (:func:`apply_share_plan`), which a model whose router
reads another tensor than its experts calls apart.  The two layers share
nothing but this file; a model calls the one it is.
"""

from __future__ import annotations

import dataclasses
import math
import typing
import warnings

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..models import layers
from . import grouped_ffn as grouped_ffn_lib


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    #: Routing-group size (GShard's G): tokens route within fixed-size
    #: groups so the dispatch tensor is [G, g, E, C_g] with C_g ~ k*g/E —
    #: total memory O(N*g*k), NOT the O(N^2*k) of ungrouped [N, E, C]
    #: dispatch (which OOMs at real sequence lengths).
    group_size: int = 1024


def init(rng, dim: int, hidden: int, moe: MoEConfig):
    ks = jax.random.split(rng, 3)
    E = moe.n_experts
    # Per-expert glorot: fan_in/out of ONE expert's matrices.
    w1 = jax.vmap(lambda k: layers.glorot_uniform(k, (dim, hidden)))(
        jax.random.split(ks[0], E)
    )
    w2 = jax.vmap(lambda k: layers.glorot_uniform(k, (hidden, dim)))(
        jax.random.split(ks[1], E)
    )
    return {
        "router": {"kernel": layers.glorot_uniform(ks[2], (dim, E))},
        "w1": w1,
        "b1": jnp.zeros((E, hidden), jnp.float32),
        "w2": w2,
        "b2": jnp.zeros((E, dim), jnp.float32),
    }


def capacity(group_tokens: int, moe: MoEConfig) -> int:
    c = math.ceil(moe.top_k * group_tokens / moe.n_experts * moe.capacity_factor)
    return max(4, c)


def _group(n: int, want: int, shards: int = 1) -> int:
    """Largest divisor of ``n`` that is <= ``want`` (the routing-group size).

    ``shards``: number of mesh shards the flattened token dim arrives
    distributed over (data x expert).  The group count N/g must be a
    multiple of it, so groups never straddle a shard boundary — routing
    then stays shard-local and only the dispatched [E, G, C, D] buffers
    cross the mesh (as all_to_all).  Falls back to plain divisor-of-N when
    no such g exists (e.g. tiny unit-test shapes)."""
    from .common import largest_divisor

    g = min(want, n)
    while g > 1 and not (n % g == 0 and (n // g) % shards == 0):
        g -= 1
    if g > 1 or n % shards == 0:
        return g
    warnings.warn(
        f"moe: no routing-group size <= {want} splits {n} tokens into a "
        f"multiple of {shards} shards; groups will straddle shard "
        "boundaries and the dispatch may lower to all-gather instead of "
        "all_to_all (pad batch*seq to a multiple of data*expert shards)."
    )
    return largest_divisor(n, want)


def apply(p, x, moe: MoEConfig, *, dtype=None, mesh=None):
    """x: [B, T, D] -> (y [B, T, D], aux_loss scalar f32).

    Routing runs in f32 (softmax/top-k numerics); expert matmuls in
    ``dtype`` (bf16 on TPU) like every other dense layer.  Tokens route
    within groups of ``moe.group_size`` (capacity is per group), the GShard
    construction that keeps the dispatch tensors linear in total tokens.

    With ``mesh`` (carrying an ``expert`` axis): tokens arrive sharded over
    ``('data','expert')`` (the caller shards its batch over BOTH axes —
    models/transformer.py ``data_axes``), expert_in/out are pinned to
    ``P('expert','data',...)``, and the group->expert redistribution on each
    side of the expert FFN lowers to a genuine ``all_to_all`` over the
    expert axis (asserted at the HLO level by tests/test_hlo_sharding.py).
    Without a mesh the einsums run locally (unit tests, single chip).
    """
    B, T, D = x.shape
    E, k = moe.n_experts, moe.top_k
    N = B * T
    shards = 1
    if mesh is not None:
        shards = mesh.shape.get("data", 1) * mesh.shape.get("expert", 1)
    g = _group(N, moe.group_size, shards)
    G = N // g
    C = capacity(g, moe)
    tok = x.reshape(G, g, D)
    if mesh is not None and G % shards == 0:
        # Keep the group dim on the token shards across the reshape: groups
        # are whole-shard slices (see _group), so this is a no-move pin.
        tok = jax.lax.with_sharding_constraint(
            tok,
            jax.sharding.NamedSharding(mesh, P(("data", "expert"), None, None)),
        )
    elif mesh is not None and shards > 1:
        warnings.warn(
            f"moe: group count {G} is not a multiple of the {shards} token "
            "shards; skipping the ('data','expert') token pin — the "
            "dispatch may not lower to all_to_all at this shape."
        )

    logits = jnp.einsum("gnd,de->gne", tok.astype(jnp.float32), p["router"]["kernel"])
    probs = jax.nn.softmax(logits, axis=-1)  # [G, g, E]

    # Top-k expert choice per token; gates renormalised over the chosen k.
    gate_vals, expert_idx = jax.lax.top_k(probs, k)  # [G, g, k]
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9
    )

    # Position of each (token, choice) within its expert's per-group
    # capacity buffer: rank by arrival order (cumsum over the one-hot),
    # GShard's position-in-group; positions >= C are dropped.
    onehot = jax.nn.one_hot(expert_idx, E, dtype=jnp.float32)  # [G, g, k, E]
    # Priority: every token's FIRST choice ranks before any second choice
    # (GShard's ordering) — lay choices out [k, g] inside each group.
    flat = onehot.transpose(0, 2, 1, 3).reshape(G, k * g, E)
    pos_flat = jnp.cumsum(flat, axis=1) - flat
    pos_in_expert = pos_flat.reshape(G, k, g, E).transpose(0, 2, 1, 3)
    pos = jnp.sum(pos_in_expert * onehot, axis=-1)  # [G, g, k]
    keep = pos < C
    gate_vals = gate_vals * keep

    # combine[g, n, e, c]: gate weight of token n at slot c of expert e.
    slot = jax.nn.one_hot(
        jnp.where(keep, pos, C).astype(jnp.int32), C, dtype=jnp.float32
    )  # [G, g, k, C]
    combine = jnp.einsum("gnke,gnkc->gnec", onehot, slot * gate_vals[..., None])
    dispatch = jnp.einsum("gnke,gnkc->gnec", onehot, slot * keep[..., None])

    cd = jnp.float32 if dtype is None else dtype
    expert_in = jnp.einsum(
        "gnec,gnd->egcd", dispatch.astype(cd), tok.astype(cd)
    )  # [E, G, C, D] — expert x group: the all_to_all boundary (tokens
    # leave their home ('data','expert') shard for their expert's rank)
    expert_in = _constrain_expert(expert_in, mesh)
    h = jnp.einsum("egcd,edh->egch", expert_in, p["w1"].astype(cd))
    h = jax.nn.gelu(h + p["b1"].astype(cd)[:, None, None, :])
    out = jnp.einsum("egch,ehd->egcd", h, p["w2"].astype(cd))
    out = out + p["b2"].astype(cd)[:, None, None, :]
    out = _constrain_expert(out, mesh)
    y = jnp.einsum("gnec,egcd->gnd", combine.astype(cd), out)

    # Switch load-balance loss: E * sum_e (tokens routed to e / N) * mean_e
    # router prob.  Uses the FIRST choice's routing fraction (Switch eq. 4),
    # computed over ALL tokens (groups together).
    frac = jnp.mean(onehot[:, :, 0, :], axis=(0, 1))  # [E]
    mean_prob = jnp.mean(probs, axis=(0, 1))  # [E]
    aux = E * jnp.sum(frac * mean_prob)

    return y.reshape(B, T, D).astype(x.dtype), aux


def _constrain_expert(t, mesh):
    """Pin [E, G, C, D] to ``P('expert','data',...)`` between the dispatch/
    combine einsums and the expert FFN: E on the expert ranks (each holds its
    experts' capacity buffers), G back on the data axis.  Because the input
    tokens are sharded over ``('data','expert')`` on G's flattened source,
    this reshard is exactly the GShard all_to_all.

    Explicit-mesh (round-3 fix): the previous bare-``PartitionSpec`` +
    ``except Exception`` form silently no-op'd under the jitted train step
    (which establishes no global mesh context) — per ADVICE.md, failures
    must propagate.  Skips only the two legitimate cases: no mesh given
    (unit tests / single chip) or a mesh without an ``expert`` axis; G is
    left unconstrained when it doesn't divide the data axis (a 1-group
    input must not be forced onto 'data')."""
    if mesh is None or mesh.shape.get("expert", 1) <= 1:
        return t
    g_entry = "data" if t.shape[1] % mesh.shape.get("data", 1) == 0 else None
    if g_entry is None and mesh.shape.get("data", 1) > 1:
        warnings.warn(
            f"moe: group dim {t.shape[1]} does not divide the data axis "
            f"({mesh.shape.get('data', 1)}); dropping the group entry from "
            "the expert buffers' sharding — capacity buffers replicate over "
            "'data' at this shape."
        )
    return jax.lax.with_sharding_constraint(
        t, jax.sharding.NamedSharding(mesh, P("expert", g_entry, None, None))
    )


#: Rule fragment for a block containing one MoE layer under prefix `moe/`.
SHARDING_RULES: tuple = (
    (r".*moe/router/kernel", P(None, None)),
    (r".*moe/w1", P("expert", None, "model")),
    (r".*moe/b1", P("expert", "model")),
    (r".*moe/w2", P("expert", "model", None)),
    (r".*moe/b2", P("expert", None)),
)


# ----------------------------------------------------------------------------
# One chip's share of a dropless expert layer
# ----------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ShareConfig:
    """What the router ranges over and what of it lives here."""

    n_experts: int  # routed experts of the MODEL (ids 0 .. n_experts - 1)
    n_zero: int  # zero-compute experts after them: E(u) = u
    top_k: int
    scale: float  # every chosen weight is multiplied by it
    first: int  # the routed experts held here are first .. first + held - 1
    held: int
    #: The choice limited to groups (:func:`share_choice`): expert ``e`` is
    #: of group ``e // (n_experts // n_group)`` - a device of the deployment
    #: - and a token chooses among its ``top_groups`` best groups only.
    #: ``top_groups`` 0: the free choice over everything.
    n_group: int = 0
    top_groups: int = 0
    #: What turns the router's logits into scores: ``"softmax"`` over all
    #: the experts, or ``"sigmoid"``, each expert's own.
    scoring: str = "softmax"
    #: The chosen scores are divided by their sum (plus ``NORMALISE_EPS``)
    #: before ``scale``: a token's weights then add up to ``scale``.
    normalise: bool = False
    #: What an expert applies (ops/grouped_ffn.py ``ACTIVATIONS``): to its
    #: gate's half where its parameters hold a ``gate``, ``down . (act(gate .
    #: x) * (up . x))``, else to its one product, ``down . act(up . x)``.
    activation: str = "silu"

    def __post_init__(self):
        if self.scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"unknown scoring {self.scoring!r}")
        if self.activation not in grouped_ffn_lib.ACTIVATIONS:
            raise ValueError(f"unknown activation {self.activation!r}")
        if not self.top_groups:
            return
        if self.n_zero or self.n_group <= 0 or self.n_experts % self.n_group:
            raise ValueError(
                f"a choice limited to groups needs n_experts ({self.n_experts}) "
                f"in n_group ({self.n_group}) equal groups and no zero-compute "
                f"experts ({self.n_zero})")
        per = self.n_experts // self.n_group
        if not 0 < self.top_groups <= self.n_group or self.top_groups * per < self.top_k:
            raise ValueError(
                f"top_groups ({self.top_groups}) of {self.n_group} groups of {per} "
                f"cannot give top_k ({self.top_k}) choices")
        if self.first % per or self.held % per:
            raise ValueError(
                f"the held experts {self.first} .. {self.first + self.held - 1} are "
                f"not whole groups of {per}: the share would be no device's")


#: What keeps a normalised token's weights finite where every chosen score
#: underflows (the published modelling code's constant).
NORMALISE_EPS = 1e-20

#: What :func:`share_counts` counts, each an int32 scalar.
SHARE_COUNTS = ("choices", "choices_held", "choices_zero", "experts_touched",
                "calls", "tokens_reaching")


def share_choice(s, share: ShareConfig, bias=None):
    """``(choice, score)``, each ``[T, top_k]``: the experts each row of the
    scores ``s [T, n_experts + n_zero]`` float32 chooses and the score each
    is weighted by.  Free (``top_groups`` 0): the largest of ``s + bias``
    (``bias`` enters the CHOICE only; None: of ``s`` alone).  Limited to
    groups (DeepSeek-V2's ``group_limited_greedy``, its device-limited
    routing): a group's score is the largest ``s`` in it, the
    ``top_groups`` best groups are kept, ``s`` outside them is set to 0 and
    the ``top_k`` largest of what is left are chosen, with the scores they
    have there."""
    if not share.top_groups:
        _, choice = jax.lax.top_k(s if bias is None else s + bias, share.top_k)
        return choice, jnp.take_along_axis(s, choice, axis=1)
    if bias is not None:
        raise ValueError("the choice limited to groups is on the scores alone: "
                         "the router has a bias")
    T, G = s.shape[0], share.n_group
    group_score = s.reshape(T, G, -1).max(axis=-1)
    _, best = jax.lax.top_k(group_score, share.top_groups)  # [T, top_groups]
    kept = jnp.any(best[:, :, None] == jnp.arange(G)[None, None, :], axis=1)  # [T, G]
    kept = jnp.repeat(kept, share.n_experts // G, axis=1)
    score, choice = jax.lax.top_k(jnp.where(kept, s, 0.0), share.top_k)
    return choice, score


def share_rows_block(tokens: int) -> int:
    """Rows of one block of the grouped product: a prefill chunk's hundreds
    of tokens fill MXU-high blocks, a decode step's few dozen rows would
    pay for 128 and use two."""
    return 128 if tokens >= 128 else 32


class SharePlan(typing.NamedTuple):
    """Where a call's choices go (:func:`share_plan`): everything of a call
    that depends on the router's input alone."""

    w: jax.Array  # [T, k] float32: the weight of each choice
    on_held: jax.Array  # [T, k] bool: a live row's choice on an expert held here
    on_zero: jax.Array  # [T, k] bool: ... on a zero-compute expert
    sizes: jax.Array  # [held] int32: rows of each held expert's group
    dest: jax.Array  # [T k] int32: a held choice's row of the buffer (else past it)
    token_of_row: jax.Array  # [rows] int32: the token whose row each one is
    live: jax.Array  # [T] bool


def share_plan(p_router, r_in, share: ShareConfig, live=None) -> SharePlan:
    """The plan of one call from the router's input ``r_in [T, D]`` float32
    - which need not be what the experts read (a router that reads its
    layer's input plans before the attention whose result the experts get).

    Router in float32 throughout (the product at the highest precision): ``s
    = softmax(r_in . router)`` over all ``n_experts + n_zero``, or the
    sigmoid of each (``share.scoring``); the choice is :func:`share_choice`'s
    (free over ``s + bias``, or limited to groups); weights ``scale * s`` of
    the chosen, or where ``share.normalise`` ``scale * s / (sum of the chosen
    s + NORMALISE_EPS)``.  A choice on a held expert becomes a row of that
    expert's group (sorted by expert, each group on a block boundary:
    ops/grouped_ffn.py).  ``p_router``: ``kernel [D, n_experts + n_zero]``,
    ``bias`` (or none: the choice is on ``s`` alone).  ``live [T]`` bool: a
    row that is not live (an empty slot, padding) gets no expert row.  The
    row buffer is static and holds the worst case, ``top_k x T`` choices all
    held."""
    T = r_in.shape[0]
    k, E, held = share.top_k, share.n_experts, share.held
    f32 = jnp.float32
    live = jnp.ones((T,), bool) if live is None else live
    with jax.named_scope("moe/route"):
        logits = jnp.dot(
            r_in.astype(f32), p_router["kernel"].astype(f32),
            precision=jax.lax.Precision.HIGHEST,
        )
        if share.scoring == "softmax":
            s = jax.nn.softmax(logits, axis=-1)
        else:
            s = jax.nn.sigmoid(logits)
        bias = p_router.get("bias")
        choice, chosen = share_choice(  # [T, k]
            s, share, None if bias is None else bias.astype(f32))
        if share.normalise:
            chosen = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + NORMALISE_EPS)
        w = share.scale * chosen
        local = choice - share.first
        on_held = (local >= 0) & (local < held) & live[:, None]
        on_zero = (choice >= E) & live[:, None]
        # Each held choice's row: its group's start plus how many choices on
        # the same expert come before it.
        block = share_rows_block(T)
        rows = _share_rows(T, share)
        flat = jnp.where(on_held, local, held).reshape(-1)  # [T k]
        hit = flat[:, None] == jnp.arange(held)[None, :]  # [T k, held]
        sizes = jnp.sum(hit, axis=0, dtype=jnp.int32)
        before = jnp.cumsum(hit, axis=0, dtype=jnp.int32) - 1
        starts, _ = grouped_ffn_lib.group_starts(sizes, block)
        dest = jnp.sum(jnp.where(hit, starts[None, :] + before, 0), axis=1)
        dest = jnp.where(flat < held, dest, rows)  # past the end: dropped
        token_of_row = jnp.full((rows,), T - 1, jnp.int32).at[dest].set(
            jnp.arange(T * k, dtype=jnp.int32) // k, mode="drop")
    return SharePlan(w, on_held, on_zero, sizes, dest, token_of_row, live)


def _share_rows(tokens: int, share: ShareConfig) -> int:
    """Rows of the grouped product's buffer for a call of ``tokens``."""
    block = share_rows_block(tokens)
    return (-(-tokens * share.top_k // block) + share.held) * block


def apply_share_plan(p, u, plan: SharePlan, share: ShareConfig, *, dtype):
    """u ``[T, D]`` float32 (normed) -> ``m [T, D]`` float32: the part of
    ``sum_i w_i E_i(u)`` that this chip's experts and the zero-compute
    experts give, the choices and weights ``plan``'s.  A choice on a held
    expert is a row of the grouped product; a choice on a zero-compute
    expert adds ``w u`` where the token lives; a choice on an expert that
    lives on another chip adds NOTHING - no capacity, no dropped token, no
    stand-in for the other chips' part; a row that is not live gets a zero
    result.  ``p``: ``gate, up [held, D, F]``, ``down [held, F, D]``; with
    no ``gate`` among them the experts are ungated.  ``D`` is ``u``'s width,
    which need not be the width the plan's router read."""
    T, D = u.shape
    rows = _share_rows(T, share)
    with jax.named_scope("moe/experts"):
        y = grouped_ffn_lib.grouped_ffn(
            jnp.take(u.astype(dtype), plan.token_of_row, axis=0), plan.sizes,
            p.get("gate"), p["up"], p["down"], block_rows=share_rows_block(T),
            activation=share.activation,
        )
        # A row outside the groups may hold anything: selected away, never
        # multiplied by a zero.
        mine = jnp.take(y, jnp.minimum(plan.dest, rows - 1), axis=0).reshape(
            T, share.top_k, D)
        m = jnp.sum(
            jnp.where(plan.on_held[..., None], plan.w[..., None] * mine, 0.0), axis=1)
    with jax.named_scope("moe/zero"):
        m = m + jnp.sum(jnp.where(plan.on_zero, plan.w, 0.0), axis=1, keepdims=True) * u
    return m


def share_counts(plan: SharePlan, share: ShareConfig) -> dict:
    """:data:`SHARE_COUNTS` of the call ``plan`` is of, from the plan alone:
    the live rows' choices, those on held and on zero-compute experts, the
    held experts with at least one row, 1 for the call, and the live rows
    with at least one held choice (``tokens_reaching``: what the
    deployment's exchange would send here)."""
    count = lambda x: jnp.sum(x, dtype=jnp.int32)
    return {
        "choices": share.top_k * count(plan.live), "choices_held": count(plan.on_held),
        "choices_zero": count(plan.on_zero), "experts_touched": count(plan.sizes > 0),
        "calls": jnp.int32(1), "tokens_reaching": count(jnp.any(plan.on_held, axis=1)),
    }


def apply_share(p, u, share: ShareConfig, live=None, *, dtype, plan=None):
    """u ``[T, D]`` float32 (normed) -> ``(m [T, D] float32, counts)``:
    :func:`apply_share_plan` under ``plan`` - None: :func:`share_plan`'s
    from ``u`` itself, with ``p["router"]`` and ``live`` - and the call's
    :func:`share_counts`.  A plan handed in was made with its own ``live``."""
    if plan is None:
        plan = share_plan(p["router"], u, share, live)
    m = apply_share_plan(p, u, plan, share, dtype=dtype)
    return m, share_counts(plan, share)


def share_counters(counts, chunk_counts=()) -> dict:
    """What a model that calls :func:`apply_share_counted` keeps in its
    cache tree's ``counters`` entry: a zeroed ``moe_<name>`` for each of
    ``counts`` (of :data:`SHARE_COUNTS`) and a ``moe_chunk_<name>`` for each
    the prefill chunk keeps a second time."""
    names = [f"moe_{n}" for n in counts] + [f"moe_chunk_{n}" for n in chunk_counts]
    return {name: jnp.zeros((), jnp.int32) for name in names}


def apply_share_counted(p, u, share: ShareConfig, live, counters, *,
                        chunk_counts=(), dtype, plan=None):
    """:func:`apply_share` and ``counters`` (:func:`share_counters`) with
    this call's counts added to the entries it has - a prefill chunk names
    the ``chunk_counts`` it keeps a second time."""
    m, counts = apply_share(p, u, share, live, dtype=dtype, plan=plan)
    added = {f"moe_{k}": v for k, v in counts.items()}
    added.update({f"moe_chunk_{k}": counts[k] for k in chunk_counts})
    return m, {k: v + added.get(k, 0) for k, v in counters.items()}
