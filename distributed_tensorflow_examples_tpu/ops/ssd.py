"""Mamba-2's recurrence - a state that is a MATRIX a head - in its two served
forms: the chunked ("state-space duality") form over a prefill chunk, and
the one-token update of every live slot's state in place.

    S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] x_t[h] (outer) B_t[g(h)]     [P, N] a head
    y_t[h] = S_t[h] C_t[g(h)] + D[h] x_t[h]

``H`` heads of ``P`` channels, a state of ``N`` columns a channel, ONE decay
a head (``A [H]`` negative, ``dt [.., H]`` positive), ``B, C [.., G, N]``
shared by the ``H / G`` heads of a group (head ``h`` of group ``h // (H /
G)``).  ops/selective_scan.py's recurrence (Mamba-1: a decay a channel and a
state column, one ``B, C`` for all channels) is a vector-unit walk over time;
with one decay a head the walk over a block of positions IS three matrix
products, and that is this module.

THE CHUNK (:func:`ssd_chunk`, ``mamba2_ssd_chunk`` in a trace).  With ``a_t
= dt_t A`` and ``cum_t`` its running sum INSIDE a block of ``chunk_size``
positions (float32, made outside the kernel: ``exp`` of it is what the
state's precision rests on), position ``t`` of a block that starts from
``S``:

    y_t = sum_{s <= t} exp(cum_t - cum_s) dt_s (C_t . B_s) x_s  +  exp(cum_t) S C_t  +  D x_t
    S'  = exp(cum_last) S  +  sum_s exp(cum_last - cum_s) dt_s x_s (outer) B_s

- the block's own part ``(L o (C B^T) dt) X`` with ``L`` the lower-triangular
decay products, the state's part and the block's contribution to the state,
each a product on the MXU.  The grid is (group, time block): groups in
parallel, time in order, the group's ``[H / G, P, N]`` states carried in VMEM
scratch from block to block.  TIME LIES ON THE LANES: the kernel takes ``x``
as ``[H, P, T]`` and gives ``y`` so (the wrapper transposes; ``P`` = 64 would
fill half a register's lanes), computes ``(C B^T)^T = B C^T`` and multiplies
from the right, so every product is plain or has its right operand
transposed, every decay a row or a column that broadcasts as it lies.
Operands of the products are cast to ``dtype`` (the caller's parameter
type: bfloat16 served), accumulated in float32; decays, ``dt`` and the state
are float32.  Padding must not advance the state: a position at or after
``n_valid`` is given ``dt = 0`` (``y`` there is finite and meaningless), as
are the positions the wrapper pads to whole blocks.

THE STEP (:func:`state_step`, ``mamba2_state_step``).  One token a slot:
``S' = exp(dt A) S + (dt x) (outer) B``, ``y = S' C`` (the caller adds ``D
x``).  The work is the states' bytes - ``H P N`` float32 a slot a layer, 4.19
MB at 128 x 64 x 128 - so each LIVE slot's state is read once and written
once, IN PLACE: the cache's array is aliased to the output
(``input_output_aliases``) and the grid walks the live slots only - their
numbers are prefetched, compacted to the front, and every grid step past the
last of them names the block already in VMEM, which moves nothing (the
grouped feed-forward's way with its blocks, ops/grouped_ffn.py).  A slot that
is not live is neither read nor written; a ``fresh`` one (a session's first
position) starts from zero whatever the slot held.  A block is ONE SLOT'S
WHOLE STATE (4 MiB; in and out, double buffers: 16 MiB of VMEM,
:data:`STEP_VMEM_LIMIT`): the heads' outputs are columns of one ``[P, H]``
block, which may be cut along the lanes only in whole registers.

Layout.  The state is ``[.., H, P, N]``: ``N`` on the lanes (128: whole
registers), ``P`` on the sublanes; the step takes ``x`` as ``[S, P, H]`` so
that a head's channels are a COLUMN, which broadcasts over the lanes as it
lies, as ``B``'s row does over the sublanes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import compiler_params, interpret_mode

#: The kernels' names, which their operations carry in a device trace.
CHUNK_KERNEL_NAME = "mamba2_ssd_chunk"
STEP_KERNEL_NAME = "mamba2_state_step"
#: What the step's call may take of VMEM: a slot's whole state a block (4
#: MiB at 128 x 64 x 128), in and out, double buffers - 16 MiB, the compiler's
#: default allowance, before the small operands.
STEP_VMEM_LIMIT = 48 * 1024 * 1024


# ----------------------------------------------------------------------------
# The chunk
# ----------------------------------------------------------------------------


def _chunk_kernel(x_ref, b_ref, c_ref, rows_ref, cols_ref, s0_ref, y_ref, s_ref,
                  state, *, heads: int, dtype):
    """One (group, time block): the block's ``L`` positions of the group's
    ``heads`` heads.  ``x_ref [heads, P, L]``; ``b_ref, c_ref [L, N]``;
    ``rows_ref [heads, 2, L]`` = (cum, dt) with time on the lanes;
    ``cols_ref [L, 2 heads]`` = the same with time on the sublanes (cum of
    head ``h`` in column ``h``, its dt in column ``heads + h``)."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        state[...] = s0_ref[...]

    f32 = jnp.float32
    b, c = b_ref[...].astype(dtype), c_ref[...].astype(dtype)
    L = b.shape[0]
    # (C B^T)^T: [s, t], shared by the group's heads.
    gt = jax.lax.dot_general(b, c, (((1,), (1,)), ((), ())), preferred_element_type=f32)
    s_at = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    t_at = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    seen = s_at <= t_at
    for h in range(heads):
        cum_row, dt_row = rows_ref[h, 0:1, :], rows_ref[h, 1:2, :]  # [1, L]
        cum_col = cols_ref[:, h:h + 1]  # [L, 1]
        dt_col = cols_ref[:, heads + h:heads + h + 1]
        # L^T o (B C^T) o dt_s: position s's weight in position t's output.
        mt = jnp.where(seen, jnp.exp(jnp.where(seen, cum_row - cum_col, 0.0)), 0.0)
        mt = (gt * mt * dt_col).astype(dtype)
        x = x_ref[h]  # [P, L]
        s_h = state[h]  # [P, N]
        y = jnp.dot(x.astype(dtype), mt, preferred_element_type=f32)
        y += jnp.exp(cum_row) * jax.lax.dot_general(
            s_h.astype(dtype), c, (((1,), (1,)), ((), ())), preferred_element_type=f32)
        y_ref[h] = y
        last = cum_row[:, L - 1:L]  # [1, 1]
        xw = (x * (jnp.exp(last - cum_row) * dt_row)).astype(dtype)
        # [1, 1] -> [1, N] -> [P, N]: along the lanes, then the sublanes.
        kept = jnp.exp(last + jnp.zeros((1, s_h.shape[1]), f32))
        state[h] = kept * s_h + jnp.dot(xw, b, preferred_element_type=f32)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        s_ref[...] = state[...]


def _masked_steps(dt, a, n_valid, chunk_size: int):
    """``(dt, cum)``, each ``[n_blocks, L, H]`` float32: ``dt`` zeroed at and
    after ``n_valid`` and padded to whole blocks, and the running sum of ``dt
    A`` inside each block."""
    T, H = dt.shape
    L = chunk_size
    n = -(-T // L)
    dt = jnp.where(jnp.arange(T)[:, None] < n_valid, dt.astype(jnp.float32), 0.0)
    dt = jnp.pad(dt, ((0, n * L - T), (0, 0))).reshape(n, L, H)
    return dt, jnp.cumsum(dt * a.astype(jnp.float32), axis=1)


@functools.partial(jax.jit, static_argnames=("chunk_size", "dtype"))
def ssd_chunk(x, dt, a, b, c, d, s0, n_valid, *, chunk_size: int = 128,
              dtype=jnp.float32):
    """``(y [T, H, P], S [H, P, N])``: the outputs of ``T`` consecutive
    positions and the state after the first ``n_valid`` of them (a traced
    scalar), from ``s0``.  ``x [T, H, P]``, ``dt [T, H]``, ``a, d [H]``, ``b,
    c [T, G, N]``, ``s0 [H, P, N]``; any ``T`` (padded here to whole blocks
    of ``chunk_size``, which on a TPU is 128: time lies on the lanes).
    Compiles through Mosaic on a TPU, interpreted on the CPU."""
    T, H, P = x.shape
    G, N = b.shape[1:]
    L, hg = chunk_size, H // G
    f32 = jnp.float32
    dt_b, cum = _masked_steps(dt, a, n_valid, L)
    n = dt_b.shape[0]
    Tp = n * L
    pad_t = lambda v: jnp.pad(v.astype(f32), ((0, Tp - T),) + ((0, 0),) * (v.ndim - 1))
    # Time on the lanes: [H, 2, Tp]; on the sublanes, by group: [G, Tp, 2 hg].
    rows = jnp.stack([cum.reshape(Tp, H).T, dt_b.reshape(Tp, H).T], axis=1)
    by_group = lambda v: v.reshape(Tp, G, hg).transpose(1, 0, 2)
    cols = jnp.concatenate([by_group(cum), by_group(dt_b)], axis=-1)
    x_t = jnp.moveaxis(pad_t(x), 0, 2)  # [H, P, Tp]
    flat = lambda v: pad_t(v).reshape(Tp, G * N)
    head_block = pl.BlockSpec((hg, P, L), lambda g, j: (g, 0, j))
    by_time = pl.BlockSpec((L, N), lambda g, j: (j, g))
    state_block = pl.BlockSpec((hg, P, N), lambda g, j: (g, 0, 0))
    y_t, s = pl.pallas_call(
        functools.partial(_chunk_kernel, heads=hg, dtype=dtype),
        grid=(G, n),
        in_specs=[
            head_block, by_time, by_time,
            pl.BlockSpec((hg, 2, L), lambda g, j: (g, 0, j)),
            pl.BlockSpec((None, L, 2 * hg), lambda g, j: (g, j, 0)),
            state_block,
        ],
        out_specs=[head_block, state_block],
        out_shape=[
            jax.ShapeDtypeStruct((H, P, Tp), f32),
            jax.ShapeDtypeStruct((H, P, N), f32),
        ],
        scratch_shapes=[pltpu.VMEM((hg, P, N), f32)],
        compiler_params=compiler_params(("parallel", "arbitrary")),
        interpret=interpret_mode(),
        name=CHUNK_KERNEL_NAME,
    )(x_t, flat(b), flat(c), rows, cols, s0.astype(f32))
    y = jnp.moveaxis(y_t, 2, 0)[:T]
    return y + d.astype(f32)[:, None] * x.astype(f32), s


def ssd_chunk_reference(x, dt, a, b, c, d, s0, n_valid):
    """The same positions by the recurrence itself, one at a time
    (``lax.scan``), in float32: what the kernel is tested against."""
    f32 = jnp.float32
    x, dt, a, b, c, d, s0 = (v.astype(f32) for v in (x, dt, a, b, c, d, s0))
    hg = x.shape[1] // b.shape[1]
    dt = jnp.where(jnp.arange(x.shape[0])[:, None] < n_valid, dt, 0.0)

    def step(s, inp):
        xt, dtt, bt, ct = inp  # [H, P], [H], [G, N], [G, N]
        bt, ct = jnp.repeat(bt, hg, axis=0), jnp.repeat(ct, hg, axis=0)  # [H, N]
        s = (jnp.exp(dtt * a)[:, None, None] * s
             + (dtt[:, None] * xt)[:, :, None] * bt[:, None, :])
        return s, jnp.sum(s * ct[:, None, :], axis=-1) + d[:, None] * xt

    s, y = jax.lax.scan(step, s0, (x, dt, b, c))
    return y, s


# ----------------------------------------------------------------------------
# The step
# ----------------------------------------------------------------------------


def _step_kernel(slot_of, n_live, decay, s_ref, x_ref, b_ref, c_ref, y_ref, out_ref,
                 *, per_group: int):
    """One live slot: ``s_ref, out_ref [H, P, N]`` (the same rows of the
    cache), ``x_ref [P, H]`` = ``dt x`` with a head a column, ``b_ref, c_ref
    [G, N]``, ``decay`` flat ``[S H]`` in SMEM; ``y_ref [P, H]``."""
    i = pl.program_id(0)
    live = n_live[0]
    H = s_ref.shape[0]

    @pl.when(i < live)
    def _():
        slot = slot_of[i]
        lane = jax.lax.broadcasted_iota(jnp.int32, y_ref.shape, 1)
        y = jnp.zeros(y_ref.shape, jnp.float32)
        for h in range(H):
            g = h // per_group
            new = decay[slot * H + h] * s_ref[h] + x_ref[:, h:h + 1] * b_ref[g:g + 1, :]
            out_ref[h] = new
            col = jnp.sum(new * c_ref[g:g + 1, :], axis=1, keepdims=True)  # [P, 1]
            y = jnp.where(lane == h, col, y)
        y_ref[...] = y

    # No slot is live: the one block the grid names goes back as it came.
    @pl.when((live == 0) & (i == 0))
    def _():
        out_ref[...] = s_ref[...]
        y_ref[...] = jnp.zeros(y_ref.shape, jnp.float32)


@jax.jit
def state_step(state, x, dt, a, b, c, live, fresh):
    """``(y [S, H, P], state)``: every LIVE slot's state advanced one
    position IN PLACE (donate ``state [S, H, P, N]`` float32 and the update
    costs its live rows' bytes once each way) and ``y = S' C`` of it (zeros
    where not live; WITHOUT ``D x``).  ``x [S, H, P]``, ``dt [S, H]``, ``a
    [H]``, ``b, c [S, G, N]``, ``live, fresh [S]`` bool: a slot that is not
    live is left as it was, a fresh one starts from the zero state."""
    S, H, P, N = state.shape
    G = b.shape[1]
    f32 = jnp.float32
    dt, x = dt.astype(f32), x.astype(f32)
    decay = jnp.where(fresh[:, None], 0.0, jnp.exp(dt * a.astype(f32)))
    dtx = jnp.moveaxis(dt[:, :, None] * x, 1, 2)  # [S, P, H]
    # The live slots' numbers first; past them the last live one again.
    slot_of = jnp.argsort(~live, stable=True).astype(jnp.int32)
    n_live = jnp.sum(live, dtype=jnp.int32).reshape(1)

    def of_slot(*tail):
        return lambda i, so, n, _: (
            so[jnp.minimum(i, jnp.maximum(n[0] - 1, 0))],) + tail

    state_block = pl.BlockSpec((None, H, P, N), of_slot(0, 0, 0))
    by_head = pl.BlockSpec((None, P, H), of_slot(0, 0))
    by_group = pl.BlockSpec((None, G, N), of_slot(0, 0))
    y, state = pl.pallas_call(
        functools.partial(_step_kernel, per_group=H // G),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(S,),
            in_specs=[state_block, by_head, by_group, by_group],
            out_specs=[by_head, state_block],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((S, P, H), f32),
            jax.ShapeDtypeStruct(state.shape, f32),
        ],
        # The cache's array IS the output: slots the grid does not name keep
        # what they hold (operand 3 counts the three prefetched scalars).
        input_output_aliases={3: 1},
        # In order: a grid step past the live slots counts on the block the
        # step before it left in VMEM.
        compiler_params=compiler_params(("arbitrary",), STEP_VMEM_LIMIT),
        interpret=interpret_mode(),
        name=STEP_KERNEL_NAME,
    )(slot_of, n_live, decay.reshape(-1), state, dtx, b.astype(f32), c.astype(f32))
    y = jnp.where(live[:, None, None], jnp.moveaxis(y, 1, 2), 0.0)
    return y, state


def state_step_reference(state, x, dt, a, b, c, live, fresh):
    """:func:`state_step` in plain ``jax.numpy``: one position of the
    recurrence on every slot, then the rows that are not live put back."""
    f32 = jnp.float32
    x, dt, a, b, c = (v.astype(f32) for v in (x, dt, a, b, c))
    hg = x.shape[1] // b.shape[1]
    b, c = jnp.repeat(b, hg, axis=1), jnp.repeat(c, hg, axis=1)  # [S, H, N]
    s0 = jnp.where(fresh[:, None, None, None], 0.0, state)
    new = (jnp.exp(dt * a)[:, :, None, None] * s0
           + (dt[:, :, None] * x)[..., None] * b[:, :, None, :])
    y = jnp.sum(new * c[:, :, None, :], axis=-1)
    keep = live[:, None, None, None]
    return jnp.where(keep[..., 0], y, 0.0), jnp.where(keep, new, state)
