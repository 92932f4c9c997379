"""Selective scan (Mamba-1's recurrence) over one chunk of one sequence.

    h_t = exp(dt_t * A) * h_{t-1} + (dt_t * x_t) B_t        h: [N, D]
    y_t = h_t . C_t + D * x_t

``x, dt [C, D]`` (D channels), ``B, C [C, N]`` (N state dimensions, the same
for every channel), ``A [N, D]``, ``D [D]``, the carried state ``h0 [N, D]``
-> ``y [C, D]`` and the state after the chunk's VALID tokens.  Everything
is float32: ``exp(dt A)`` over hundreds of steps is what the state's
precision rests on.

A recurrence over ``C`` steps on a ``[N, D]`` state is neither a matrix
product nor, as a ``lax.scan``, anything but ``C`` dependent tiny launches;
materialised over time it is ``C * N * D`` floats (168 MB a layer at
C = 512, D = 5120, N = 16).  The kernel keeps the state in VMEM and walks
time inside: the grid is (channel blocks, time blocks), channels parallel,
time in order with the state carried in scratch from one time block to the
next.

Layout.  One time step of one channel block touches ``N`` rows of the
state, each as wide as the block, and nothing couples two channels.  So a
block of ``8 * LANES`` channels is held as ``[8, LANES]`` - whole vector
registers - and the ``N`` state rows are walked by a static loop whose
coefficients ``B_t[n]``, ``C_t[n]`` are SCALARS read from SMEM: no step
needs a transpose, a lane broadcast or a cross-lane reduction.  The callers'
``[C, D]`` arrays are reshaped to ``[C, D / LANES, LANES]`` outside.

Padding must not advance the state: a position at or after ``n_valid`` is
given ``dt = 0``, so ``exp(0) = 1`` and ``dt x B = 0`` - the state after
``n_valid`` tokens falls out with no branch (``y`` at such a position is
finite and meaningless).  The same holds for the time steps and channels
the wrapper pads up to whole blocks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import compiler_params, interpret_mode

LANES = 128
#: Channels of one block: 8 sublanes x LANES, one vector register a state row.
BLOCK_CHANNELS = 8 * LANES
#: Time steps of one block (what one grid step brings into VMEM).
BLOCK_TIME = 128
#: The kernel's name, which its operations carry in a device trace.
KERNEL_NAME = "mamba_selective_scan"


def _scan_kernel(b_ref, c_ref, x_ref, dt_ref, a_ref, d_ref, h0_ref,
                 y_ref, h_ref, state, *, n_state: int, block_time: int):
    """One (channel block, time block): ``block_time`` steps of the
    recurrence on the block's ``[N, 8, LANES]`` state."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        state[...] = h0_ref[...]

    a = [a_ref[n] for n in range(n_state)]
    d = d_ref[...]
    base = j * block_time * n_state

    def step(t, h):
        x, dt = x_ref[t], dt_ref[t]
        u = dt * x
        y = d * x
        at = base + t * n_state
        out = []
        for n in range(n_state):
            hn = jnp.exp(dt * a[n]) * h[n] + u * b_ref[at + n]
            y = y + hn * c_ref[at + n]
            out.append(hn)
        y_ref[t] = y
        return tuple(out)

    h = jax.lax.fori_loop(
        0, block_time, step, tuple(state[n] for n in range(n_state))
    )
    for n in range(n_state):
        state[n] = h[n]

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        h_ref[...] = state[...]


def _pad_to(x, size: int, axis: int):
    if x.shape[axis] == size:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, size - x.shape[axis])
    return jnp.pad(x, pad)


@jax.jit
def selective_scan(x, dt, a, b, c, d, h0, n_valid):
    """``(y [C, D], h [N, D])``: the chunk's outputs and the state after its
    first ``n_valid`` tokens (a traced scalar), from ``h0``.  See the
    module docstring for shapes; any ``C`` and ``D`` (padded here to whole
    blocks).  Compiles through Mosaic on a TPU, interpreted on the CPU."""
    n_time, n_chan = x.shape
    n_state = a.shape[0]
    f32 = jnp.float32
    bt = min(BLOCK_TIME, -(-n_time // 8) * 8)
    T = -(-n_time // bt) * bt
    Dp = -(-n_chan // BLOCK_CHANNELS) * BLOCK_CHANNELS
    G = Dp // LANES  # channel groups; a block takes 8 of them

    dt = jnp.where(jnp.arange(n_time)[:, None] < n_valid, dt.astype(f32), 0.0)

    def chan(v):  # [..., D] -> [..., G, LANES]
        v = _pad_to(v.astype(f32), Dp, v.ndim - 1)
        return v.reshape(v.shape[:-1] + (G, LANES))

    def time(v):
        return _pad_to(v, T, 0)

    flat = lambda v: time(v.astype(f32)).reshape(-1)  # [T * N], to SMEM
    by_time = pl.BlockSpec((bt, 8, LANES), lambda i, j, *_: (j, i, 0))
    by_state = pl.BlockSpec((n_state, 8, LANES), lambda i, j, *_: (0, i, 0))
    y, h = pl.pallas_call(
        functools.partial(_scan_kernel, n_state=n_state, block_time=bt),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(G // 8, T // bt),
            in_specs=[
                by_time, by_time, by_state,
                pl.BlockSpec((8, LANES), lambda i, j, *_: (i, 0)),
                by_state,
            ],
            out_specs=[by_time, by_state],
            scratch_shapes=[pltpu.VMEM((n_state, 8, LANES), f32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((T, G, LANES), f32),
            jax.ShapeDtypeStruct((n_state, G, LANES), f32),
        ],
        compiler_params=compiler_params(("parallel", "arbitrary")),
        interpret=interpret_mode(),
        name=KERNEL_NAME,
    )(flat(b), flat(c), time(chan(x)), time(chan(dt)), chan(a), chan(d), chan(h0))
    return (
        y.reshape(T, Dp)[:n_time, :n_chan],
        h.reshape(n_state, Dp)[:, :n_chan],
    )


def selective_scan_reference(x, dt, a, b, c, d, h0, n_valid):
    """The same recurrence as a ``lax.scan`` over time in plain ``jax.numpy``:
    what the kernel is tested against."""
    f32 = jnp.float32
    x, dt, a, b, c, d, h0 = (v.astype(f32) for v in (x, dt, a, b, c, d, h0))
    dt = jnp.where(jnp.arange(x.shape[0])[:, None] < n_valid, dt, 0.0)

    def step(h, inp):
        xt, dtt, bt, ct = inp
        h = jnp.exp(dtt[None] * a) * h + (dtt * xt)[None] * bt[:, None]
        return h, jnp.sum(h * ct[:, None], axis=0) + d * xt

    h, y = jax.lax.scan(step, h0, (x, dt, b, c))
    return y, h
