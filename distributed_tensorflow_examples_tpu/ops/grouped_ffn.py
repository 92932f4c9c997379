"""Feed-forward over rows grouped by expert: the experts a chip holds, each
applied to the rows routed to it, gated or - where the caller hands in no
``gate`` - not.

    y[r] = down[e] . (act(gate[e] . x[r]) * (up[e] . x[r]))       r in group e
    y[r] = down[e] . act(up[e] . x[r])                            gate=None

``act`` is SiLU or what the caller names of :data:`ACTIVATIONS`: ReLU, or
the squared ReLU (``"relu2"``) of an ungated expert.

``rows [M, D]`` (bfloat16) hold group after group in expert order, each
group STARTING ON A BLOCK BOUNDARY (:func:`group_starts`): group ``e`` is
rows ``[start_e, start_e + size_e)`` with ``start_e`` the sum of the sizes
before it, each rounded up to ``block_rows``.  ``group_sizes [E]`` int32,
``gate, up [E, D, F]`` (``gate`` may be ``None``), ``down [E, F, D]`` ->
``[M, D]`` float32.  A row
that lies in no group (the filling of a group's last block, everything past
the last group) has an UNDEFINED result - it may never have been written -
and its input may be anything finite; the caller reads the groups' rows.

Why a kernel.  At decode a token's dozen choices fall on a handful of the
experts held, a row or two each; in a prefill chunk on all of them, some
rows each.  The work is reading the touched experts' matrices ONCE (75.5 MB
an expert at 6144 x 2048, 92 us of a v5e's bandwidth against 12 us of its
MXU for a block of 32 rows), and ``M`` is static and sized for the worst
case, of which a step fills a hundredth.  XLA's ragged products read every
expert or loop over all ``M`` rows.  Here the grid is (row block, phase
step) and three scalars a block are prefetched: a block belongs to ONE
expert (that is what the alignment buys), its index maps name that expert's
matrices, and every block past the last real one maps to the blocks already
in VMEM and computes nothing - no read, no write, a third of a microsecond
a grid step.  An expert with no row is never named, so never read.

One row block runs two phases over the grid's second axis: ``D / block_k``
steps that accumulate ``x . gate`` and ``x . up`` in float32 scratch
(``[block_rows, F]`` each; ungated: ``x . up`` alone, one accumulator and no
read of a second matrix), then ``F / block_f`` steps that add ``h[:,
f-block] . down[f-block]`` into the resident output block.  Blocks of the
matrices are whole rows of them (contiguous in HBM).  Products are bfloat16
x bfloat16 accumulated in float32; ``act`` and the gate's product in float32,
``h`` rounded once to bfloat16 - the arithmetic of ``models/layers.py
gated_mlp``.

VMEM at the served widths (D 6144, F 2048) with ``block_rows`` 128,
``BLOCK_K`` 1024, ``BLOCK_F`` 512, double buffers counted: gate and up
blocks 16.8 MB, down 12.6 MB, output 6.3 MB, rows 0.5 MB, scratch 2.6 MB -
39 MB of the chip's 128 MiB, above the compiler's default allowance, so
the call states its own (:data:`VMEM_LIMIT`).  At D 2560, F 768 neither
block divides its dimension and :func:`_block` takes 640 and 384 (four steps
and two): gate and up blocks 3.9 MB, down 3.9 MB, output 2.6 MB, rows 0.3
MB, scratch 1.0 MB - 12 MB.  Ungated at D 1024, F 2688 ``D`` is one block
and :func:`_block` takes 384 of ``F`` (one step and seven): the up block -
an expert's whole matrix - 11.0 MB, down 1.6 MB, output 1.0 MB, rows 0.5 MB,
scratch 2.1 MB - 16 MB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import compiler_params, interpret_mode

#: Rows of ``gate`` / ``up`` (of D) and of ``down`` (of F) one grid step
#: brings in; a dimension shorter than its block is taken whole, one that
#: its block does not divide in narrower blocks (:func:`_block`).
BLOCK_K = 1024
BLOCK_F = 512
VMEM_LIMIT = 64 * 1024 * 1024
#: The kernel's name, which its operations carry in a device trace.
KERNEL_NAME = "moe_grouped_ffn"
#: What ``activation`` may name.
ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu,
               "relu2": lambda v: jnp.square(jax.nn.relu(v))}


def group_starts(group_sizes, block_rows: int):
    """``(starts [E], rows_used)``: where each group begins when every group
    starts on a multiple of ``block_rows``, and the end of the last one's
    last block."""
    padded = -(-group_sizes // block_rows) * block_rows
    ends = jnp.cumsum(padded)
    return ends - padded, ends[-1]


def _accumulate(s, acc, value):
    """``acc`` set at the phase's first step, added to after."""

    @pl.when(s == 0)
    def _():
        acc[...] = value

    @pl.when(s > 0)
    def _():
        acc[...] += value


def _down_phase(real, s, h, h_ref, down_ref, out_ref, *, nk: int, nf: int):
    """What both forms end on: at the first phase's last step ``h()`` is
    rounded and laid out by ``F`` block, then ``F / block_f`` steps add ``h[:,
    f-block] . down[f-block]`` into the resident output block."""

    @pl.when(real & (s == nk - 1))
    def _():
        rounded = h().astype(h_ref.dtype)
        bf = rounded.shape[1] // nf
        for j in range(nf):
            h_ref[j] = rounded[:, j * bf:(j + 1) * bf]

    @pl.when(real & (s >= nk))
    def _():
        j = s - nk
        _accumulate(j, out_ref, jnp.dot(
            h_ref[j], down_ref[...], preferred_element_type=jnp.float32))


def _kernel(tile_expert, n_real, x_ref, gate_ref, up_ref, down_ref, out_ref,
            g_acc, u_acc, h_ref, *, nk: int, nf: int, act):
    del tile_expert  # the index maps read it
    t, s = pl.program_id(0), pl.program_id(1)
    real = t < n_real[0]

    @pl.when(real & (s < nk))
    def _():
        x = x_ref[...]
        g = jnp.dot(x, gate_ref[...], preferred_element_type=jnp.float32)
        u = jnp.dot(x, up_ref[...], preferred_element_type=jnp.float32)

        @pl.when(s == 0)
        def _():
            g_acc[...] = g
            u_acc[...] = u

        @pl.when(s > 0)
        def _():
            g_acc[...] += g
            u_acc[...] += u

    _down_phase(real, s, lambda: act(g_acc[...]) * u_acc[...], h_ref, down_ref, out_ref,
                nk=nk, nf=nf)


def _kernel_ungated(tile_expert, n_real, x_ref, up_ref, down_ref, out_ref,
                    u_acc, h_ref, *, nk: int, nf: int, act):
    """:func:`_kernel` with no gate: one accumulation over ``up``, ``h =
    act(x . up)``."""
    del tile_expert
    t, s = pl.program_id(0), pl.program_id(1)
    real = t < n_real[0]

    @pl.when(real & (s < nk))
    def _():
        _accumulate(s, u_acc, jnp.dot(
            x_ref[...], up_ref[...], preferred_element_type=jnp.float32))

    _down_phase(real, s, lambda: act(u_acc[...]), h_ref, down_ref, out_ref, nk=nk, nf=nf)


def _block(n: int, want: int) -> int:
    """``want`` where it divides ``n`` (or ``n`` is shorter); otherwise the
    largest multiple of 128 under ``want`` that does (2560 under 1024: 640;
    768 under 512: 384)."""
    if n <= want:
        return n
    for block in (want, *range(want - want % 128, 0, -128)):
        if n % block == 0:
            return block
    raise ValueError(f"a dimension of {n} is no multiple of its block {want}, "
                     "nor of a multiple of 128 under it")


@functools.partial(jax.jit, static_argnames=("block_rows", "activation"))
def grouped_ffn(rows, group_sizes, gate, up, down, *, block_rows: int = 128,
                activation: str = "silu"):
    """See the module docstring.  Any ``M`` (padded here to whole blocks);
    ``block_rows`` a multiple of 16; ``activation`` of :data:`ACTIVATIONS`;
    ``gate`` None: the ungated form.  Compiles through Mosaic on a TPU,
    interpreted on the CPU."""
    M, D = rows.shape
    E, _, F = up.shape
    bm = block_rows
    bk, bf = _block(D, BLOCK_K), _block(F, BLOCK_F)
    nk, nf = D // bk, F // bf
    tiles = -(-M // bm)
    if tiles * bm != M:
        rows = jnp.pad(rows, ((0, tiles * bm - M), (0, 0)))

    starts, used = group_starts(group_sizes.astype(jnp.int32), bm)
    n_real = jnp.minimum(used // bm, tiles).astype(jnp.int32)
    # The expert of each block: the last group that starts at or before it
    # among those that have rows (an empty group starts where the next one
    # does).  Past the last real block: that block's, so nothing moves.
    at = jnp.minimum(jnp.arange(tiles), jnp.maximum(n_real - 1, 0)) * bm
    owns = (starts[None, :] <= at[:, None]) & (group_sizes[None, :] > 0)
    tile_expert = jnp.max(
        jnp.where(owns, jnp.arange(E)[None, :], 0), axis=1).astype(jnp.int32)

    def tile(t, n):
        return jnp.minimum(t, jnp.maximum(n[0] - 1, 0))

    def k_step(t, s, n):
        return jnp.where(t < n[0], jnp.minimum(s, nk - 1), nk - 1)

    def f_step(t, s, n):
        return jnp.where(t < n[0], jnp.maximum(s - nk, 0), nf - 1)

    w_in = pl.BlockSpec(
        (None, bk, F), lambda t, s, te, n: (te[tile(t, n)], k_step(t, s, n), 0))
    gated = gate is not None
    acc = pltpu.VMEM((bm, F), jnp.float32)
    out = pl.pallas_call(
        functools.partial(_kernel if gated else _kernel_ungated, nk=nk, nf=nf,
                          act=ACTIVATIONS[activation]),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(tiles, nk + nf),
            in_specs=[
                pl.BlockSpec((bm, bk), lambda t, s, te, n: (tile(t, n), k_step(t, s, n))),
                *([w_in, w_in] if gated else [w_in]),
                pl.BlockSpec(
                    (None, bf, D),
                    lambda t, s, te, n: (te[tile(t, n)], f_step(t, s, n), 0)),
            ],
            out_specs=pl.BlockSpec((bm, D), lambda t, s, te, n: (tile(t, n), 0)),
            scratch_shapes=[
                *([acc, acc] if gated else [acc]),
                pltpu.VMEM((nf, bm, bf), rows.dtype),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((tiles * bm, D), jnp.float32),
        # In order: a block that is skipped counts on what the block before
        # it left in VMEM.
        compiler_params=compiler_params(("arbitrary", "arbitrary"), VMEM_LIMIT),
        interpret=interpret_mode(),
        name=KERNEL_NAME,
    )(tile_expert, n_real.reshape(1), rows, *([gate, up] if gated else [up]), down)
    return out[:M]


def grouped_ffn_reference(rows, group_sizes, gate, up, down, *, block_rows: int = 128,
                          activation: str = "silu"):
    """The same rows through a loop over the experts in plain ``jax.numpy``
    (every expert applied to every row, masked): what the kernel is tested
    against.  Rows in no group come out 0."""
    starts, _ = group_starts(group_sizes.astype(jnp.int32), block_rows)
    r = jnp.arange(rows.shape[0])
    out = jnp.zeros(rows.shape, jnp.float32)
    act = ACTIVATIONS[activation]
    for e in range(up.shape[0]):
        u = jnp.dot(rows, up[e], preferred_element_type=jnp.float32)
        if gate is None:
            h = act(u).astype(rows.dtype)
        else:
            g = jnp.dot(rows, gate[e], preferred_element_type=jnp.float32)
            h = (act(g) * u).astype(rows.dtype)
        y = jnp.dot(h, down[e], preferred_element_type=jnp.float32)
        mine = (r >= starts[e]) & (r < starts[e] + group_sizes[e])
        out = jnp.where(mine[:, None], y, out)
    return out
