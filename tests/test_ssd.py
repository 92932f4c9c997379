"""ops/ssd.py - Mamba-2's recurrence as the chunk's three products a block
and as the step's in-place update - interpreted on the CPU against the
recurrence itself, one position at a time (``ssd_chunk_reference``, a
``lax.scan``; ``state_step_reference``).

Tolerances: with float32 operands both sides multiply the same numbers and
differ by the order of their sums and by ``exp`` of a SUM of steps where the
recurrence multiplies ``exp`` of each - float32 rounding of sums of some
hundred terms of unit size: 2e-5.  With bfloat16 operands (the served type)
every product rounds its operands to 8 bits: 3e-2 on outputs of size 10,
which a float32 kernel held to 2e-5 shows is the operands' rounding and
nothing else.  (Mosaic takes both kernels at the served widths in
tests/test_selective_scan.py, the one file that loads the TPU's library.)
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_examples_tpu.ops import ssd

TOL = 2e-5


def _inputs(T, H, P, G, N, seed=0, s0=True):
    k = jax.random.split(jax.random.key(seed), 7)
    x = jax.random.normal(k[0], (T, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[1], (T, H)) - 2.0)
    a = -jnp.exp(0.5 * jax.random.normal(k[2], (H,)))
    b, c = jax.random.normal(k[3], (T, G, N)), jax.random.normal(k[4], (T, G, N))
    d = jax.random.normal(k[5], (H,))
    s = jax.random.normal(k[6], (H, P, N)) if s0 else jnp.zeros((H, P, N))
    return x, dt, a, b, c, d, s


@pytest.mark.parametrize(
    "T,H,P,G,N,n_valid,block,s0",
    [
        (40, 8, 16, 2, 16, 40, 16, False),  # three blocks, the last padded
        (40, 8, 16, 2, 16, 25, 16, True),   # a carried state, padding after 25 tokens
        (64, 4, 8, 4, 8, 64, 16, True),     # whole blocks, a head a group
        (16, 4, 8, 1, 8, 0, 16, True),      # nothing valid: the state comes back
        (30, 6, 8, 3, 24, 30, 32, True),    # one block longer than the chunk
    ],
)
def test_the_chunk_against_the_recurrence(T, H, P, G, N, n_valid, block, s0):
    args = _inputs(T, H, P, G, N, seed=T + H, s0=s0)
    y, s = ssd.ssd_chunk(*args, n_valid, chunk_size=block)
    y_ref, s_ref = ssd.ssd_chunk_reference(*args, n_valid)
    assert y.shape == (T, H, P) and s.shape == (H, P, N)
    assert np.abs(np.asarray(y_ref)).max() > 1.0
    np.testing.assert_allclose(s, s_ref, rtol=TOL, atol=TOL)
    np.testing.assert_allclose(y[:n_valid], y_ref[:n_valid], rtol=TOL, atol=TOL)
    if n_valid == 0:
        np.testing.assert_array_equal(s, args[-1])


def test_padding_does_not_advance_the_state_and_chunks_chain():
    """The state after a chunk padded beyond ``n_valid`` is the state after
    a chunk that ends there, and two chunks chained are one."""
    x, dt, a, b, c, d, s0 = _inputs(40, 8, 16, 2, 16, seed=3)
    run = lambda lo, hi, s, n: ssd.ssd_chunk(
        x[lo:hi], dt[lo:hi], a, b[lo:hi], c[lo:hi], d, s, n, chunk_size=16)
    y, whole = run(0, 40, s0, 40)
    _, padded = run(0, 40, s0, 25)
    _, cut = run(0, 25, s0, 25)
    np.testing.assert_allclose(padded, cut, rtol=TOL, atol=TOL)
    y2, chained = run(25, 40, padded, 15)
    np.testing.assert_allclose(chained, whole, rtol=TOL, atol=TOL)
    np.testing.assert_allclose(y2, y[25:], rtol=TOL, atol=TOL)


def test_bfloat16_operands_round_the_products_and_nothing_else():
    """The served type: the three products' operands in bfloat16, decays,
    ``dt`` and the carried state float32 - within the operands' rounding of
    the recurrence, and not within the float32 kernel's tolerance."""
    args = _inputs(48, 8, 16, 2, 16, seed=9)
    y, s = ssd.ssd_chunk(*args, 48, chunk_size=16, dtype=jnp.bfloat16)
    y_ref, s_ref = ssd.ssd_chunk_reference(*args, 48)
    worst = np.abs(np.asarray(y) - np.asarray(y_ref)).max()
    assert 10 * TOL < worst < 0.15 and np.abs(np.asarray(y_ref)).max() > 5
    np.testing.assert_allclose(s, s_ref, rtol=0.05, atol=0.05)


def _step_inputs(S, H, P, G, N, seed=1):
    k = jax.random.split(jax.random.key(seed), 6)
    return (
        jax.random.normal(k[0], (S, H, P, N)), jax.random.normal(k[1], (S, H, P)),
        jax.nn.softplus(jax.random.normal(k[2], (S, H)) - 2.0),
        -jnp.exp(0.5 * jax.random.normal(k[3], (H,))),
        jax.random.normal(k[4], (S, G, N)), jax.random.normal(k[5], (S, G, N)),
    )


@pytest.mark.parametrize("live", [
    [True, False, True, True, False],     # live slots among slots that are not
    [False, False, False, False, False],  # no slot is live: everything comes back
    [True, True, True, True, True],
    [False, False, False, False, True],   # only the last slot
])
def test_the_step_advances_the_live_slots_and_leaves_the_others_bit_equal(live):
    state, x, dt, a, b, c = _step_inputs(5, 8, 16, 2, 16)
    live = jnp.asarray(live)
    fresh = jnp.asarray([False, False, True, False, True])
    y, new = ssd.state_step(state, x, dt, a, b, c, live, fresh)
    y_ref, new_ref = ssd.state_step_reference(state, x, dt, a, b, c, live, fresh)
    np.testing.assert_allclose(new, new_ref, rtol=TOL, atol=TOL)
    np.testing.assert_allclose(y, y_ref, rtol=TOL, atol=TOL)
    dead = ~np.asarray(live)
    np.testing.assert_array_equal(np.asarray(new)[dead], np.asarray(state)[dead])
    assert not np.asarray(y)[dead].any()


def test_the_step_is_one_position_of_the_chunk():
    """A slot's step from a state is the chunk's first position from it:
    ``y`` (with ``D x`` added, as the model adds it) and the state after;
    a fresh slot's is the chunk's from the zero state."""
    x, dt, a, b, c, d, s0 = _inputs(1, 8, 16, 2, 16, seed=5)
    for fresh in (False, True):
        start = jnp.zeros_like(s0) if fresh else s0
        y_ref, s_ref = ssd.ssd_chunk_reference(x, dt, a, b, c, d, start, 1)
        y, s = ssd.state_step(
            s0[None], x, dt, a, b, c, jnp.asarray([True]), jnp.asarray([fresh]))
        np.testing.assert_allclose(s[0], s_ref, rtol=TOL, atol=TOL)
        np.testing.assert_allclose(y[0] + d[:, None] * x[0], y_ref[0], rtol=TOL, atol=TOL)
