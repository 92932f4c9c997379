"""ops/grouped_ffn.py, interpreted on the CPU, against a plain loop over the
experts (``grouped_ffn_reference``: every expert applied to every row under
a mask).  Both multiply the same operands and accumulate in float32, in
float32 operands here, so they agree to summation order: 1e-5 on results of
unit size.  (Mosaic takes the kernel at the served widths in
tests/test_selective_scan.py, the one file that loads the TPU's library.)
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_examples_tpu.ops import grouped_ffn as gf

TOL = 1e-5


def _inputs(M, D=64, F=32, E=4, dtype=jnp.float32):
    k = jax.random.split(jax.random.key(M), 4)
    return (
        jax.random.normal(k[0], (M, D)).astype(dtype),
        (jax.random.normal(k[1], (E, D, F)) / 8).astype(dtype),
        (jax.random.normal(k[2], (E, D, F)) / 8).astype(dtype),
        (jax.random.normal(k[3], (E, F, D)) / 6).astype(dtype),
    )


def _group_rows(sizes, block, M):
    starts, _ = gf.group_starts(jnp.asarray(sizes, jnp.int32), block)
    mask = np.zeros(M, bool)
    for s, n in zip(np.asarray(starts), sizes):
        mask[s:s + n] = True
    return mask


def test_group_starts_put_every_group_on_a_block_boundary():
    starts, used = gf.group_starts(jnp.asarray([3, 0, 17, 16, 0], jnp.int32), 16)
    assert starts.tolist() == [0, 16, 16, 48, 64] and int(used) == 64


@pytest.mark.parametrize(
    "sizes,M",
    [
        ([3, 0, 17, 5], 96),    # an empty group among groups of uneven size
        ([0, 0, 0, 0], 64),     # no row at all: nothing is read or computed
        ([0, 50, 0, 0], 64),    # one group has every row, four blocks of it
        ([0, 0, 0, 9], 64),     # only the last expert is touched
        ([3, 0, 17, 5], 101),   # M no multiple of the block
        ([16, 16, 16, 16], 64), # every block full: no block is skipped
    ],
)
def test_kernel_against_the_loop(sizes, M):
    rows, gate, up, down = _inputs(M)
    n = jnp.asarray(sizes, jnp.int32)
    got = np.asarray(gf.grouped_ffn(rows, n, gate, up, down, block_rows=16))
    want = np.asarray(gf.grouped_ffn_reference(rows, n, gate, up, down, block_rows=16))
    mine = _group_rows(sizes, 16, M)
    assert got.shape == (M, 64) and got.dtype == np.float32
    if mine.any():
        assert np.abs(want[mine]).max() > 0.1
        assert np.abs(got[mine] - want[mine]).max() < TOL


def test_kernel_walks_several_blocks_of_both_inner_dimensions(monkeypatch):
    """D and F longer than their blocks: the two phases of a row block run
    more than one step each, as they do at the served widths."""
    monkeypatch.setattr(gf, "BLOCK_K", 32)
    monkeypatch.setattr(gf, "BLOCK_F", 16)
    rows, gate, up, down = _inputs(80, D=128, F=48)
    n = jnp.asarray([30, 0, 2, 17], jnp.int32)
    fn = gf.grouped_ffn.__wrapped__  # the constants are read at trace time
    got = np.asarray(fn(rows, n, gate, up, down, block_rows=16))
    want = np.asarray(gf.grouped_ffn_reference(rows, n, gate, up, down, block_rows=16))
    mine = _group_rows([30, 0, 2, 17], 16, 80)
    assert np.abs(got[mine] - want[mine]).max() < TOL
    with pytest.raises(ValueError, match="no multiple of its block"):
        fn(rows[:, :100], n, gate[:, :100], up[:, :100], down[:, :, :100], block_rows=16)


def test_bfloat16_operands_give_the_gated_mlps_arithmetic():
    """In bfloat16 the kernel is ``layers.gated_mlp`` row for row: products
    of bfloat16 operands accumulated in float32, ``h`` rounded once."""
    from distributed_tensorflow_examples_tpu.models import layers

    rows, gate, up, down = _inputs(32, dtype=jnp.bfloat16)
    n = jnp.asarray([0, 20, 0, 0], jnp.int32)
    got = np.asarray(gf.grouped_ffn(rows, n, gate, up, down, block_rows=32))
    p = {"gate": {"kernel": gate[1]}, "up": {"kernel": up[1]}, "down": {"kernel": down[1]}}
    want = np.asarray(layers.gated_mlp(p, rows[:20], dtype=jnp.bfloat16))
    np.testing.assert_allclose(got[:20], want, atol=1e-6)


def test_relu_and_widths_the_old_blocks_refused_against_the_loop():
    """SmallThinker's experts, 2560 -> 768 -> 2560 with ``relu``: neither
    width is a multiple of its block, and the kernel takes the largest
    multiple of 128 under it that divides the width (four steps of 640, two
    of 384); a width the blocks divide keeps them.  A few rows, two experts
    of three touched."""
    D, F = 2560, 768
    assert (gf._block(D, gf.BLOCK_K), gf._block(F, gf.BLOCK_F)) == (640, 384)
    assert (gf._block(6144, gf.BLOCK_K), gf._block(2048, gf.BLOCK_F)) == (1024, 512)
    rows, gate, up, down = _inputs(40, D=D, F=F, E=3)
    gate, up, down = gate / 6, up / 6, down / 4
    n = jnp.asarray([5, 0, 20], jnp.int32)
    got = np.asarray(gf.grouped_ffn(rows, n, gate, up, down, block_rows=16, activation="relu"))
    want = np.asarray(gf.grouped_ffn_reference(
        rows, n, gate, up, down, block_rows=16, activation="relu"))
    mine = _group_rows([5, 0, 20], 16, 40)
    assert np.abs(want[mine]).max() > 0.1
    assert np.abs(got[mine] - want[mine]).max() < 20 * TOL  # sums of 2560 and 768 terms
    silu = np.asarray(gf.grouped_ffn_reference(rows, n, gate, up, down, block_rows=16))
    assert np.abs(silu[mine] - want[mine]).max() > 0.01


def test_silu_is_what_the_kernel_computed_before_it_took_an_activation():
    """The default is the old kernel's program to the letter: ``silu`` named
    or not lowers to the same text, and ``relu`` to another."""
    rows, gate, up, down = _inputs(48)
    n = jnp.asarray([3, 0, 17, 5], jnp.int32)
    text = lambda **kw: gf.grouped_ffn.lower(
        rows, n, gate, up, down, block_rows=16, **kw).as_text()
    assert text() == text(activation="silu") != text(activation="relu")
    assert np.array_equal(
        np.asarray(gf.grouped_ffn(rows, n, gate, up, down, block_rows=16)),
        np.asarray(gf.grouped_ffn(rows, n, gate, up, down, block_rows=16, activation="silu")))
    with pytest.raises(KeyError):
        gf.grouped_ffn(rows, n, gate, up, down, block_rows=16, activation="gelu")


@pytest.mark.parametrize("sizes,M,D,F", [
    ([3, 0, 17, 5], 96, 64, 32),    # an empty group among groups of uneven size
    ([0, 0, 0, 0], 64, 64, 32),     # no row at all
    ([0, 50, 0, 0], 64, 64, 32),    # one group has every row, four blocks of it
    ([5, 0, 20, 0], 40, 1024, 2688),  # Nemotron's latent and expert widths
])
def test_the_ungated_relu2_kernel_against_the_loop(sizes, M, D, F):
    """``gate=None``: ``down . relu(up . x)^2``, one accumulation and no
    second matrix - at a tiny width and at 1024 -> 2688 -> 1024, where ``D``
    is one block and ``F`` seven of 384 - against the loop's ungated form;
    and it is neither the gated kernel with ``up`` for a gate nor ``relu``."""
    if D == 1024:
        assert (gf._block(D, gf.BLOCK_K), gf._block(F, gf.BLOCK_F)) == (1024, 384)
    rows, gate, up, down = _inputs(M, D=D, F=F)
    scale = (64 / D) ** 0.5
    up, down = up * scale, down * (32 / F) ** 0.5
    n = jnp.asarray(sizes, jnp.int32)
    got = np.asarray(gf.grouped_ffn(
        rows, n, None, up, down, block_rows=16, activation="relu2"))
    want = np.asarray(gf.grouped_ffn_reference(
        rows, n, None, up, down, block_rows=16, activation="relu2"))
    mine = _group_rows(sizes, 16, M)
    assert got.shape == (M, D) and got.dtype == np.float32
    if not mine.any():
        return
    assert np.abs(want[mine]).max() > 0.1
    assert np.abs(got[mine] - want[mine]).max() < 20 * TOL
    by_hand = np.square(np.maximum(np.asarray(rows)[mine] @ np.asarray(up)[
        np.flatnonzero(sizes)[0]], 0)) @ np.asarray(down)[np.flatnonzero(sizes)[0]]
    first = slice(0, sizes[np.flatnonzero(sizes)[0]])
    assert np.abs(got[mine][first] - by_hand[first]).max() < 20 * TOL
    for other in (dict(gate=up * scale, activation="relu2"), dict(gate=None, activation="relu")):
        wrong = np.asarray(gf.grouped_ffn_reference(
            rows, n, other["gate"], up, down, block_rows=16, activation=other["activation"]))
        assert np.abs(wrong[mine] - want[mine]).max() > 0.01


def test_the_form_is_chosen_by_the_gate_alone():
    """The gated call takes two ``[E, D, F]`` operands and the ungated one,
    which lowers to another text: no switch but ``gate`` itself."""
    rows, gate, up, down = _inputs(48)
    n = jnp.asarray([3, 0, 17, 5], jnp.int32)
    head = lambda *a, **kw: gf.grouped_ffn.lower(*a, block_rows=16, **kw).as_text().split(
        "\n")[1]
    gated, ungated = head(rows, n, gate, up, down), head(rows, n, None, up, down)
    assert (gated.count("tensor<4x64x32xf32>"), ungated.count("tensor<4x64x32xf32>")) == (2, 1)
