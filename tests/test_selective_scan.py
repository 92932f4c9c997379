"""The selective-scan kernel (ops/selective_scan.py): interpreted on the CPU
against the same recurrence as a ``lax.scan``, and compiled - not run - for
a described v5e chip at the widths AI21-Jamba2-3B gives it.

Tolerances: the state is computed in the same order of operations by both
(``exp(dt A) * h + u B``), so it agrees to the last bit or two; ``y`` sums
the ``N`` state rows in another order, so it may differ by float32 rounding
of a sum of ``N`` terms of the state's size.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_examples_tpu.ops import selective_scan as ss


def _inputs(C, D, N, seed=0, h0=True):
    k = jax.random.split(jax.random.key(seed), 7)
    x = jax.random.normal(k[0], (C, D))
    dt = jax.nn.softplus(jax.random.normal(k[1], (C, D)) - 2.0)
    a = -jnp.exp(0.5 * jax.random.normal(k[2], (N, D)))
    b, c = jax.random.normal(k[3], (C, N)), jax.random.normal(k[4], (C, N))
    d = jax.random.normal(k[5], (D,))
    h = jax.random.normal(k[6], (N, D)) if h0 else jnp.zeros((N, D))
    return x, dt, a, b, c, d, h


@pytest.mark.parametrize(
    "C,D,N,n_valid,h0",
    [
        (24, 200, 4, 24, False),     # channels far from a block of 1024
        (24, 200, 4, 7, True),       # carried state, padding after 7 tokens
        (300, 1100, 16, 290, True),  # three time blocks, two channel blocks
        (16, 1024, 16, 0, True),     # nothing valid: the state comes back
        (128, 2048, 16, 128, True),  # whole blocks, nothing padded
    ],
)
def test_kernel_against_a_scan(C, D, N, n_valid, h0):
    args = _inputs(C, D, N, seed=C + D, h0=h0)
    y, h = ss.selective_scan(*args, n_valid)
    y_ref, h_ref = ss.selective_scan_reference(*args, n_valid)
    assert y.shape == (C, D) and h.shape == (N, D)
    np.testing.assert_allclose(h, h_ref, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(y[:n_valid], y_ref[:n_valid], rtol=1e-5, atol=1e-5)
    if n_valid == 0:
        np.testing.assert_array_equal(h, args[-1])


def test_padding_does_not_advance_the_state():
    """The state after a chunk padded beyond ``n_valid`` is the state after
    a chunk that ends there, and two chunks chained are one."""
    x, dt, a, b, c, d, h0 = _inputs(40, 300, 8, seed=3)
    _, whole = ss.selective_scan(x, dt, a, b, c, d, h0, 40)
    _, padded = ss.selective_scan(x, dt, a, b, c, d, h0, 25)
    _, cut = ss.selective_scan(x[:25], dt[:25], a, b[:25], c[:25], d, h0, 25)
    np.testing.assert_array_equal(padded, cut)
    y2, chained = ss.selective_scan(x[25:], dt[25:], a, b[25:], c[25:], d, padded, 15)
    np.testing.assert_allclose(chained, whole, rtol=1e-6, atol=1e-6)
    y, _ = ss.selective_scan(x, dt, a, b, c, d, h0, 40)
    np.testing.assert_allclose(y2, y[25:], rtol=1e-5, atol=1e-5)


# -- compiled for the chip, not run -------------------------------------------


@pytest.fixture(scope="module")
def one_chip():
    """A described v5e chip: the TPU's compiler without a TPU.  Only this
    file of the suite loads the TPU's library (one process at a time may)."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps the library away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


#: The widths each chunk kernel compiles at: those the serve engine
#: dispatches a chunk at (PREFILL_CHUNK and its halvings down to
#: PREFILL_FLOOR), and one halving further, for the day the floor moves.
CHUNK_WIDTHS = (128, 256, 512)


def test_the_chunk_widths_here_hold_the_serve_engines():
    from distributed_tensorflow_examples_tpu.serve import model_server

    assert model_server.chunk_widths(model_server.PREFILL_CHUNK) == CHUNK_WIDTHS[1:]


@pytest.mark.parametrize("C", CHUNK_WIDTHS)
def test_kernel_compiles_for_a_v5e_at_the_served_widths(one_chip, monkeypatch, C):
    """Mosaic takes the kernel at ``C`` 256 / 512 (the engine's chunk widths)
    and 128, ``d_inner`` 5120, ``N`` 16 (what interpret mode cannot show:
    tiling, VMEM and SMEM), and the operation carries the kernel's name,
    which the benchmark's readers look for."""
    monkeypatch.setattr(ss, "interpret_mode", lambda: False)
    D, N = 5120, 16
    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    n_valid = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = jax.jit(ss.selective_scan.__wrapped__).lower(
        s(C, D), s(C, D), s(N, D), s(C, N), s(C, N), s(D), s(N, D), n_valid
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and f"%{ss.KERNEL_NAME}" in text


@pytest.mark.parametrize("D,F,E,rows,block", [
    (6144, 2048, 16, 896, 32), (6144, 2048, 16, 8192, 128),
    (2048, 1024, 128, 4352, 32), (2048, 1024, 128, 20480, 128),
    # The narrower chunks' row buffers (128 and 256 tokens' choices in blocks
    # of 128, a block more for each group held).
    (6144, 2048, 16, 3584, 128), (6144, 2048, 16, 5120, 128),
    (2048, 1024, 128, 17408, 128), (2048, 1024, 128, 18432, 128)])
def test_grouped_ffn_compiles_for_a_v5e_at_the_served_widths(
    one_chip, monkeypatch, D, F, E, rows, block,
):
    """Mosaic takes ops/grouped_ffn.py at ``D`` 6144, ``F`` 2048, 16 experts
    held (LongCat), with the row buffers of a decode step (32 slots x 12
    choices, in blocks of 32) and of a prefill chunk (512 x 12, in blocks of
    128), and at ``D`` 2048, ``F`` 1024 with ALL 128 experts of a layer held
    (Trinity-Mini, models/afmoe.py: 32 x 8 and 512 x 8 choices, a block more
    for each of 128 groups): its VMEM, the scalar-prefetched index maps and
    the kernel's name, which the benchmark's readers look for.  (Kept in
    this file: the one that loads the TPU's library.)"""
    _compile_grouped_ffn(one_chip, monkeypatch, D, F, E, rows, block)


def _compile_grouped_ffn(one_chip, monkeypatch, D, F, E, rows, block, **kw):
    from distributed_tensorflow_examples_tpu.ops import grouped_ffn as gf

    monkeypatch.setattr(gf, "interpret_mode", lambda: False)
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    bf16 = jnp.bfloat16
    compiled = jax.jit(
        lambda r, n, g, u, d: gf.grouped_ffn.__wrapped__(r, n, g, u, d, block_rows=block, **kw)
    ).lower(
        s((rows, D), bf16), s((E,), jnp.int32), s((E, D, F), bf16),
        s((E, D, F), bf16), s((E, F, D), bf16),
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and f"%{gf.KERNEL_NAME}" in text


@pytest.mark.parametrize("rows,block", [(2240, 32), (9728, 128), (11264, 128)])
def test_grouped_ffn_compiles_for_a_v5e_with_relu_at_widths_its_blocks_do_not_divide(
    one_chip, monkeypatch, rows, block,
):
    """... and at ``D`` 2560, ``F`` 768 with ``relu`` and ALL 64 experts of a
    layer held (SmallThinker, models/smallthinker.py: 32 x 6 choices in blocks
    of 32, 256 x 6 and 512 x 6 in blocks of 128, a block more for each of 64
    groups): neither width is a multiple of its block, and Mosaic takes the
    640 and 384 that ``_block`` finds (lanes of 128, sublanes of 16)."""
    _compile_grouped_ffn(one_chip, monkeypatch, 2560, 768, 64, rows, block, activation="relu")


@pytest.mark.parametrize("rows,block", [(4800, 32), (22016, 128), (27648, 128)])
def test_the_ungated_grouped_ffn_compiles_for_a_v5e_at_nemotrons_widths(
    one_chip, monkeypatch, rows, block,
):
    """... and UNGATED (``gate=None``) with ``relu2`` at ``D`` 1024, ``F`` 2688
    and 128 experts held (Nemotron-H's LatentMoE, models/nemotron_h.py: 32 x
    22 choices in blocks of 32, 256 x 22 and 512 x 22 in blocks of 128, a
    block more for each of 128 groups): ``D`` one block, ``F`` seven of 384,
    an expert's whole ``up`` a block."""
    from distributed_tensorflow_examples_tpu.ops import grouped_ffn as gf

    monkeypatch.setattr(gf, "interpret_mode", lambda: False)
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    bf16 = jnp.bfloat16
    compiled = jax.jit(
        lambda r, n, u, d: gf.grouped_ffn.__wrapped__(
            r, n, None, u, d, block_rows=block, activation="relu2")
    ).lower(
        s((rows, 1024), bf16), s((128,), jnp.int32), s((128, 1024, 2688), bf16),
        s((128, 2688, 1024), bf16),
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and f"%{gf.KERNEL_NAME}" in text


@pytest.mark.parametrize("C", CHUNK_WIDTHS)
def test_the_chunked_recurrence_compiles_for_a_v5e_at_the_served_widths(
    one_chip, monkeypatch, C,
):
    """Mosaic takes ops/ssd.py's chunk at ``C`` 128 / 256 / 512 positions of
    128 heads x 64 channels in 8 groups with a state of 128 columns, blocks
    of 128 positions, bfloat16 operands (Nemotron-H): time on the lanes, the
    decays' rows and columns, the three products a head, and the kernel's
    name, which the benchmark's readers look for."""
    from distributed_tensorflow_examples_tpu.ops import ssd

    monkeypatch.setattr(ssd, "interpret_mode", lambda: False)
    H, P, G, N = 128, 64, 8, 128
    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    n_valid = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = jax.jit(lambda *a: ssd.ssd_chunk.__wrapped__(
        *a, chunk_size=128, dtype=jnp.bfloat16)).lower(
        s(C, H, P), s(C, H), s(H), s(C, G, N), s(C, G, N), s(H), s(H, P, N), n_valid
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and f"%{ssd.CHUNK_KERNEL_NAME}" in text


def test_the_state_step_compiles_for_a_v5e_in_place_in_the_donated_cache(
    one_chip, monkeypatch,
):
    """Mosaic takes ops/ssd.py's step at 32 slots of 128 x 64 x 128 float32
    (Nemotron-H: 4.19 MB a slot, 134 MB a layer) - a slot's whole state a
    block, the live slots' numbers prefetched - and WITH THE STATES DONATED
    the compiled program holds no copy of them: the kernel's output is the
    cache's own array, a slot that is not live is neither read nor written."""
    from distributed_tensorflow_examples_tpu.ops import ssd

    monkeypatch.setattr(ssd, "interpret_mode", lambda: False)
    S, H, P, G, N = 32, 128, 64, 8, 128
    s = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    compiled = jax.jit(ssd.state_step.__wrapped__, donate_argnums=0).lower(
        s((S, H, P, N)), s((S, H, P)), s((S, H)), s((H,)), s((S, G, N)), s((S, G, N)),
        s((S,), jnp.bool_), s((S,), jnp.bool_),
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and f"%{ssd.STEP_KERNEL_NAME}" in text
    assert not [line for line in text.splitlines()
                if " copy(" in line and f"f32[{S},{H},{P},{N}]" in line]
    assert compiled.memory_analysis().temp_size_in_bytes < S * H * P * N * 4 / 16


@pytest.mark.parametrize("kernel", ["decode", "prefill", "prefill_128", "prefill_256"])
@pytest.mark.parametrize("model,slots,heads,length", [("deepseek", 64, 128, 4096), ("longcat", 32, 64, 8192)])
def test_latent_attention_compiles_for_a_v5e_at_the_served_widths(
    one_chip, monkeypatch, kernel, model, slots, heads, length,
):
    """Mosaic takes ops/latent_decode.py and ops/latent_prefill.py at the two
    served shapes (DeepSeek-V2 and LongCat: latent 576 = 512 values + 64
    rotated, bfloat16, each model's own blocks; the chunk 512 queries, the
    256 of the engine's narrower chunk, and 128): the
    grids of a traced extent, the prefetched index arrays, the products over
    a latent that is no multiple of 128 lanes, the prefill kernel's VMEM
    allowance, and the kernels' names, which the benchmark's readers look
    for.  And both take the cache AS IT LIES: the compiler puts the positions
    of a ``[S, T, 576]`` array last, the kernels read blocks of ``[576,
    block]``, and the compiled program holds no copy of the cache (302 MB a
    call, were it otherwise) nor - the chunk, which writes its rows into the
    cache it was donated and then attends over them - of one slot's rows
    (4.7 and 9.4 MB): the transpose is a bitcast.  (Kept in this file: the
    one that loads the TPU's library.)"""
    from distributed_tensorflow_examples_tpu import models
    from distributed_tensorflow_examples_tpu.models import mla
    from distributed_tensorflow_examples_tpu.ops import latent_decode as ld
    from distributed_tensorflow_examples_tpu.ops import latent_prefill as lp

    monkeypatch.setattr(ld, "interpret_mode", lambda: False)
    monkeypatch.setattr(lp, "interpret_mode", lambda: False)
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    bf16, i32 = jnp.bfloat16, jnp.int32
    cache = s((slots, length, 576), bf16)
    if kernel == "decode":
        name = ld.KERNEL_NAME
        compiled = jax.jit(
            lambda q, c, n: ld.latent_decode_attention.__wrapped__(
                q, c, n, values=512, scale=0.1, block=getattr(models, model).DECODE_BLOCK)
        ).lower(s((slots, heads, 576), bf16), cache, s((slots,), i32)).compile()
    else:
        name = lp.KERNEL_NAME

        def chunk(q_nope, q_rope, kv_b, new, c, slot, offset, n_valid):
            c = mla.chunk_write(c, new, slot, offset, n_valid)
            return c, lp.latent_prefill_attention.__wrapped__(
                q_nope, q_rope, kv_b, c, slot, offset, nope=128, scale=0.1,
                block=getattr(models, model).PREFILL_BLOCK)

        C = int(kernel.partition("_")[2] or 512)
        assert C in CHUNK_WIDTHS
        compiled = jax.jit(chunk, donate_argnums=4).lower(
            s((C, heads, 128), bf16), s((C, heads, 64), bf16),
            s((512, heads * 256), bf16), s((C, 576), bf16), cache,
            s((), i32), s((), i32), s((), i32),
        ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and f"%{name}" in text
    # The step's temporaries are the work list and the absorbed query; the
    # chunk's nothing to speak of (a slot's rows are 4.7 MB and more).
    limit = slots * length * 576 * 2 / 16 if kernel == "decode" else 3 << 20
    assert compiled.memory_analysis().temp_size_in_bytes < limit


def _cache_sized_moves(text, elements):
    """The compiled program's ``copy`` and ``transpose`` instructions whose
    result has ``elements`` elements or more, whatever its shape: a layer's
    whole cache moved or laid out anew."""
    found = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = \(?\w+\[([\d,]+)\].*? (copy|transpose|copy-start)\(", line)
        if m and np.prod([int(d) for d in m.group(1).split(",")]) >= elements:
            found.append(line.strip()[:200])
    return found


@pytest.mark.parametrize("KV,G,rows,window", [
    (4, 7, 16384, None), (4, 7, 4608, 4096),  # SmallThinker: a global layer, a ring
    (4, 8, 16384, None), (4, 8, 2560, 2048),  # Trinity-Mini: the full layer, a ring
    (2, 16, 32768, None),  # Nemotron-3-Super: its attention layer
])
def test_slot_decode_attention_compiles_for_a_v5e_at_the_served_shapes(
    one_chip, monkeypatch, KV, G, rows, window,
):
    """Mosaic takes ops/slot_decode.py at the three served models' shapes (32
    slots and the spare, head size 128, bfloat16, blocks of 512 rows): the
    grid of a traced extent over the prefetched work list, a query of 7
    heads a group (padded to 8 sublanes: the query, not the cache), the
    products over a block read as it lies, and the kernel's name, which the
    benchmark's reader looks for.  The compiled program holds no copy and no
    transpose of either cache."""
    from distributed_tensorflow_examples_tpu.ops import slot_decode as sd

    monkeypatch.setattr(sd, "interpret_mode", lambda: False)
    S = 32
    s = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    compiled = jax.jit(
        lambda q, ck, cv, pos, live: sd.slot_decode_attention.__wrapped__(
            q, ck, cv, pos, live, window, block=512)
    ).lower(s((S, KV, G, 128)), s((S + 1, KV, rows, 128)), s((S + 1, KV, rows, 128)),
            s((S,), jnp.int32), s((S,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and f"%{sd.KERNEL_NAME}" in text
    assert not _cache_sized_moves(text, (S + 1) * KV * rows * 128)
    # The temporaries are the work list and the padded query and result.
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 20


def test_the_smallthinker_step_compiles_for_a_v5e_and_leaves_its_cache_where_it_lies(
    one_chip, monkeypatch,
):
    """The WHOLE step of the ``smallthinker-21b-serve-think`` cell (eight
    layers of the published widths, 32 slots x 16,384, the engine's selecting
    wrapper, the cache donated) compiled for the chip: eight calls of the
    attention kernel and no loop; NO ``copy`` and NO ``transpose`` of a
    layer's cache (a row read out of it at a traced ROW made the compiler lay
    the whole cache out position-major, a copy in and another out a step, PR
    39 - a block the kernel reads must not); every row write
    (``dynamic-update-slice`` of a ``[1, 4, 1, 128]`` row) still there, and
    the cache handed back in the arrays it came in (aliased whole), with
    temporaries a hundredth of it."""
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from benchmarks.harness import manifest
    from distributed_tensorflow_examples_tpu.models import ring_cache
    from distributed_tensorflow_examples_tpu.ops import grouped_ffn, slot_decode
    from distributed_tensorflow_examples_tpu.serve import model_server

    for module in (grouped_ffn, slot_decode, ring_cache):
        monkeypatch.setattr(module, "interpret_mode", lambda: False)
    cell = manifest.Cell("smallthinker-21b-serve-think")
    cfg, tree_fn = cell.family.build(cell.config)
    fns = cell.family.decode_fns(cfg)
    slots, max_len = cell.traffic["server"]["decode_slots"], cell.family.max_len(cell.config)
    described = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)
    row = lambda dt: jax.ShapeDtypeStruct((slots,), dt, sharding=one_chip)
    cache = described(jax.eval_shape(lambda: fns.init_cache(slots, max_len)))
    compiled = jax.jit(model_server._selecting(fns.step), donate_argnums=1).lower(
        described(jax.eval_shape(tree_fn, jax.random.key(0))), cache,
        row(jnp.int32), row(jnp.int32), row(jnp.bool_), row(jnp.int32), row(jnp.bool_),
    ).compile()
    text = compiled.as_text()
    assert " while(" not in text
    assert len([line for line in text.splitlines()
                if "custom-call(" in line and slot_decode.KERNEL_NAME in line]) == len(cfg.layers)
    assert not _cache_sized_moves(text, min(a.size for a in jax.tree.leaves(cache) if a.ndim == 4))
    assert text.count(" dynamic-update-slice(") == 2 * slots * len(cfg.layers)
    memory = compiled.memory_analysis()
    cache_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))
    assert memory.alias_size_in_bytes >= cache_bytes
    assert memory.temp_size_in_bytes < cache_bytes / 50
