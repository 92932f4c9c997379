"""models/smallthinker.py against the plain float32 reference
(benchmarks/reference/smallthinker_ref.py) on seeded weights, at a tiny
width: THE FIRST TWO PERIODS of a 12-layer model - published layers 0-7
(global, window x 3, global, window x 3: the global layer FIRST) - with 14
query heads on 2 K/V heads (7 a K/V head), a window of 16 positions, rings of
16 + 8 rows, 16 experts of which a token takes 4, and sequences of 72
positions, four windows and three rings long.

The tolerance and its reason.  Both sides compute with the same
bfloat16-rounded leaves; the program holds them as float32 and multiplies in
float32, as the reference does, so nothing but the order of the sums differs
and a routing flip is no excuse: the logits (largest about 4, std 1) agree to
``TOL`` = 2e-3, hundreds of times what is read (1e-5) and hundreds of times
under what the reference with fp8 products reads.  The chunk-then-step tests
hold the same tolerance against the same full forward, though their
attention reads a RING by position arithmetic where the reference masks,
rotates interleaved pairs where the reference rotates halves, and routes by
a plan made before attention where the reference's router is one product.
"""

import dataclasses
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.reference import smallthinker_ref, weights  # noqa: E402
from distributed_tensorflow_examples_tpu.models import ring_cache, smallthinker  # noqa: E402
from distributed_tensorflow_examples_tpu.ops import moe as moe_ops  # noqa: E402

WINDOW, SLACK, BLOCK = 16, 8, 8
LAYOUT = (0, 1, 1, 1) * 3
C_TINY = dict(
    vocab_size=300, hidden_size=64, moe_ffn_hidden_size=32, num_hidden_layers=12,
    num_attention_heads=14, num_key_value_heads=2, head_dim=16,
    sliding_window_layout=LAYOUT, rope_layout=LAYOUT, sliding_window_size=WINDOW,
    moe_num_primary_experts=16, moe_num_active_primary_experts=4,
    rms_norm_eps=1e-6, rope_theta=100.0, held_layers=tuple(range(8)),
    init_std=0.125, router_spread=2.0, out_std_factor=0.05,
)
SEEDED = ("init_std", "router_spread", "out_std_factor")
CFG = smallthinker.Config(
    **{k: v for k, v in C_TINY.items() if k not in SEEDED},
    param_dtype="float32", ring_slack=SLACK, attn_block=BLOCK)
TOL = 2e-3
SEED = 2**31 + 7  # beyond 31 bits, as the driver's seeds are
L = 72


@pytest.fixture(scope="module")
def params():
    tree = jax.jit(lambda k: smallthinker_ref.tree(C_TINY, k))(weights.base_key(SEED))
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


@pytest.fixture(scope="module")
def tokens():
    return np.asarray(jax.random.randint(jax.random.key(4), (2, L), 0, 300))


@pytest.fixture(scope="module")
def reference(tokens):
    return smallthinker_ref.logits(C_TINY, SEED, tokens)


@pytest.fixture(scope="module")
def programs():
    """The chunk and the step of ``CFG``, compiled once a shape."""
    return (
        jax.jit(lambda p, c, t, s, o, n: smallthinker.prefill_chunk(CFG, p, c, t, s, o, n)),
        jax.jit(lambda p, c, t, pos, live: smallthinker.decode_step_batch(
            CFG, p, c, t, pos, live)),
    )


def _prefill(chunk, params, cache, prompt, slot, width):
    """All but the prompt's last token through chunks of ``width`` (the last
    one padded), or through the chunks ``[(valid, width), ...]`` given."""
    n = len(prompt) - 1
    plan = width if isinstance(width, list) else [
        (min(width, n - offset), width) for offset in range(0, n, width)]
    assert sum(valid for valid, _w in plan) == n
    offset = 0
    for valid, w in plan:
        buf = np.zeros(w, np.int32)
        buf[:valid] = prompt[offset:offset + valid]
        cache = chunk(params, cache, buf, slot, offset, valid)
        offset += valid
    return cache


def test_the_defaults_are_the_published_model_and_the_stage_is_named_by_layer(params):
    full = smallthinker.Config()
    assert (full.num_hidden_layers, full.hidden_size, full.moe_num_primary_experts) == (
        52, 2560, 64)
    assert full.layers == tuple(range(52)) and sum(full.sliding_window_layout) == 39
    assert [full.window(i) for i in (0, 1, 3, 4)] == [None, 4096, 4096, None]
    share = full.share
    assert (share.scoring, share.normalise, share.activation, share.first, share.held,
            share.top_k, share.scale) == ("softmax", True, "relu", 0, 64, 6, 1.0)
    stage = dataclasses.replace(full, held_layers=tuple(range(8)))
    # 32 slots x (2 layers of 16384 rows + 6 rings of 4608) x 2 KB: 4.05 GB,
    # and a spare slot beside them for the rows that are not live.
    cache = jax.eval_shape(lambda: smallthinker.init_cache(stage, 32, 16384))
    assert cache["layer_0"]["k"].shape == (33, 4, 16384, 128)
    assert cache["layer_5"]["v"].shape == (33, 4, 4608, 128)
    held = sum(a.size * a.dtype.itemsize for k, v in cache.items()
               if k != "counters" for a in v.values())
    assert held == pytest.approx(4.08e9, rel=0.01)
    for bad in (dict(held_layers=(4, 0)), dict(held_layers=(0, 52)),
                dict(rope_layout=(0, 1)), dict(num_key_value_heads=5)):
        with pytest.raises(ValueError):
            dataclasses.replace(full, **bad)
    # The seeded tree names a layer by its published index.
    assert sorted(k for k in params if k.startswith("layer_")) == [
        f"layer_{i}" for i in range(8)]


def test_apply_is_the_reference_in_float32(params, tokens, reference):
    out = np.asarray(jax.jit(lambda p, t: smallthinker.apply(CFG, p, t))(params, tokens))
    assert np.abs(reference).max() > 2.5
    assert np.abs(out - reference).max() < TOL
    # The window matters at these lengths: the same program with a window as
    # long as the sequence is the reference up to the window and not after.
    wide = dataclasses.replace(CFG, sliding_window_size=L)
    out = np.asarray(jax.jit(lambda p, t: smallthinker.apply(wide, p, t))(params, tokens))
    assert np.abs(out[:, :WINDOW] - reference[:, :WINDOW]).max() < TOL
    assert np.abs(out[:, WINDOW:] - reference[:, WINDOW:]).max() > 0.5


def _router_reads_the_experts_input(mp):
    """The layer as every other expert model has it: the plan made from
    ``N_post(x1)``, what the experts read, after attention."""

    def layer(cfg, p, x, live, attn, counters, *, experts=True, chunk_counts=()):
        x = x + attn(p["attn"], smallthinker._norm(cfg, p["norm_in"], x))
        if not experts:
            return x, counters
        m, counters = moe_ops.apply_share_counted(
            p["moe"], smallthinker._norm(cfg, p["norm_post"], x), cfg.share, live,
            counters, chunk_counts=chunk_counts, dtype=cfg.dtype)
        return x + m, counters

    mp.setattr(smallthinker, "_layer", layer)
    return CFG


def _silu_for_relu(mp):
    share = smallthinker.Config.share.fget
    mp.setattr(smallthinker.Config, "share", property(
        lambda self: dataclasses.replace(share(self), activation="silu")))
    return CFG


#: Each plants its fault and returns the ``Config`` to run.
FAULTS = {
    "the router reads the experts' input": _router_reads_the_experts_input,
    "silu for relu": _silu_for_relu,
    "rotary in a global layer": lambda mp: dataclasses.replace(
        CFG, rope_layout=(1,) * len(LAYOUT)),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_part_of_the_block_planted_wrong_is_seen_at_the_tolerance(
        monkeypatch, fault, params, tokens, reference):
    """Each recalled piece of the block is in the comparison: the program
    with it planted wrong misses the reference by tens of tolerances."""
    cfg = FAULTS[fault](monkeypatch)
    out = np.asarray(jax.jit(lambda p, t: smallthinker.apply(cfg, p, t))(params, tokens))
    assert np.abs(out - reference).max() > 20 * TOL


@pytest.mark.parametrize("prompt_len,width", [
    (9, 8), (WINDOW, 8), (WINDOW + 1, 5), (37, "widths"), (59, 5)])
def test_chunks_then_steps_through_the_ring_are_the_full_forward(
        programs, params, tokens, reference, engine_chunks, prompt_len, width):
    """Prompts SHORTER than the window (9), EQUAL to it (16, 17: the first
    step is the first query that loses a position) and several times LONGER
    (37, 59: past the ring's 24 rows once and twice), by chunks of 8 (a
    boundary ON the ring's end), of 5 (a chunk ACROSS it) and as the engine
    cuts them (the last chunk 4 wide) - then steps to position 71, with a
    second slot stepping at another depth in the same launches and a third
    that is not live."""
    chunk, step = programs
    cache = smallthinker.init_cache(CFG, 3, L + 8)
    assert cache["layer_0"]["k"].shape == (3 + 1, 2, L + 8, 16)
    assert cache["layer_1"]["v"].shape == (3 + 1, 2, WINDOW + SLACK, 16)
    other_len = 5
    if width == "widths":
        width = [(n, w) for _o, n, w in engine_chunks(prompt_len - 1, 8, 2)]
        assert min(w for _n, w in width) < 8
    cache = _prefill(chunk, params, cache, tokens[0, :prompt_len], 2, width)
    cache = _prefill(chunk, params, cache, tokens[1, :other_len], 0,
                     8 if isinstance(width, list) else width)
    pos = np.array([other_len - 1, 0, prompt_len - 1], np.int32)
    live = np.array([True, False, True])
    worst = 0.0
    while pos[2] < L:
        tok = np.array([tokens[1, pos[0]], 7, tokens[0, pos[2]]], np.int32)
        logits, cache = step(params, cache, tok, pos, live)
        logits = np.asarray(logits)
        worst = max(worst, np.abs(logits[2] - reference[0, pos[2]]).max(),
                    np.abs(logits[0] - reference[1, pos[0]]).max())
        pos = pos + np.array([1, 0, 1], np.int32)
    assert worst < TOL


@pytest.mark.slow
def test_chunks_then_steps_at_the_cells_cache_geometry_across_the_wrap():
    """The cell's REAL cache geometry - a window of 4,096, rings of 4,608
    rows, blocks of 512, 16,384 rows a global layer, chunks as the engine
    cuts them (512 and 256) - at tiny widths in float32: a session
    prefilled to 4,500 and stepped to 6,144, across the ring's wrap at
    4,608, beside one at 700-2,343 in the same launches, is the float32
    reference at every position.  A minute on the CPU: not in tier-1.  It is
    the witness PR 42's review asked for, that the served path's gap
    against the reference on the chip is no stale ring row."""
    from distributed_tensorflow_examples_tpu.serve import model_server

    layout = (0, 1, 1, 1)
    c = dict(C_TINY, num_hidden_layers=4, sliding_window_layout=layout,
             rope_layout=layout, sliding_window_size=4096, rope_theta=1.5e6,
             held_layers=(0, 1, 2))
    cfg = smallthinker.Config(
        **{k: v for k, v in c.items() if k not in SEEDED}, param_dtype="float32",
        ring_slack=512, attn_block=512)
    n, prompt_len, other_len = 6144, 4500, 700
    params = jax.tree.map(
        lambda a: a.astype(jnp.float32),
        jax.jit(lambda k: smallthinker_ref.tree(c, k))(weights.base_key(SEED)))
    toks = np.asarray(jax.random.randint(jax.random.key(5), (2, n), 0, 300))
    ref = smallthinker_ref.logits(c, SEED, toks)
    chunk = jax.jit(lambda p, ca, t, s, o, m: smallthinker.prefill_chunk(cfg, p, ca, t, s, o, m))
    step = jax.jit(lambda p, ca, t, pos, live: smallthinker.decode_step_batch(
        cfg, p, ca, t, pos, live))
    widths = model_server.chunk_widths(model_server.PREFILL_CHUNK)
    cache = smallthinker.init_cache(cfg, 3, 16384)
    assert cache["layer_1"]["k"].shape == (4, 2, 4608, 16)
    for prompt, slot in ((toks[0, :prompt_len], 2), (toks[1, :other_len], 0)):
        plan, left = [], len(prompt) - 1
        while left:
            valid = min(max(widths), left)
            plan.append((valid, min(w for w in widths if w >= valid)))
            left -= valid
        cache = _prefill(chunk, params, cache, prompt, slot, plan)
    pos = np.array([other_len - 1, 0, prompt_len - 1], np.int32)
    live = np.array([True, False, True])
    worst = 0.0
    while pos[2] < n:
        tok = np.array([toks[1, pos[0]], 7, toks[0, pos[2]]], np.int32)
        logits, cache = step(params, cache, tok, pos, live)
        logits = np.asarray(logits)
        worst = max(worst, np.abs(logits[2] - ref[0, pos[2]]).max(),
                    np.abs(logits[0] - ref[1, pos[0]]).max())
        pos = pos + np.array([1, 0, 1], np.int32)
    assert worst < TOL


@pytest.mark.parametrize("form", ["loop", "kernel"])
def test_what_the_step_counts_by_kind_of_layer_and_where_the_window_binds(
        programs, params, tokens, form, monkeypatch):
    """Two live slots at depths 5 and 40 and one that is not, in both forms
    of the step's attention - "loop" is what ``ring_cache.attend_step`` runs
    on the CPU and "kernel" what it runs on a TPU (ops/slot_decode.py,
    interpreted here): the loop reads every slot's blocks to the deepest live
    row, the kernel each live slot's to its OWN row and nothing of the one
    that is not (a ring: at most the ring), and their logits are equal to
    the bit; each live slot NEEDS ``min(pos + 1, window)`` rows of a window layer and
    ``pos + 1`` of a global one; a live row counts ONE step whatever the
    layers, and one past the window where its position + 1 exceeds it."""
    chunk, step = programs
    if form == "kernel":
        monkeypatch.setattr(ring_cache, "interpret_mode", lambda: False)
        step = jax.jit(lambda p, c, t, pos, live: smallthinker.decode_step_batch(
            CFG, p, c, t, pos, live))
    read = lambda deep, shallow: [deep] * 3 if form == "loop" else [deep, 0, shallow]
    cache = smallthinker.init_cache(CFG, 3, L)
    cache = _prefill(chunk, params, cache, tokens[0, :41], 0, 8)
    cache = _prefill(chunk, params, cache, tokens[1, :6], 2, 8)
    chunks = {k: int(v) for k, v in cache["counters"].items() if np.ndim(v) == 0}
    # The chunk skips the LAST layer's experts and their plan: 7 calls a chunk.
    assert chunks["moe_chunk_calls"] == chunks["moe_calls"] == 7 * (5 + 1)
    pos, live = np.array([40, 9, 5], np.int32), np.array([True, False, True])
    want, _ = programs[1](params, cache, np.array([1, 2, 3], np.int32), pos, live)  # the loop's
    logits, cache = step(params, cache, np.array([1, 2, 3], np.int32), pos, live)
    np.testing.assert_array_equal(np.asarray(logits)[live], np.asarray(want)[live])
    _, cache = step(params, cache, np.array([1, 2, 3], np.int32),
                    np.array([41, 9, WINDOW - 1], np.int32), live)
    c = {k: np.asarray(v).tolist() for k, v in cache["counters"].items()}
    assert c["attn_live_steps"] == [2, 0, 2]
    assert c["attn_past_window_steps"] == [2, 0, 0]  # 16 positions: the window whole
    # The deep slot: six rings read whole (24 rows) in both steps, a global
    # layer 6 blocks of 8; the shallow one, read to its own row, 1 block
    # (position 5), then 2 (position 15), of every layer.
    assert c["attn_window_rows_read"] == read(2 * 6 * 24, 6 * (8 + 16))
    assert c["attn_global_rows_read"] == read(2 * 2 * 48, 2 * (8 + 16))
    assert c["attn_window_rows_needed"] == [2 * 6 * WINDOW, 0, 6 * (6 + WINDOW)]
    assert c["attn_global_rows_needed"] == [2 * (41 + 42), 0, 2 * (6 + WINDOW)]
    assert c["moe_calls"] - chunks["moe_calls"] == 2 * 8
    assert c["moe_choices"] == c["moe_choices_held"]
    assert smallthinker.decode_rows_read(CFG, pos, live, L) == pytest.approx(
        np.mean(read(6 * 24 + 2 * 48, 8 * 8)) / 8)
    assert smallthinker.prefill_rows_read(CFG, 32, 8, L) == pytest.approx(
        (6 * 24 + 2 * 40) / 8)


def test_generate_is_the_references_greedy_continuation(params, tokens):
    """``generate`` through models/decoding.py - one chunk a row, wider than
    the rings' slack, then a scan of steps past the window - picks the
    tokens the float32 reference puts first, two rows at a time."""
    prompt = tokens[:, :30]
    out = np.asarray(smallthinker.generate(CFG, params, prompt, max_new_tokens=12))
    assert out.shape == (2, 42) and np.array_equal(out[:, :30], prompt)
    # Causal, so right padding is inert: the reference's programs of the
    # fixture's shape serve.
    padded = np.zeros((2, L), np.int32)
    padded[:, :41] = out[:, :-1]
    ref = smallthinker_ref.logits(C_TINY, SEED, padded)[:, 29:41]
    chosen = np.take_along_axis(ref, out[:, 30:, None], axis=-1)[..., 0]
    assert (ref.max(axis=-1) - chosen).max() < TOL


def test_a_router_that_reads_the_experts_input_is_caught_by_the_cells_comparison(
        monkeypatch):
    """``smallthinker-21b-serve-think`` rehearsed through its family - a
    pinned ``ModelReplicaServer``, the engine's chunks and steps - with the
    router handed ``N_post(x1)`` in the PROGRAM (the replica's step and chunk
    are traced after the fault is planted; the reference is untouched): the
    served tokens have to come out of the cell's comparison as not correct.
    The sound rehearsal is tests/test_benchmark_families.py's."""
    from benchmarks import rehearse
    from benchmarks.harness import manifest, serve_cell

    _router_reads_the_experts_input(monkeypatch)
    cell = rehearse.shrink(manifest.Cell("smallthinker-21b-serve-think"))
    out = serve_cell.run(cell, 5, 3.0, False, time.monotonic())
    assert out["check"]["positions"] > 0 and out["failed"] == 0
    assert out["correct"] is False
