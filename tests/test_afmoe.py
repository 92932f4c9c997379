"""models/afmoe.py against the plain float32 reference
(benchmarks/reference/afmoe_ref.py) on seeded weights, at a tiny width: ONE
PIPELINE STAGE of an 8-layer model - published layers 0 (dense, sliding) and
4-7 (expert layers: sliding, sliding, sliding, full) - with a window of 16
positions, rings of 16 + 8 rows, 16 experts of which a token takes 4, and
sequences of 72 positions, four windows and three rings long.

The tolerances and their reasons.  Both sides compute with the same
bfloat16-rounded leaves.  In the FLOAT32 tests the program holds them as
float32 and multiplies in float32, as the reference does, so nothing but the
order of the sums differs and a routing flip is no excuse: the logits
(largest about 4, std 1) agree to ``TOL_F32`` = 2e-3, hundreds of times what
is read (5e-6) and hundreds of times under what the reference with fp8
products reads (1.7).  The chunk-then-step tests hold the same tolerance
against the same full forward, though their attention reads a RING by
position arithmetic where the reference masks, and rotates interleaved pairs
where the reference rotates halves.  In the BFLOAT16 test the program
multiplies bfloat16 operands, and a choice at a near-tie now and then falls
the other way than in the float32 reference; with 16 experts and normalised
weights a choice weighs 0.7 here, and the largest difference of a run is a
flip's (1.06, 1.27, 1.56 over three seeds), so the test holds the bulk: the
MEDIAN over positions of the largest difference reads 0.039-0.057 (the
reference itself with bfloat16 operands: 0.020-0.049) and is bound by
``TOL_BF16`` = 0.1, where fp8 products read 0.61-0.71; and the fp8
reference has to read at least four times the program's.
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

from benchmarks.reference import afmoe_ref, weights  # noqa: E402
from distributed_tensorflow_examples_tpu.models import afmoe, ring_cache  # noqa: E402
from distributed_tensorflow_examples_tpu.ops import moe as moe_ops  # noqa: E402

S, F = afmoe.SLIDING, afmoe.FULL
WINDOW, SLACK, BLOCK = 16, 8, 8
C_TINY = dict(
    vocab_size=300, hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
    num_hidden_layers=8, num_dense_layers=2, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, layer_types=(S, S, S, F) * 2,
    sliding_window=WINDOW, num_experts=16, num_experts_per_tok=4,
    num_shared_experts=1, route_scale=2.826, rms_norm_eps=1e-5, rope_theta=100.0,
    held_layers=(0, 4, 5, 6, 7), init_std=0.125, router_std_factor=0.25,
    expert_bias_std=0.05,
)
SHAPE = {k: v for k, v in C_TINY.items()
         if k not in ("init_std", "router_std_factor", "expert_bias_std")}
CFG32 = afmoe.Config(**SHAPE, param_dtype="float32", ring_slack=SLACK, attn_block=BLOCK)
CFG16 = dataclasses.replace(CFG32, param_dtype="bfloat16")
TOL_F32, TOL_BF16 = 2e-3, 0.1
SEED = 2**31 + 5  # beyond 31 bits, as the driver's seeds are
L = 72


@pytest.fixture(scope="module")
def params16():
    return jax.jit(lambda k: afmoe_ref.tree(C_TINY, k))(weights.base_key(SEED))


@pytest.fixture(scope="module")
def params32(params16):
    return jax.tree.map(lambda a: a.astype(jnp.float32), params16)


@pytest.fixture(scope="module")
def tokens():
    return np.asarray(jax.random.randint(jax.random.key(4), (2, L), 0, 300))


@pytest.fixture(scope="module")
def reference(tokens):
    return afmoe_ref.logits(C_TINY, SEED, tokens)


@pytest.fixture(scope="module")
def programs():
    """The chunk and the step of ``CFG32``, compiled once a shape."""
    return (
        jax.jit(lambda p, c, t, s, o, n: afmoe.prefill_chunk(CFG32, p, c, t, s, o, n)),
        jax.jit(lambda p, c, t, pos, live: afmoe.decode_step_batch(CFG32, p, c, t, pos, live)),
    )


def _prefill(chunk, params, cache, prompt, slot, width):
    """All but the prompt's last token through chunks of ``width``, the last
    one padded - what the serve engine did with its one width.  A list: the
    chunks themselves, ``(valid, width)``."""
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


# ----------------------------------------------------------------------------
# The configuration and the cache's shape
# ----------------------------------------------------------------------------


def test_the_defaults_are_the_published_model_and_the_stage_is_named_by_layer():
    full = afmoe.Config()
    assert (full.num_hidden_layers, full.hidden_size, full.num_experts) == (32, 2048, 128)
    assert full.layers == tuple(range(32)) and full.layer_types.count(F) == 8
    assert [full.window(i) for i in (0, 3, 4, 7)] == [2048, None, 2048, None]
    assert full.is_dense(1) and not full.is_dense(2)
    share = full.share
    assert (share.scoring, share.normalise, share.first, share.held, share.top_k) == (
        "sigmoid", True, 0, 128, 8)
    assert share.scale == pytest.approx(2.826) and not share.top_groups
    stage = dataclasses.replace(full, held_layers=(0, 4, 5, 6, 7))
    assert stage.layers == (0, 4, 5, 6, 7)
    # 32 slots x (4 rings of 2560 rows + 16384 rows) x 2 KB: 1.74 GB, and
    # a spare slot beside them (54.5 MB) for the rows that are not live.
    cache = jax.eval_shape(lambda: afmoe.init_cache(stage, 32, 16384))
    assert cache["layer_4"]["k"].shape == (33, 4, 2560, 128)
    assert cache["layer_7"]["v"].shape == (33, 4, 16384, 128)
    held = sum(a.size * a.dtype.itemsize for k, v in cache.items()
               if k != "counters" for a in v.values())
    assert held * 32 / 33 == pytest.approx(1.74e9, rel=0.01)
    # A cache no longer than a ring has no ring: a position's row is its own.
    assert stage.cache_rows(4, 2000) == 2000
    for bad in (dict(held_layers=(4, 0)), dict(held_layers=(0, 32)),
                dict(layer_types=(S, F)), dict(num_key_value_heads=5)):
        with pytest.raises(ValueError):
            dataclasses.replace(full, **bad)


def test_the_seeded_tree_is_the_tree_init_builds(params16):
    shapes = lambda t: jax.tree.map(lambda a: (a.shape, str(a.dtype)), t)
    assert shapes(params16) == shapes(
        jax.eval_shape(lambda k: afmoe.init(CFG16, k), jax.random.key(0)))
    assert sorted(k for k in params16 if k.startswith("layer_")) == [
        "layer_0", "layer_4", "layer_5", "layer_6", "layer_7"]
    assert "ffn" in params16["layer_0"] and "moe" in params16["layer_4"]
    # Seeded small and NOT zero: the choice-only path is exercised.
    bias = np.asarray(params16["layer_4"]["moe"]["router"]["bias"], np.float32)
    assert 0.02 < bias.std() < 0.1


# ----------------------------------------------------------------------------
# The full forward against the reference
# ----------------------------------------------------------------------------


def test_apply_is_the_reference_in_float32(params32, tokens, reference):
    out = np.asarray(jax.jit(lambda p, t: afmoe.apply(CFG32, p, t))(params32, tokens))
    assert np.abs(reference).max() > 2.5
    assert np.abs(out - reference).max() < TOL_F32
    # The window matters at these lengths, and so does the stage's choice of
    # layers: the reference of the first five layers is another function.
    other = afmoe_ref.logits(dict(C_TINY, held_layers=(0, 1, 2, 3, 4)), SEED, tokens)
    assert np.abs(other - reference).max() > 0.5
    wide = afmoe_ref.logits(dict(C_TINY, sliding_window=L), SEED, tokens)
    assert np.abs(wide[:, :WINDOW] - reference[:, :WINDOW]).max() < TOL_F32
    assert np.abs(wide[:, WINDOW:] - reference[:, WINDOW:]).max() > 0.5


def test_the_bfloat16_program_is_nearer_the_reference_than_fp8_is(params16, tokens, reference):
    out = np.asarray(jax.jit(lambda p, t: afmoe.apply(CFG16, p, t))(params16, tokens))
    fp8 = afmoe_ref.logits(C_TINY, SEED, tokens, "fp8")
    ours = np.median(np.abs(out - reference).max(axis=-1))
    theirs = np.median(np.abs(fp8 - reference).max(axis=-1))
    assert ours < TOL_BF16 and theirs > 4 * ours


FAULTS = {
    "the window mask dropped": lambda mp: mp.setattr(
        afmoe.Config, "window", lambda self, i: None),
    "the output gate dropped": lambda mp: mp.setattr(
        afmoe, "_gated_out",
        lambda cfg, p, u, o: afmoe._mm(cfg, p["o"], o.reshape(o.shape[:-3] + (-1,)))),
    "the bias added to the weights": lambda mp: mp.setattr(
        moe_ops, "share_choice", lambda s, share, bias=None: (
            lambda choice: (choice, jnp.take_along_axis(s + bias, choice, axis=1)))(
                jax.lax.top_k(s + bias, share.top_k)[1])),
    "the rotary left out of the sliding layers": lambda mp: mp.setattr(
        afmoe.layers, "rope_interleaved", lambda x, cos, sin: x.astype(jnp.float32)),
    "the embedding not scaled": lambda mp: mp.setattr(afmoe.math, "sqrt", lambda x: 1.0),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_part_of_the_block_left_out_is_seen_at_the_tolerance(
        monkeypatch, fault, params32, tokens, reference):
    """Each recalled piece of the block is in the comparison: the program
    with it planted wrong misses the reference by tens of tolerances."""
    FAULTS[fault](monkeypatch)
    out = np.asarray(jax.jit(lambda p, t: afmoe.apply(CFG32, p, t))(params32, tokens))
    assert np.abs(out - reference).max() > 20 * TOL_F32


# ----------------------------------------------------------------------------
# Prefill by chunks, then steps through the ring, against the full forward
# ----------------------------------------------------------------------------


#: 36 tokens as chunks of 8, 8, 6 in 8, then FOUR IN A CHUNK OF 4 at rows 22,
#: 23, 0, 1 of a ring of 24 - a narrow chunk across the ring's seam - 2 in 2, 8.
SEAM = [(8, 8), (8, 8), (6, 8), (4, 4), (2, 2), (8, 8)]


@pytest.mark.parametrize("prompt_len,width", [
    (n, w) for w in (8, 5) for n in (9, WINDOW, WINDOW + 1, 37, 59)] + [
    # As the serve engine cuts them at this size, widths 2 / 4 / 8.
    (11, "widths"),  # 10 = 8 + 2 in a chunk of 2
    (WINDOW + 4, "widths"),  # 19 = 8 + 8 + 3 in a chunk of 4, the window just left
    (37, "widths"),  # 36 = four chunks of 8 + 4 in a chunk of 4, on the second lap
    (59, "widths"),  # 58 = seven chunks of 8 + 2 in a chunk of 2, on the third
    (37, SEAM),
], ids=lambda v: "seam" if v is SEAM else str(v))
def test_chunks_then_steps_through_the_ring_are_the_full_forward(
        programs, params32, tokens, reference, engine_chunks, prompt_len, width):
    """Prompts SHORTER than the window (9), EQUAL to it (16, 17: the first
    step is the first query that loses a position) and several times LONGER
    (37, 59: past the ring's 24 rows once and twice), by chunks of 8 (three
    to a ring: a boundary ON the ring's end) and of 5 (a chunk ACROSS it) -
    and as the engine cuts them, the last chunk 2 or 4 wide where that holds
    what is left, and with a chunk of 4 across the ring's end - then steps
    to position 71 - with a second slot stepping at another depth in the
    same launches, and a third that is not live."""
    chunk, step = programs
    cache = afmoe.init_cache(CFG32, 3, L + 8)
    assert cache["layer_4"]["k"].shape == (3 + 1, 2, WINDOW + SLACK, 16)
    assert cache["layer_7"]["v"].shape == (3 + 1, 2, L + 8, 16)
    other_len = 5
    if width == "widths":
        width = [(n, w) for _o, n, w in engine_chunks(prompt_len - 1, 8, 2)]
        assert min(w for _n, w in width) < 8
    cache = _prefill(chunk, params32, cache, tokens[0, :prompt_len], 2, width)
    cache = _prefill(chunk, params32, cache, tokens[1, :other_len], 0,
                     8 if isinstance(width, list) else width)
    pos = np.array([other_len - 1, 0, prompt_len - 1], np.int32)
    live = np.array([True, False, True])
    worst = 0.0
    while pos[2] < L:
        tok = np.array([tokens[1, pos[0]], 7, tokens[0, pos[2]]], np.int32)
        logits, cache = step(params32, cache, tok, pos, live)
        logits = np.asarray(logits)
        worst = max(worst, np.abs(logits[2] - reference[0, pos[2]]).max(),
                    np.abs(logits[0] - reference[1, pos[0]]).max())
        pos = pos + np.array([1, 0, 1], np.int32)
    assert worst < TOL_F32


def test_a_stale_ring_row_of_the_slots_last_session_is_never_read(
        programs, params32, tokens, reference):
    """A session of 60 positions fills every row of its slot's rings and 60
    of the full layer's; the next session in that slot is answered as in a
    fresh cache, TO THE BIT, and as the reference answers."""
    chunk, step = programs

    def session(cache, row, prompt_len, steps):
        cache = _prefill(chunk, params32, cache, tokens[row, :prompt_len], 1, 8)
        out = []
        for p in range(prompt_len - 1, prompt_len - 1 + steps):
            logits, cache = step(
                params32, cache, np.array([0, tokens[row, p]], np.int32),
                np.array([0, p], np.int32), np.array([False, True]))
            out.append(np.asarray(logits)[1])
        return cache, np.stack(out)

    used, _ = session(afmoe.init_cache(CFG32, 2, L), 0, 40, 20)
    assert all(np.abs(np.asarray(used[f"layer_{i}"][a], np.float32)[1]).max(axis=(0, 2)).all()
               for i in (0, 4, 5, 6) for a in "kv")  # no ring row is as it was made
    _, second = session(used, 1, 6, 30)
    _, fresh = session(afmoe.init_cache(CFG32, 2, L), 1, 6, 30)
    assert np.array_equal(second, fresh)
    assert np.abs(second - reference[1, 5:35]).max() < TOL_F32


def test_a_row_that_is_not_live_leaves_its_slot_as_it_was(programs, params32, tokens):
    """The engine's promise for a model that asks for the live rows: a
    session being prefilled is stepped, not live, at its LAST prompt
    position - on a ring that is a row an earlier chunk wrote and a later
    one reads."""
    chunk, step = programs
    cache = _prefill(chunk, params32, afmoe.init_cache(CFG32, 2, L), tokens[0, :30], 0, 8)
    before = jax.tree.map(np.asarray, {k: v for k, v in cache.items() if k != "counters"})
    counted = jax.tree.map(np.asarray, cache["counters"])
    _, after = step(params32, cache, np.array([3, 4], np.int32),
                    np.array([52, 0], np.int32), np.array([False, False]))
    for name, layer in before.items():
        for a in "kv":  # every slot but the spare one, which nothing reads
            assert np.array_equal(np.asarray(after[name][a])[:2], layer[a][:2]), name
    assert np.asarray(after["layer_4"]["k"])[2].any() and not before["layer_4"]["k"][2].any()
    # ... and is counted nowhere but as a call; the step read nothing.
    for name, value in after["counters"].items():
        calls = 4 if name == "moe_calls" else 0
        assert np.array_equal(np.asarray(value), counted[name] + calls), name


def test_a_chunk_wider_than_the_rings_slack_is_refused(params32):
    cache = afmoe.init_cache(CFG32, 1, L)
    with pytest.raises(ValueError, match="ring_slack"):
        afmoe.prefill_chunk(CFG32, params32, cache, np.zeros(SLACK + 1, np.int32), 0, 0, 1)
    # Where no position wraps (a cache shorter than a ring), any width goes.
    short = afmoe.init_cache(CFG32, 1, WINDOW + SLACK - 4)
    afmoe.prefill_chunk(CFG32, params32, short, np.zeros(SLACK + 4, np.int32), 0, 0, 1)


# ----------------------------------------------------------------------------
# What the step counts, and what it tells the engine it read
# ----------------------------------------------------------------------------


@pytest.mark.parametrize("form", ["loop", "kernel"])
def test_the_steps_rows_read_and_needed_are_counted_by_kind_of_layer(
        programs, params32, tokens, form, monkeypatch):
    """Two live slots at depths 5 and 40 and one that is not, in both forms
    of the step's attention - "loop" is what ``ring_cache.attend_step`` runs
    on the CPU and "kernel" what it runs on a TPU (ops/slot_decode.py,
    interpreted here): the loop reads every slot's blocks to the deepest live
    row, the kernel each live slot's to its OWN row and nothing of the one
    that is not (a ring: at most the ring); each live slot NEEDS ``min(pos +
    1, window)`` rows of a sliding layer and ``pos + 1`` of the full one.
    Each counter is a ``[slots]`` array, and the two forms' logits are equal
    to the bit."""
    chunk, step = programs
    if form == "kernel":
        monkeypatch.setattr(ring_cache, "interpret_mode", lambda: False)
        step = jax.jit(lambda p, c, t, pos, live: afmoe.decode_step_batch(
            CFG32, p, c, t, pos, live))
    read = lambda deep, shallow: [deep] * 3 if form == "loop" else [deep, 0, shallow]
    cache = afmoe.init_cache(CFG32, 3, L)
    cache = _prefill(chunk, params32, cache, tokens[0, :41], 0, 8)
    cache = _prefill(chunk, params32, cache, tokens[1, :6], 2, 8)
    pos, live = np.array([40, 9, 5], np.int32), np.array([True, False, True])
    want, _ = programs[1](params32, cache, np.array([1, 2, 3], np.int32), pos, live)  # the loop's
    logits, cache = step(params32, cache, np.array([1, 2, 3], np.int32), pos, live)
    np.testing.assert_array_equal(np.asarray(logits)[live], np.asarray(want)[live])
    c = {k: np.asarray(v).tolist() for k, v in cache["counters"].items()}
    # The deep slot: four sliding layers read the whole ring of 24, the full
    # layer 6 blocks of 8; the shallow one, read to its own row, 1 block of each.
    assert c["attn_window_rows_read"] == read(4 * 24, 4 * 8)
    assert c["attn_global_rows_read"] == read(48, 8)
    assert c["attn_rows_read"] == read(4 * 24 + 48, 5 * 8)
    assert c["attn_window_rows_needed"] == [4 * WINDOW, 0, 4 * 6]
    assert c["attn_global_rows_needed"] == [41, 0, 6]
    # What the engine's counter is told: the mean layer's rows a slot.
    assert afmoe.decode_rows_read(CFG32, pos, live, L) == pytest.approx(
        np.mean(read(4 * 24 + 48, 5 * 8)) / 5)
    assert afmoe.decode_rows_read(CFG32, pos, np.zeros(3, bool), L) == 0
    assert afmoe.prefill_rows_read(CFG32, 8, 8, L) == pytest.approx(16)
    assert afmoe.prefill_rows_read(CFG32, 32, 8, L) == pytest.approx((4 * 24 + 40) / 5)
    # The expert layers' counts: 2 live rows x 4 layers x 4 choices, all held.
    assert c["moe_choices"] - c["moe_chunk_choices_held"] == 2 * 4 * 4
    assert c["moe_choices_held"] == c["moe_choices"]


def test_generate_is_the_references_greedy_continuation(params32, tokens):
    """``generate`` through models/decoding.py - one chunk a row, wider than
    the rings' slack, then a scan of steps past the window - picks the
    tokens the float32 reference puts first, two rows at a time."""
    prompt = tokens[:, :30]
    out = np.asarray(afmoe.generate(CFG32, params32, prompt, max_new_tokens=12))
    assert out.shape == (2, 42) and np.array_equal(out[:, :30], prompt)
    ref = afmoe_ref.logits(C_TINY, SEED, out[:, :-1])
    best = ref.max(axis=-1)[:, 29:]
    chosen = np.take_along_axis(ref, out[:, 1:, None], axis=-1)[:, 29:, 0]
    assert (best - chosen).max() < TOL_F32


# ----------------------------------------------------------------------------
# The cell's comparison sees a planted fault (the rehearsal, served)
# ----------------------------------------------------------------------------


@pytest.mark.parametrize("fault", [
    "the window mask dropped", "the output gate dropped", "the bias added to the weights"])
def test_a_planted_fault_is_caught_by_the_cells_comparison(monkeypatch, fault):
    """``trinity-mini-serve-mixed`` rehearsed through its family with a piece
    of the block planted wrong in the PROGRAM (the replica's step and chunk
    are traced after the fault is planted; the reference is untouched): the
    served tokens have to come out of the cell's comparison as not correct.
    The sound rehearsal (tests/test_benchmark_families.py) reads a widest
    gap of 0.0 over 165-290 positions on seeds 5, 6, 7 against the limit of
    0.005 - the rehearsal holds the reference's own leaves in float32 - and
    over the same seeds the window dropped reads 5.2-5.7 (nine positions of
    ten disagree), the gate dropped 2.3-2.7 and the bias in the weights
    0.50, 0.04, 0.95: the last moves a weight by a tenth and is seen where
    it turns a token, so the test pins the seed."""
    from benchmarks import rehearse
    from benchmarks.harness import manifest, serve_cell

    FAULTS[fault](monkeypatch)
    cell = rehearse.shrink(manifest.Cell("trinity-mini-serve-mixed"))
    out = serve_cell.run(cell, 5, 3.0, False, time.monotonic())
    assert out["check"]["positions"] > 0 and out["failed"] == 0
    assert out["correct"] is False
