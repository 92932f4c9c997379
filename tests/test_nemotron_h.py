"""models/nemotron_h.py against the plain float32 reference
(benchmarks/reference/nemotron_h_ref.py) on seeded weights, at a tiny width:
ONE CHIP'S SHARE of the first stage of an 8-layer model - published layers
0-5 of ``ME*EMEM*`` (two Mamba-2 layers, three expert layers, one attention
layer), 4 Mamba heads of 8 channels in 2 groups with a state of 16 columns,
blocks of 8 positions, 4 query heads on 2 K/V heads, a latent of 32 under a
hidden of 64, experts 4-11 of 16 held and 4 a token, ids 0-149 of 300 - and
sequences of 72 positions, nine blocks of the chunked recurrence long.

The tolerance and its reason.  Both sides compute with the same
bfloat16-rounded leaves; the program holds them as float32 and multiplies in
float32, as the reference does, so nothing differs but the order of the sums
- and, in a Mamba layer, ``exp`` of a sum of steps (the chunked form) where
the reference multiplies ``exp`` of each (the recurrence, one position at a
time): the logits (largest about 4, std 1) agree to ``TOL`` = 2e-3, hundreds
of times what is read (1e-5), a routing flip is no excuse, and the reference
itself with bfloat16 products misses by eight tolerances.  The chunk-then-step
tests hold the same tolerance against the same full forward, though their
recurrence runs by chunks of other widths and then by the in-place step,
and their attention reads a cache by position arithmetic.
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.reference import nemotron_h_ref, weights  # noqa: E402
from distributed_tensorflow_examples_tpu.models import nemotron_h, ring_cache  # noqa: E402
from distributed_tensorflow_examples_tpu.ops import moe as moe_ops  # noqa: E402
from distributed_tensorflow_examples_tpu.ops import ssd  # noqa: E402

C_TINY = dict(
    vocab_size=300, hidden_size=64, num_hidden_layers=8, hybrid_override_pattern="ME*EMEM*",
    mamba_num_heads=4, mamba_head_dim=8, ssm_state_size=16, n_groups=2, conv_kernel=4,
    chunk_size=8, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    n_routed_experts=16, num_experts_per_tok=4, moe_latent_size=32,
    moe_intermediate_size=48, moe_shared_expert_intermediate_size=96,
    routed_scaling_factor=5.0, layer_norm_epsilon=1e-5,
    held_layers=tuple(range(6)), experts_held=8, expert_first=4, vocab_rows=150,
    table_std=1.0, out_factor=0.3, expert_down_factor=0.5, router_spread=1.0,
    expert_bias_std=0.05, conv_std=0.5, conv_bias_std=0.1,
)
SEEDED = ("table_std", "out_factor", "expert_down_factor", "router_spread",
          "expert_bias_std", "conv_std", "conv_bias_std")
CFG = nemotron_h.Config(
    **{k: v for k, v in C_TINY.items() if k not in SEEDED},
    param_dtype="float32", attn_block=8, handoff_rows=16)
TOL = 2e-3
SEED = 2**31 + 11  # beyond 31 bits, as the driver's seeds are
L = 72


@pytest.fixture(scope="module")
def params():
    tree = jax.jit(lambda k: nemotron_h_ref.tree(C_TINY, k))(weights.base_key(SEED))
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


@pytest.fixture(scope="module")
def tokens():
    return np.asarray(jax.random.randint(jax.random.key(4), (2, L), 0, 150))


@pytest.fixture(scope="module")
def reference(tokens):
    return nemotron_h_ref.logits(C_TINY, SEED, tokens)


@pytest.fixture(scope="module")
def programs():
    """The chunk and the step of ``CFG``, compiled once a shape."""
    return (
        jax.jit(lambda p, c, t, s, o, n: nemotron_h.prefill_chunk(CFG, p, c, t, s, o, n)),
        jax.jit(lambda p, c, t, pos, live: nemotron_h.decode_step_batch(
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


def test_the_defaults_are_the_published_model_and_the_share_is_named():
    full = nemotron_h.Config()
    assert (full.num_hidden_layers, full.hidden_size, full.d_inner, full.conv_dim) == (
        88, 4096, 8192, 10240)
    pattern = full.hybrid_override_pattern
    assert [pattern.count(k) for k in "ME*"] == [40, 40, 8] and pattern[:11] == "MEMEMEM*EME"
    share = full.share
    assert (share.scoring, share.normalise, share.activation, share.first,
            share.held, share.top_k, share.scale) == (
        "sigmoid", True, "relu2", 0, 512, 22, 5.0)
    stage = dataclasses.replace(
        full, held_layers=tuple(range(11)), experts_held=128, vocab_rows=32768)
    assert stage.share.held == 128 and stage.vocab == 32768
    # 32 slots x 5 layers x (4.19 MB of state + 0.12 MB of tail) = 0.69 GB; 33
    # slots x 32768 rows x 1 KB = 1.11 GB; nothing for an expert layer.
    cache = jax.eval_shape(lambda: nemotron_h.init_cache(stage, 32, 32768))
    assert cache["layer_0"]["ssm"].shape == (32, 128, 64, 128)
    assert cache["layer_0"]["conv"].shape == (32, 3, 10240)
    assert cache["layer_7"]["k"].shape == (33, 2, 32768, 128) and "layer_1" not in cache
    size = lambda kind: sum(
        a.size * a.dtype.itemsize for k, v in cache.items()
        if k.startswith("layer_") and kind in v for a in v.values())
    assert size("ssm") == pytest.approx(0.69e9, rel=0.01)
    assert size("k") == pytest.approx(1.11e9, rel=0.01)
    params = jax.eval_shape(lambda: nemotron_h.init(stage, jax.random.key(0)))
    assert sum(a.size for a in jax.tree.leaves(params)) == pytest.approx(4648e6, rel=1e-3)
    for bad in (dict(held_layers=(4, 0)), dict(held_layers=(0, 88)),
                dict(hybrid_override_pattern="ME*"), dict(num_key_value_heads=5),
                dict(n_groups=3), dict(experts_held=256, expert_first=384)):
        with pytest.raises(ValueError):
            dataclasses.replace(full, **bad)


def test_apply_is_the_reference_in_float32(params, tokens, reference):
    out = np.asarray(jax.jit(lambda p, t: nemotron_h.apply(CFG, p, t))(params, tokens))
    assert np.abs(reference).max() > 2.5
    assert np.abs(out - reference).max() < TOL
    # Tight enough that bfloat16 for float32 fails it: the reference itself
    # with its products' operands rounded to bfloat16.
    low = nemotron_h_ref.logits(C_TINY, SEED, tokens, "bfloat16")
    assert np.abs(low - reference).max() > 5 * TOL


def _share_with(**changes):
    share = nemotron_h.Config.share.fget
    return property(lambda self: dataclasses.replace(share(self), **changes))


def _norm_before_the_gate(mp):
    def out(cfg, p, y, z):
        lead, G = z.shape[:-1], cfg.n_groups
        g = y.reshape(lead + (G, -1))
        g = g * jax.lax.rsqrt(
            jnp.mean(jnp.square(g), axis=-1, keepdims=True) + cfg.layer_norm_epsilon)
        g = g.reshape(z.shape) * p["norm"]["scale"] * jax.nn.silu(z)
        return nemotron_h._mm(cfg, p["out_proj"], g)

    mp.setattr(nemotron_h, "_mamba_out", out)


def _another_groups_b_and_c(mp):
    chunk = ssd.ssd_chunk
    mp.setattr(ssd, "ssd_chunk", lambda x, dt, a, b, c, *rest, **kw: chunk(
        x, dt, a, jnp.roll(b, 1, axis=1), jnp.roll(c, 1, axis=1), *rest, **kw))


def _experts_fed_the_hidden(mp):
    """The experts on the first ``moe_latent_size`` values of the HIDDEN in
    place of the latent."""
    mm = nemotron_h._mm

    def fed(cfg, p, x):
        if p["kernel"].shape == (cfg.hidden_size, cfg.moe_latent_size):
            return x[..., :cfg.moe_latent_size]
        return mm(cfg, p, x)

    mp.setattr(nemotron_h, "_mm", fed)


#: Each plants its fault; the value is the ``Config`` to run, or what makes it.
FAULTS = {
    "silu for relu^2": lambda mp: mp.setattr(
        nemotron_h.Config, "share", _share_with(activation="silu")),
    "relu for relu^2": lambda mp: mp.setattr(
        nemotron_h.Config, "share", _share_with(activation="relu")),
    "the scale of 5 lost": lambda mp: dataclasses.replace(CFG, routed_scaling_factor=1.0),
    "the norm before the gate": _norm_before_the_gate,
    "a head reads another group's B and C": _another_groups_b_and_c,
    "experts fed the hidden and not the latent": _experts_fed_the_hidden,
    "the shared expert doubled": lambda mp: mp.setattr(
        nemotron_h, "_relu2", lambda v: 2 * jnp.square(jax.nn.relu(v))),
    "the shared expert left out": lambda mp: mp.setattr(
        nemotron_h, "_relu2", lambda v: 0 * v),
    "the bias in the weights": lambda mp: mp.setattr(
        moe_ops, "share_choice", lambda s, share, bias=None: (
            lambda choice: (choice, jnp.take_along_axis(s + bias, choice, axis=1)))(
                jax.lax.top_k(s + bias, share.top_k)[1])),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_part_of_the_block_planted_wrong_is_seen_at_the_tolerance(
        monkeypatch, fault, params, tokens, reference):
    """Each recalled piece of the three kinds of layer is in the comparison:
    the program with it planted wrong misses the reference by tens of
    tolerances."""
    cfg = FAULTS[fault](monkeypatch) or CFG
    out = np.asarray(jax.jit(lambda p, t: nemotron_h.apply(cfg, p, t))(params, tokens))
    assert np.abs(out - reference).max() > 20 * TOL


@pytest.mark.parametrize("prompt_len,width", [(9, 8), (30, 16), (37, "widths"), (59, 5)])
def test_chunks_then_steps_through_the_cache_are_the_full_forward(
        programs, params, tokens, reference, engine_chunks, prompt_len, width):
    """Prompts of 9 to 59 tokens by chunks of 8 (a block of the recurrence),
    16 (two), 5 (a chunk ends inside a block: the state is carried from the
    middle of one) and as the engine cuts them (the last chunk 4 wide) - then
    steps to position 71, with a second slot stepping at another depth in
    the same launches and a third that is not live.  THE SLOT WAS ANOTHER
    SESSION'S: the other row's whole sequence went through it first, and the
    session seated after starts from zero whatever the slot holds."""
    chunk, step = programs
    cache = nemotron_h.init_cache(CFG, 3, L + 8)
    assert cache["layer_2"]["k"].shape == (3 + 1, 2, L + 8, 16)
    assert cache["layer_0"]["ssm"].shape == (3, 4, 8, 16) and "layer_1" not in cache
    other_len = 5
    if width == "widths":
        width = [(n, w) for _o, n, w in engine_chunks(prompt_len - 1, 8, 2)]
        assert min(w for _n, w in width) < 8
    cache = _prefill(chunk, params, cache, tokens[1], 2, 8)  # the slot's past
    assert np.abs(np.asarray(cache["layer_0"]["ssm"][2])).max() > 0.1
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


def test_a_row_that_is_not_live_leaves_its_slot_bit_equal_and_a_first_step_starts_from_zero(
        programs, params, tokens, reference):
    """A step with slot 1 NOT live - whatever token and position it is
    handed - leaves slot 1's states, tails and rows as they were to the bit
    (and the spare slot takes its key), while slots 0 and 2 advance; then a
    session seated in the dirty slot 1 BY THE STEP, from position 0, reads
    the reference's logits: it started from the zero state and tail."""
    chunk, step = programs
    cache = nemotron_h.init_cache(CFG, 3, L)
    for slot, row in ((0, 0), (1, 1), (2, 0)):
        cache = _prefill(chunk, params, cache, tokens[row, :20], slot, 8)
    mine = lambda c: [np.asarray(a[1]) for k in sorted(c) if k.startswith("layer_")
                      for a in c[k].values()]
    before = mine(cache)
    assert len(before) == 2 * 3 and all(np.abs(a).max() > 0 for a in before)
    tok = np.array([tokens[0, 19], 5, tokens[0, 19]], np.int32)
    _, after = step(params, cache, tok, np.array([19, 19, 19], np.int32),
                    np.array([True, False, True]))
    for a, b in zip(before, mine(after)):
        np.testing.assert_array_equal(a, b)
    assert np.abs(np.asarray(after["layer_0"]["ssm"][0] - cache["layer_0"]["ssm"][0])).max() > 0
    cache, pos = after, np.array([20, 0, 20], np.int32)
    for t in range(6):
        tok = np.array([tokens[0, 20 + t], tokens[1, t], tokens[0, 20 + t]], np.int32)
        logits, cache = step(params, cache, tok, pos + t, np.array([True, True, True]))
        assert np.abs(np.asarray(logits)[1] - reference[1, t]).max() < TOL
        assert np.abs(np.asarray(logits)[0] - reference[0, 20 + t]).max() < TOL


@pytest.mark.parametrize("form", ["loop", "kernel"])
def test_what_the_chunk_and_the_step_count(programs, params, tokens, form, monkeypatch):
    """Every layer held is computed by the chunk, the last ``E`` too: three
    expert calls and two calls of the chunked recurrence a chunk, at the
    width it was dispatched at; the step's attention counts the one
    attention layer's rows in both its forms - "loop" is what
    ``ring_cache.attend_step`` runs on the CPU, "kernel" what it runs on a
    TPU (ops/slot_decode.py, interpreted here), with logits equal to the
    bit; and what the model tells the engine it reads is that layer's
    blocks as the form that runs reads them."""
    chunk, step = programs
    if form == "kernel":
        monkeypatch.setattr(ring_cache, "interpret_mode", lambda: False)
        step = jax.jit(lambda p, c, t, pos, live: nemotron_h.decode_step_batch(
            CFG, p, c, t, pos, live))
    cache = nemotron_h.init_cache(CFG, 3, L)
    cache = _prefill(chunk, params, cache, tokens[0, :41], 0, 8)
    cache = _prefill(chunk, params, cache, tokens[1, :6], 2, 8)
    c = {k: np.asarray(v).tolist() for k, v in cache["counters"].items()}
    assert c["moe_chunk_calls"] == c["moe_calls"] == 3 * (5 + 1)
    assert (c["ssd_calls"], c["ssd_positions"]) == (2 * 6, 2 * 6 * 8)
    assert c["moe_choices"] == 3 * 4 * (40 + 5) and 0 < c["moe_choices_held"] < c["moe_choices"]
    assert np.abs(np.asarray(cache["handoff"][:5])).max() > 0.5
    pos, live = np.array([40, 9, 5], np.int32), np.array([True, False, True])
    want, _ = programs[1](params, cache, np.array([1, 2, 3], np.int32), pos, live)  # the loop's
    logits, cache = step(params, cache, np.array([1, 2, 3], np.int32), pos, live)
    np.testing.assert_array_equal(np.asarray(logits)[live], np.asarray(want)[live])
    c = {k: np.asarray(v).tolist() for k, v in cache["counters"].items()}
    assert c["moe_calls"] == 3 * 6 + 3 and c["ssd_calls"] == 12
    # Six blocks of 8 - the loop every slot's, the kernel each live slot's own.
    assert c["attn_global_rows_read"] == ([48] * 3 if form == "loop" else [48, 0, 8])
    assert c["attn_global_rows_needed"] == [41, 0, 6]
    assert nemotron_h.decode_rows_read(CFG, pos, live, L) == pytest.approx(
        48 if form == "loop" else (48 + 8) / 3)
    assert nemotron_h.prefill_rows_read(CFG, 32, 8, L) == 40
    no_rows = dataclasses.replace(CFG, held_layers=(0, 1))
    assert nemotron_h.decode_rows_read(no_rows, pos, live, L) == 0


def test_generate_is_the_references_greedy_continuation(params, tokens):
    """``generate`` through models/decoding.py - one chunk a row, wider than
    the hand-off buffer was, then a scan of steps - picks the tokens the
    float32 reference puts first, two rows at a time."""
    prompt = tokens[:, :30]
    out = np.asarray(nemotron_h.generate(CFG, params, prompt, max_new_tokens=12))
    assert out.shape == (2, 42) and np.array_equal(out[:, :30], prompt)
    # Causal, so right padding is inert: the reference's programs of the
    # fixture's shape serve.
    padded = np.zeros((2, L), np.int32)
    padded[:, :41] = out[:, :-1]
    ref = nemotron_h_ref.logits(C_TINY, SEED, padded)[:, 29:41]
    chosen = np.take_along_axis(ref, out[:, 30:, None], axis=-1)[..., 0]
    assert (ref.max(axis=-1) - chosen).max() < TOL


def test_the_shares_of_four_chips_and_what_every_chip_computes_once_are_the_uncut_layer(
        params):
    """THE SHARE TIES TO THE MODEL: an expert layer's output for chips 0-3,
    4 of the 16 experts each from one seed - every chip planning from the
    same normed input with the whole router, its partial LATENT sum through
    ``latent_out`` - with the shared expert counted once, add up to the
    reference's whole layer (``experts_held`` 0: all 16); and every choice is
    on some chip's expert."""
    key = weights.base_key(SEED)
    u = jax.random.normal(jax.random.key(3), (40, 64))
    whole = dict(C_TINY, experts_held=0, expert_first=0)
    p_ref = jax.tree.map(
        lambda a: a.astype(jnp.float32),
        nemotron_h_ref.build(nemotron_h_ref.layer_spec(whole, 1), key, layer=1))["moe"]
    expert_fn = lambda e: jax.tree.map(
        lambda a: a.astype(jnp.float32), nemotron_h_ref.expert(whole, key, 1, e))
    want = np.asarray(nemotron_h_ref.latent_moe(whole, p_ref, expert_fn, u, "float32"))
    shared = np.asarray(nemotron_h._mm(CFG, p_ref["shared"]["down"], nemotron_h._relu2(
        nemotron_h._mm(CFG, p_ref["shared"]["up"], u))))
    total, held = shared.copy(), 0
    for chip in range(4):
        c = dict(C_TINY, experts_held=4, expert_first=4 * chip)
        cfg = dataclasses.replace(CFG, experts_held=4, expert_first=4 * chip)
        p = jax.tree.map(lambda a: a.astype(jnp.float32),
                         nemotron_h_ref.tree(c, key))["layer_1"]["moe"]
        counters = moe_ops.share_counters(nemotron_h.COUNTS)
        out, counters = nemotron_h._experts(cfg, p, u, None, counters)
        total += np.asarray(out) - shared
        held += int(counters["moe_choices_held"])
        assert int(counters["moe_choices"]) == 40 * 4
    assert held == 40 * 4
    assert np.abs(want).max() > 0.5 and np.abs(want - shared).max() > 0.1
    assert np.abs(total - want).max() < 1e-4
