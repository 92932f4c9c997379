"""models/jamba.py against the plain float32 reference
(benchmarks/reference/jamba_ref.py) on seeded weights, at a tiny width with
ONE WHOLE PERIOD of the published layer pattern: 14 layers, the attention
layer at offset 7, Mamba-1 everywhere else.

The tolerance and its reason.  Both compute with the same bfloat16-rounded
weights; the program multiplies bfloat16 operands and accumulates in
float32, the reference multiplies in float32.  The seeded kernels have
standard deviation ``1 / sqrt(64)`` (``init_std``), so that at this width,
as at the published one, the layers and not the embedding make the
residual stream and the logits have unit size (largest 3.8-4.4, std 1.0).
Over four seeds the program's logits lie 0.074-0.084 from the reference's
(the reference with its own operands rounded to bfloat16 reads the same
0.072-0.087: the gap IS bfloat16's), and the reference with products in fp8
lies 1.3-2.0 away.  ``TOL`` = 0.25 is three times the sound gap and a fifth
of fp8's smallest, so a program that computed in a lower precision than it
states, or left a piece of the mathematics out, fails; the first test checks
that fp8 does.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.reference import jamba_ref, weights  # noqa: E402
from distributed_tensorflow_examples_tpu.models import jamba  # noqa: E402

C_TINY = dict(
    vocab_size=250, hidden_size=64, num_hidden_layers=14, attn_layer_period=14,
    attn_layer_offset=7, num_attention_heads=4, num_key_value_heads=1,
    intermediate_size=128, mamba_d_state=16, mamba_d_conv=4, mamba_dt_rank=8,
    mamba_expand=2, rms_norm_eps=1e-6, init_std=0.125,
)
CFG = jamba.Config(**{k: v for k, v in C_TINY.items() if k != "init_std"})
TOL = 0.25
SEED = 2**31 + 5  # beyond 31 bits, as the driver's seeds are


@pytest.fixture(scope="module")
def params():
    return jax.jit(lambda k: jamba_ref.tree(C_TINY, k))(weights.base_key(SEED))


@pytest.fixture(scope="module")
def tokens():
    return np.asarray(jax.random.randint(jax.random.key(4), (2, 48), 0, 250))


@pytest.fixture(scope="module")
def reference(tokens):
    return jamba_ref.logits(C_TINY, SEED, tokens)


def test_layer_pattern_tree_and_cache_by_kind(params):
    kinds = CFG.layer_kinds
    assert kinds.count(jamba.ATTENTION) == 1 and kinds[7] == jamba.ATTENTION
    assert jamba.Config().layer_kinds.count(jamba.MAMBA) == 26
    assert [i for i, k in enumerate(jamba.Config().layer_kinds)
            if k == jamba.ATTENTION] == [7, 21]
    own = jax.eval_shape(lambda: jamba.init(CFG, jax.random.key(0)))
    assert jax.tree.structure(own) == jax.tree.structure(params)
    assert jax.tree.map(lambda a: (a.shape, a.dtype), own) == jax.tree.map(
        lambda a: (a.shape, a.dtype), params)
    assert len(jax.tree.leaves(params["layer_0"])) == 17  # a Mamba layer
    cache = jamba.init_cache(CFG, 3, 32)
    assert cache["layer_7"]["k"].shape == (3, 1, 32, 16)
    assert cache["layer_0"]["ssm"].shape == (3, 16, 128)
    assert cache["layer_0"]["ssm"].dtype == jnp.float32
    assert cache["layer_0"]["conv"].shape == (3, 3, 128)


def test_apply_against_the_references_full_forward(params, tokens, reference):
    got = np.asarray(jax.jit(lambda p, t: jamba.apply(CFG, p, t))(params, tokens))
    assert got.shape == reference.shape == (2, 48, 250)
    assert np.abs(got - reference).max() < TOL
    fp8 = jamba_ref.logits(C_TINY, SEED, tokens, "fp8")
    assert np.abs(fp8 - reference).max() > 3 * TOL


@pytest.mark.parametrize(
    "prompt_len,chunk,floor",
    [
        # One width: the floor is over half the chunk.
        (21, 8, 128),   # three chunks, the last padded (20 = 8 + 8 + 4)
        (17, 16, 128),  # one whole chunk, none padded
        (10, 16, 128),  # one padded chunk
        (1, 8, 128),    # no chunk: the step at pos == 0 starts the state
        # The engine's widths, ``floor`` .. ``chunk``.
        (21, 16, 4),    # 20 = 16 + 4 in a chunk of 4: none padded
        (23, 16, 4),    # 22 = 16 + 6 in a chunk of 8
        (4, 16, 4),     # 3 in a chunk of 4, the narrowest: the state starts there
        (27, 8, 2),     # 26 = 8 + 8 + 8 + 2 in a chunk of 2
    ],
)
def test_prefill_by_chunks_then_decode_against_the_full_forward(
    params, tokens, reference, engine_chunks, prompt_len, chunk, floor,
):
    """A prompt enters slot 1 of a USED cache by chunks - as the engine cuts
    it: whole chunks, then the narrowest of its widths that holds the rest -
    then the tokens that follow are decoded through it one by one beside two
    rows that are not live; every step's logits are the full forward's at
    that position.  Conv tail and state after the valid tokens are what the
    chunks of ONE width leave: the padding a narrower chunk no longer
    computes never reached them."""
    pre = jax.jit(lambda p, c, t, s, o, n: jamba.prefill_chunk(CFG, p, c, t, s, o, n))
    step = jax.jit(lambda p, c, t, pos, live: jamba.decode_step_batch(CFG, p, c, t, pos, live))
    # Whatever a session before left in the slots: a state that is not zero.
    cache = jax.tree.map(
        lambda a: jnp.full(a.shape, 0.37, a.dtype), jamba.init_cache(CFG, 3, 64))
    row = tokens[0]

    def fill(cache, off, n, width):
        buf = np.zeros(width, np.int32)
        buf[:n] = row[off:off + n]
        return pre(params, cache, buf, 1, off, n)

    used = cache
    for off, n, width in engine_chunks(prompt_len - 1, chunk, floor):
        # Only a prompt's last chunk is narrower: that one at the ONE width too.
        cache, used = fill(cache, off, n, width), fill(cache, off, n, chunk)
    for name, layer in cache.items():
        for kind, a in layer.items():  # conv, ssm | k, v: what slot 1 holds
            rows = (slice(None), slice(0, prompt_len - 1)) if kind in "kv" else ()
            np.testing.assert_allclose(
                np.asarray(a[1])[rows], np.asarray(used[name][kind][1])[rows],
                rtol=1e-5, atol=1e-6, err_msg=f"{name}/{kind}")
    others = jax.tree.map(lambda a: np.asarray(a[::2]), cache)
    worst = 0.0
    for pos in range(prompt_len - 1, 40):
        logits, cache = step(
            params, cache, np.array([5, row[pos], 9], np.int32),
            np.array([3, pos, 0], np.int32), np.array([False, True, False]))
        worst = max(worst, float(np.abs(np.asarray(logits[1]) - reference[0, pos]).max()))
    assert worst < TOL
    # The rows that were not live left everything their slots own as it was.
    jax.tree.map(np.testing.assert_array_equal,
                 others, jax.tree.map(lambda a: np.asarray(a[::2]), cache))


def test_generate_is_the_references_greedy_continuation(params, tokens):
    """Tokens are compared through the reference's logits, not one for one
    (with seeded weights the largest logit changes on rounding): each
    generated token's reference logit lies within ``TOL`` of the best."""
    out = np.asarray(jamba.generate(CFG, params, tokens[:, :9], max_new_tokens=6))
    assert out.shape == (2, 15) and np.array_equal(out[:, :9], tokens[:, :9])
    ref = jamba_ref.logits(C_TINY, SEED, out)
    for b in range(2):
        for t in range(8, 14):
            assert ref[b, t].max() - ref[b, t, out[b, t + 1]] < TOL
