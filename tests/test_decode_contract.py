"""What a served model tells the decode engine (``models.decoding.DecodeFns``)
and what its functions do agree, for every served form: traced
(``jax.eval_shape``), never compiled, at a test-sized ``Config`` each.  The
engine runs of tests/test_serving.py and the cells' rehearsals hold the same
by serving tokens, minutes a family; these hold it in under a second a case.
"""

from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.reference import smallthinker_ref  # noqa: E402
from distributed_tensorflow_examples_tpu import models  # noqa: E402
from distributed_tensorflow_examples_tpu.models.decoding import DecodeFns  # noqa: E402
from distributed_tensorflow_examples_tpu.serve import model_server  # noqa: E402

SLOTS, MAX_LEN = 3, 1024

#: models/smallthinker.py has no ``init``: its weights are the reference's.
_SMALLTHINKER = dict(
    vocab_size=97, hidden_size=32, moe_ffn_hidden_size=16, num_hidden_layers=4,
    num_attention_heads=4, num_key_value_heads=2, head_dim=8,
    sliding_window_layout=(0, 1, 1, 1), rope_layout=(0, 1, 1, 1), sliding_window_size=8,
    moe_num_primary_experts=8, moe_num_active_primary_experts=2, rms_norm_eps=1e-6,
    rope_theta=100.0, held_layers=())
_SEEDED = dict(init_std=0.125, router_spread=2.0, out_std_factor=0.05)

#: form -> (module, its test-sized Config, the width and the type of its
#: logits: float32, but the transformer's in its compute type).
FORMS = {
    "transformer": lambda: (models.transformer, models.transformer.Config(
        vocab_size=128, dim=32, n_layers=2, n_heads=4, max_seq_len=MAX_LEN), 128, jnp.bfloat16),
    "transformer_moe": lambda: (models.transformer, models.transformer.Config(
        vocab_size=128, dim=32, n_layers=2, n_heads=4, max_seq_len=MAX_LEN,
        moe_experts=4), 128, jnp.bfloat16),
    "jamba": lambda: (models.jamba, models.jamba.Config(
        vocab_size=97, hidden_size=32, num_hidden_layers=4, attn_layer_period=4,
        attn_layer_offset=1, num_attention_heads=2, num_key_value_heads=1,
        intermediate_size=64, mamba_dt_rank=4, mamba_d_state=8), 97, jnp.float32),
    "longcat": lambda: (models.longcat, models.longcat.Config(
        vocab_size=64, hidden_size=32, ffn_hidden_size=32, expert_ffn_hidden_size=16,
        num_layers=2, num_attention_heads=2, kv_lora_rank=16, q_lora_rank=16,
        qk_rope_head_dim=8, qk_nope_head_dim=8, v_head_dim=8, n_routed_experts=8,
        zero_expert_num=4, moe_topk=2, experts_held=4, expert_first=4,
        vocab_rows=64), 64, jnp.float32),
    "deepseek": lambda: (models.deepseek, models.deepseek.Config(
        vocab_size=64, hidden_size=32, intermediate_size=32, moe_intermediate_size=16,
        num_hidden_layers=2, num_attention_heads=2, kv_lora_rank=16, q_lora_rank=16,
        qk_rope_head_dim=8, qk_nope_head_dim=8, v_head_dim=8, n_routed_experts=8,
        n_shared_experts=1, n_group=2, topk_group=1, num_experts_per_tok=2,
        rope_original_max_position_embeddings=16, experts_held=4, expert_first=4,
        vocab_rows=64), 64, jnp.float32),
    "afmoe": lambda: (models.afmoe, models.afmoe.Config(
        vocab_size=97, hidden_size=32, intermediate_size=64, moe_intermediate_size=16,
        num_hidden_layers=4, num_dense_layers=1, num_attention_heads=4,
        num_key_value_heads=2, head_dim=8, sliding_window=8, num_experts=8,
        num_experts_per_tok=2,
        layer_types=(models.afmoe.SLIDING,) * 3 + (models.afmoe.FULL,)), 97, jnp.float32),
    "smallthinker": lambda: (
        models.smallthinker, models.smallthinker.Config(**_SMALLTHINKER), 97, jnp.float32),
    "nemotron_h": lambda: (models.nemotron_h, models.nemotron_h.Config(
        vocab_size=97, hidden_size=32, num_hidden_layers=4, hybrid_override_pattern="ME*E",
        mamba_num_heads=4, mamba_head_dim=8, ssm_state_size=16, n_groups=2,
        num_attention_heads=4, num_key_value_heads=2, head_dim=8, n_routed_experts=8,
        num_experts_per_tok=2, moe_latent_size=16, moe_intermediate_size=24,
        moe_shared_expert_intermediate_size=40, experts_held=4, expert_first=4,
        vocab_rows=64), 64, jnp.float32),
}


def _shapes(tree):
    return jax.tree.map(lambda a: (a.shape, a.dtype), tree)


@pytest.fixture(params=list(FORMS))
def form(request):
    """``(fns, params, cache, logits)``: the form's contract, the shapes of
    its parameters and of the cache its ``init_cache`` gives, and the shape
    and type of a step's logits."""
    mod, cfg, vocab, dtype = FORMS[request.param]()
    fns = mod.serve_decode_fns(cfg)
    assert isinstance(fns, DecodeFns)
    init = getattr(mod, "init", None) or (
        lambda _cfg, key: smallthinker_ref.tree({**_SMALLTHINKER, **_SEEDED}, key))
    params = jax.eval_shape(lambda: init(cfg, jax.random.key(0)))
    cache = jax.eval_shape(lambda: fns.init_cache(SLOTS, MAX_LEN))
    return fns, params, cache, ((SLOTS, vocab), dtype)


def _vec(dtype):
    return jax.ShapeDtypeStruct((SLOTS,), dtype)


def test_the_step_takes_what_the_engine_sends_it_and_returns_logits_and_the_cache(form):
    """The engine sends ``live`` to a step whose contract says
    ``wants_live`` and to no other: the step takes exactly that, returns
    ``[S, V]`` logits in the form's type and a cache of the shapes ``init_cache`` gave -
    and refuses the other form, so a word that disagrees with the function
    is a failure here and not a wrong token on the chip."""
    fns, params, cache, logits_are = form
    sent = (params, cache, _vec(jnp.int32), _vec(jnp.int32))
    live = (_vec(jnp.bool_),)
    logits, new = jax.eval_shape(fns.step, *sent, *(live if fns.wants_live else ()))
    assert (logits.shape, logits.dtype) == logits_are
    assert _shapes(new) == _shapes(cache)
    with pytest.raises(TypeError):
        jax.eval_shape(fns.step, *sent, *(() if fns.wants_live else live))


def test_the_chunk_takes_every_width_the_engine_dispatches(form, monkeypatch):
    """``prefill`` is traced once a width of ``chunk_widths(PREFILL_CHUNK)``
    and returns the cache's structure at each; a model that hands none (the
    transformer's MoE form) owes the cache no prefill: its prompt goes
    through the step."""
    fns, params, cache, _logits = form
    if fns.prefill is None:
        eng = model_server._DecodeEngine(
            lambda: None, fns, slots=SLOTS, max_len=MAX_LEN, max_sessions=4)
        try:
            monkeypatch.setattr(eng.batcher, "open", lambda state: state)
            state = eng.open(np.arange(1, 6, dtype=np.int32), 3)
            assert (state["prefill"], state["pos"], state["end"]) == (0, 0, 7)
        finally:
            eng.stop()
        return
    widths = model_server.chunk_widths(model_server.PREFILL_CHUNK)
    assert len(widths) > 1 and widths[-1] <= MAX_LEN
    scalar = jax.ShapeDtypeStruct((), jnp.int32)
    for width in widths:
        tokens = jax.ShapeDtypeStruct((width,), jnp.int32)
        new = jax.eval_shape(fns.prefill, params, cache, tokens, scalar, scalar, scalar)
        assert _shapes(new) == _shapes(cache)


def test_what_a_step_and_a_chunk_say_they_read_lies_within_the_cache(form):
    """``step_rows_read`` / ``chunk_rows_read`` as the engine calls them, or
    its ``max_len`` where the model says nothing: within ``[0, max_len]`` at
    position 0, at the deepest position and for rows that are not live."""
    fns, _params, _cache, _logits = form
    eng = model_server._DecodeEngine(
        lambda: None, fns, slots=SLOTS, max_len=MAX_LEN, max_sessions=4)
    try:
        step_reads, chunk_reads = eng._rows_read, eng._chunk_rows_read
    finally:
        eng.stop()
    top = np.full(SLOTS, MAX_LEN - 1, np.int32)
    if fns.step_rows_read is None:
        assert step_reads(top, np.ones(SLOTS, bool), MAX_LEN) == MAX_LEN
    if fns.chunk_rows_read is None:
        assert chunk_reads(0, 256, MAX_LEN) == MAX_LEN
    for pos, live in (
        (np.zeros(SLOTS, np.int32), np.ones(SLOTS, bool)),
        (top, np.ones(SLOTS, bool)),
        (top, np.zeros(SLOTS, bool)),
        (top, np.arange(SLOTS) == 1),
    ):
        assert 0 <= step_reads(pos, live, MAX_LEN) <= MAX_LEN
    assert step_reads(np.zeros(SLOTS, np.int32), np.ones(SLOTS, bool), MAX_LEN) <= step_reads(
        top, np.ones(SLOTS, bool), MAX_LEN)
    for width in model_server.chunk_widths(model_server.PREFILL_CHUNK):
        for offset in (0, MAX_LEN - width):
            assert 0 <= chunk_reads(offset, width, MAX_LEN) <= MAX_LEN
