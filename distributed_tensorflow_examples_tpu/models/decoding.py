"""What the served families share: the contract a model hands the decode
engine, and generation by the loop a replica runs, as one compiled program.

A model that has ``init_cache(cfg, slots, max_len)``, ``prefill_chunk(cfg,
params, cache, tokens, slot, offset, n_valid)`` and ``decode_step_batch(cfg,
params, cache, tokens, pos[, live])`` is served through :func:`serve_fns`
(a :class:`DecodeFns`) and, where its step takes ``live``, generates with
:func:`generate`; neither names a model, and nothing here imports ``serve/``.
"""

from __future__ import annotations

import functools
import typing

import jax
import jax.numpy as jnp


class DecodeFns(typing.NamedTuple):
    """What ``serve.ModelReplicaServer(decode_fns=...)`` is told of a served
    model.  Still a tuple whose first fields are the old pair and triple:
    ``DecodeFns(*pair)`` says nothing more than the pair did.

    ``init_cache(slots, max_len)``  the per-slot cache pytree.
    ``step(params, cache, tokens[S], pos[S][, live[S]])``
                      ``-> (logits [S, V], cache)``: every row one position
                      on; the logits float32 or the model's compute type
                      (the engine selects over them as they are).
    ``prefill(params, cache, tokens[C], slot, offset, n_valid) -> cache``
                      one slot's positions ``[offset, offset + n_valid)``
                      entered in one pass, at every width of the engine's
                      ``chunk_widths``; ``None``: the engine feeds a prompt
                      through ``step``, a token a step.
    ``wants_live``    ``step`` takes ``live [S]`` bool, and a row that is not
                      live leaves everything its slot owns unchanged (a
                      state, a ring: ``_DecodeEngine``'s docstring).
    ``step_rows_read(pos, live, max_len)``
                      cache positions a step reads A SLOT IN THE MEAN, from
                      the host's ``pos [S]`` int32 and ``live [S]`` bool (the
                      engine's own arrays: not to be kept or changed).
    ``chunk_rows_read(offset, chunk, max_len)``
                      positions of its slot a chunk's attention reads, from
                      the chunk's offset and the width it was dispatched at.
                      ``None`` for either: all ``max_len``.
    """

    init_cache: typing.Callable
    step: typing.Callable
    prefill: typing.Callable | None = None
    wants_live: bool = False
    step_rows_read: typing.Callable | None = None
    chunk_rows_read: typing.Callable | None = None


def serve_fns(cfg, init_cache, decode_step_batch, prefill_chunk, *,
              wants_live: bool, step_rows_read=None, chunk_rows_read=None) -> DecodeFns:
    """The :class:`DecodeFns` of a model from its own three functions, each
    closed over ``cfg``; ``prefill_chunk`` may be ``None``.  One definition,
    so the served decode path and the model cannot drift."""

    def init_cache_fn(slots: int, max_len: int):
        return init_cache(cfg, slots, max_len)

    def step_fn(params, cache, tokens, pos, *live):
        return decode_step_batch(cfg, params, cache, tokens, pos, *live)

    def prefill_fn(params, cache, tokens, slot, offset, n_valid):
        return prefill_chunk(cfg, params, cache, tokens, slot, offset, n_valid)

    return DecodeFns(
        init_cache_fn, step_fn, prefill_fn if prefill_chunk else None,
        wants_live, step_rows_read, chunk_rows_read)


def generate(cfg, params, prompt, *, init_cache, prefill_chunk, decode_step_batch,
             max_new_tokens: int, temperature: float = 0.0,
             rng: jax.Array | None = None):
    """prompt ``[B, Tp]`` -> ``[B, Tp + max_new_tokens]``: each row's prompt
    but its last token goes through ``prefill_chunk`` (one chunk a row),
    then a ``lax.scan`` of ``decode_step_batch`` decodes greedily
    (temperature 0) or by temperature sampling - the path a replica takes."""
    prompt = jnp.asarray(prompt, jnp.int32)
    B, Tp = prompt.shape
    rng = jax.random.key(0) if rng is None else rng
    run = _loop(prefill_chunk, decode_step_batch, cfg, Tp, Tp + max_new_tokens,
                float(temperature))
    cache = init_cache(cfg, B, Tp + max_new_tokens)
    return jnp.concatenate([prompt, run(params, cache, prompt, rng).T], axis=1)


@functools.lru_cache(maxsize=32)
def _loop(prefill_chunk, decode_step_batch, cfg, Tp: int, total: int, temperature: float):
    def step(params, carry, pos):
        cache, tok, rng = carry
        B = tok.shape[0]
        logits, cache = decode_step_batch(
            cfg, params, cache, tok, jnp.full((B,), pos), jnp.ones((B,), bool))
        rng, sub = jax.random.split(rng)
        if temperature > 0:
            nxt = jax.random.categorical(sub, logits / temperature)
        else:
            nxt = jnp.argmax(logits, axis=-1)
        nxt = nxt.astype(jnp.int32)
        return (cache, nxt, rng), nxt

    def run(params, cache, prompt, rng):
        if Tp > 1:
            cache = jax.lax.fori_loop(
                0, prompt.shape[0],
                lambda b, c: prefill_chunk(
                    cfg, params, c, prompt[b, :Tp - 1], b, 0, Tp - 1),
                cache,
            )
        _, toks = jax.lax.scan(
            lambda c, p: step(params, c, p),
            (cache, prompt[:, Tp - 1], rng), jnp.arange(Tp - 1, total - 1),
        )
        return toks

    return jax.jit(run)
