"""Generation for a model served by chunks and steps: the loop a replica
runs, as one compiled program.

A model that has ``init_cache(cfg, slots, max_len)``, ``prefill_chunk(cfg,
params, cache, tokens, slot, offset, n_valid)`` and ``decode_step_batch(cfg,
params, cache, tokens, pos, live)`` (models/longcat.py, models/deepseek.py)
generates with :func:`generate`; it names no model.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


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
