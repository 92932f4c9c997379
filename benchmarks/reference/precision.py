"""The precisions a reference can compute a product in.

``float32`` is the reference proper (six-pass products on a TPU).  The
others are the controls: the reference put in the program's place and
computed in the precision a later PR would be tempted by.  ``int8`` is the
plain W8A8 fake quantisation, one absmax scale per tensor, straight-through
in the backward pass; ``fp8`` rounds both operands to float8 (e4m3) under
one absmax scale per tensor.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _fake_int8(x):
    scale = jnp.max(jnp.abs(x)) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(x / scale), -127, 127) * scale
    return x + jax.lax.stop_gradient(q - x)


def _fake_fp8(x):
    scale = jnp.max(jnp.abs(x)) / 448.0
    scale = jnp.where(scale == 0, 1.0, scale)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def operands(x, w, mode: str):
    """Both operands of a product as ``mode`` would hold them."""
    if mode == "float32":
        return x, w
    if mode == "bfloat16":
        return (x.astype(jnp.bfloat16).astype(jnp.float32),
                w.astype(jnp.bfloat16).astype(jnp.float32))
    if mode == "int8":
        return _fake_int8(x), _fake_int8(w)
    if mode == "fp8":
        return _fake_fp8(x), _fake_fp8(w)
    raise ValueError(f"unknown precision {mode!r}")


def matmul(x, w, mode: str):
    x, w = operands(x, w, mode)
    return jnp.matmul(x, w, precision=HIGHEST)
