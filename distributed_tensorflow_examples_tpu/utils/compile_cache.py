"""Where the persistent XLA compilation cache lives.

Every chip-tool call starts on a fresh machine, and the flagship train step,
the decode step and ``generate`` take tens of seconds each to compile, so
every process entry point that compiles for the chip calls
:func:`enable` before its first compile.  The rule:

- ``JAX_COMPILATION_CACHE_DIR`` set in the environment: JAX reads the
  variable itself; :func:`enable` touches nothing.
- unset: the cache goes to ONE fixed directory inside the checkout
  (:data:`DEFAULT_DIR`, derived from this file's location — the directory
  is part of what the cache keys on, so it must not move between runs:
  never a temp dir, a pid, a timestamp or the cwd), and only once the
  platform is known to be ``tpu`` — CPU runs (the tier-1 suite, CLI tests
  in subprocesses) must not fill the checkout, which the chip tool copies
  whole, with CPU cache entries.

This module is the only place in the tree that sets a cache path.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: ``<checkout>/.jax_cache`` (listed in .gitignore and .chiprunignore).
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable() -> str | None:
    """Place the compilation cache (see the module docstring); returns the
    directory in use, or None when this process compiles uncached (no
    environment setting and not on a TPU).  Call after any
    ``jax.distributed`` bootstrap and before the first compile; idempotent.
    """
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    import jax

    if jax.default_backend() != "tpu":
        return None
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
