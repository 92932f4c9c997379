"""Small shared helpers for the custom-op modules: the block-size pick, and
the platform test and compiler parameters every Pallas kernel uses."""

from __future__ import annotations

import jax
from jax.experimental.pallas import tpu as pltpu


def largest_divisor(n: int, want: int) -> int:
    """Largest divisor of ``n`` that is <= ``want`` (>= 1).  The common core
    of every block/tile/group-size pick in ops/ — kernels layer their own
    policy (MXU-alignment warnings, shard-multiple constraints) on top."""
    b = max(1, min(n, want))
    while n % b:
        b -= 1
    return b


def interpret_mode() -> bool:
    """Whether the Pallas kernels run interpreted: True on the ``cpu``
    platform only, where a caller reaches a kernel by asking for it
    (``attention="flash"``, ``impl="flash"``, a direct call — the auto
    dispatch never picks it there, see ``flash_viable``).  On ``tpu`` the
    kernels compile through Mosaic or the call raises; any other platform
    is refused rather than interpreted under a kernel's name.  The platform
    is the one jit places this computation's arrays on
    (``jax.default_backend()``).  The ONE platform test every Pallas kernel
    in the package uses."""
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise NotImplementedError(
        f"Pallas TPU kernels compile on 'tpu' and interpret on 'cpu'; "
        f"platform {platform!r} is neither"
    )


def compiler_params(semantics: tuple[str, ...], vmem_limit_bytes: int | None = None):
    """The ONE spelling every TPU kernel in the package uses."""
    return pltpu.CompilerParams(
        dimension_semantics=semantics, vmem_limit_bytes=vmem_limit_bytes
    )
