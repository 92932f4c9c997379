"""Custom ops: Pallas TPU kernels with XLA reference fallbacks.

The reference's "custom native op" path is hand-written C++ kernels compiled
into libtensorflow (SURVEY.md D11/D12).  The TPU-native equivalent is Pallas:
kernels lower through Mosaic to real TPU code, while a pure-XLA reference
implementation of each op serves CPU tests and autodiff checks.
"""

from . import attention  # noqa: F401
from . import flash_attention  # noqa: F401
from . import selective_scan  # noqa: F401
from . import grouped_ffn  # noqa: F401
from . import latent_decode  # noqa: F401
from . import latent_prefill  # noqa: F401
