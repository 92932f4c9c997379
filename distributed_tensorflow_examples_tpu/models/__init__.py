"""Model zoo: the five reference workload models (SURVEY.md section 2a)
plus framework growth models.

All models are *pure functional*: ``init(cfg, rng) -> params`` and
``apply(cfg, params, ...) -> outputs`` over plain dict pytrees — no module
objects, no tracing magic.  This keeps every parameter addressable by path for
sharding rules (``parallel.sharding``) and makes the whole train step a single
traced function XLA can fuse end-to-end.

- ``mlp``      — W1 MNIST MLP (ref: sync PS/worker, SyncReplicasOptimizer)
- ``cnn``      — W2 CIFAR-10 CNN (ref: async parameter-server)
- ``resnet``   — W3 ResNet-50 ImageNet (ref: MirroredStrategy/NCCL)
- ``word2vec`` — W4 skip-gram with mesh-sharded embedding (ref: PS-sharded)
- ``lstm``     — W5 PTB LSTM LM (ref: MultiWorkerMirroredStrategy)
- ``transformer`` — GPT-2-style decoder LM, trained and served
- ``jamba``    — Mamba-1 + attention hybrid (AI21 Jamba), served
- ``longcat``  — latent attention + a chip's share of a dropless expert
  layer with zero-compute experts (Meituan LongCat-Flash), served
- ``deepseek`` — latent attention with scaled rotary positions, a choice of
  experts limited to groups, shared experts (DeepSeek-V2), served
- ``afmoe``    — gated grouped-query attention behind QK-norm, a ring cache
  in the sliding-window layers beside full rows in the global ones, whole
  expert layers under a sigmoid router (arcee-ai Trinity), served
- ``smallthinker`` — a router that reads its layer's input before attention,
  whole layers of ReLU-gated experts, a NoPE global layer to three rotary
  window layers (PowerInfer SmallThinker), served
- ``nemotron_h`` — layers of one sub-layer each: Mamba-2 mixers whose state
  is a matrix a head, NoPE grouped-query attention, LatentMoE feed-forwards
  of ungated squared-ReLU experts in a narrower latent (NVIDIA Nemotron-H),
  served
- ``ring_cache`` — the slot cache with rings in the window layers and the two
  blocked attentions over it, which ``afmoe``, ``smallthinker`` and
  ``nemotron_h`` share
- ``mla``      — the latent-attention sub-layer ``longcat`` and ``deepseek``
  share
- ``decoding`` — what the served families share: the contract each hands
  the decode engine (``DecodeFns``) and the generate loop of a model served
  by chunks and steps
"""

from . import layers  # noqa: F401
from . import mlp  # noqa: F401
from . import cnn  # noqa: F401
from . import resnet  # noqa: F401
from . import word2vec  # noqa: F401
from . import lstm  # noqa: F401
from . import transformer  # noqa: F401
from . import jamba  # noqa: F401
from . import mla  # noqa: F401
from . import decoding  # noqa: F401
from . import longcat  # noqa: F401
from . import deepseek  # noqa: F401
from . import ring_cache  # noqa: F401
from . import afmoe  # noqa: F401
from . import smallthinker  # noqa: F401
from . import nemotron_h  # noqa: F401
