"""The GPT-2-style transformer (``"model": "transformer"``), served.

The only file that names the program's ``models/transformer.py`` and the
reference ``reference/transformer_ref.py`` on the serve path.  A
configuration's ``program`` group holds the arguments of the program's
``Config``; ``published`` the source's keys.
"""

from __future__ import annotations

import copy

import jax.numpy as jnp

from benchmarks.reference import transformer_ref, weights

#: The rehearsal's size, and the limit read at it on the CPU
#: (benchmarks/tests/test_control.py holds the readings): above the sound
#: runs, below the int8 control.
TINY_PROGRAM = {
    "vocab_size": 256, "dim": 64, "n_layers": 2, "n_heads": 4, "mlp_ratio": 4,
    "max_seq_len": 128,
}
TINY_LIMITS = {"widest_gap": 0.006}


def build(config: dict, overrides: dict | None = None):
    """``(cfg, tree_fn)``: the program's ``Config`` and the seeded-weights
    builder ``tree_fn(key) -> params`` of the same tree."""
    from distributed_tensorflow_examples_tpu import models

    c = dict(config["program"])
    cfg = models.transformer.Config(**c, **(overrides or {}))
    return cfg, lambda key: weights.transformer_tree(c, key)


def apply_fn(cfg):
    """``predict_fn(params, batch)`` of the replica."""
    from distributed_tensorflow_examples_tpu import models

    return lambda p, b: models.transformer.apply(cfg, p, b["x"])


def decode_fns(cfg):
    from distributed_tensorflow_examples_tpu import models

    return models.transformer.serve_decode_fns(cfg)


def max_len(config: dict) -> int:
    return config["program"]["max_seq_len"]


def token_vocab(config: dict) -> int:
    """Tokens are drawn from the published vocabulary, not the padded one."""
    return config["published"]["vocab_size"]


def reference_logits_at(config: dict, seed: int, tokens, rows, cols,
                        mode: str = "float32"):
    return transformer_ref.logits_at(config["program"], seed, tokens, rows, cols, mode)


def decode_step_bytes(config: dict, *, slots: int, cache_rows: float) -> float:
    """Least bytes one batched decode step reads: the blocks, final norm
    and head once, ``slots`` rows of the embedding and position tables, in
    the type the configuration holds parameters in, and the keys and values
    written so far (bf16) of the seated sessions."""
    c = config["program"]
    param_bytes = jnp.dtype(config["precision"]["params"]).itemsize
    D, H = c["dim"], c["dim"] * c["mlp_ratio"]
    block = 3 * D * D + D * D + 2 * D * H + H + D + 4 * D
    read_params = c["n_layers"] * block + 2 * D + D * c["vocab_size"] + 2 * slots * D
    cache = cache_rows * c["n_layers"] * 2 * D * 2
    return read_params * param_bytes + cache


def tiny(config: dict) -> dict:
    """The configuration at the rehearsal's size, with the limits read at
    it under ``rehearsal``."""
    out = copy.deepcopy(config)
    out["program"] = dict(TINY_PROGRAM)
    out["published"]["vocab_size"] = 250
    out["rehearsal"] = {"limits": dict(TINY_LIMITS)}
    return out
