"""Jamba (``"model": "jamba"``): Mamba-1 layers beside attention layers,
served.

The only file that names the program's ``models/jamba.py`` and the reference
``reference/jamba_ref.py``.  A configuration's ``published`` group holds the
source's ``config.json`` keys (the program's ``Config`` takes them under
their own names), ``assumed`` the head size the source leaves out,
``program`` the most positions a session may hold in this deployment.
"""

from __future__ import annotations

import copy

import jax.numpy as jnp

from benchmarks.reference import jamba_ref

#: The source's keys the program's ``Config`` and the reference read.
KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers", "attn_layer_period",
    "attn_layer_offset", "num_attention_heads", "num_key_value_heads",
    "intermediate_size", "mamba_d_state", "mamba_d_conv", "mamba_dt_rank",
    "mamba_expand", "rms_norm_eps", "tie_word_embeddings",
)
#: What the program builds whatever the source says; a source that says
#: otherwise is another model.
FIXED = {"mamba_conv_bias": True, "mamba_proj_bias": False, "num_experts": 1,
         "hidden_act": "silu", "sliding_window": None}

#: The rehearsal's size: one period of four layers with its attention layer
#: at offset 2, and the limit read at it on the CPU over five seeds
#: (benchmarks/tests/test_jamba_family.py runs three of them): the reference
#: in bfloat16 reads 0.008-0.020, the fp8 control 0.47-0.79.
TINY_PUBLISHED = {
    "vocab_size": 250, "hidden_size": 64, "num_hidden_layers": 4,
    "attn_layer_period": 4, "attn_layer_offset": 2, "num_attention_heads": 4,
    "num_key_value_heads": 1, "intermediate_size": 128, "mamba_d_state": 16,
    "mamba_d_conv": 4, "mamba_dt_rank": 8, "mamba_expand": 2,
}
TINY_LIMITS = {"widest_gap": 0.1}


def sizes(config: dict) -> dict:
    """The shape of the model: the source's keys and the assumed head size,
    as the program's ``Config`` and the reference both take them."""
    pub = config["published"]
    for key, value in FIXED.items():
        if pub.get(key, value) != value:
            raise ValueError(f"the jamba family builds {key} = {value!r}, "
                             f"the configuration says {pub[key]!r}")
    assumed = config["assumed"]
    return {**{k: pub[k] for k in KEYS}, "head_dim": assumed["head_dim"],
            "init_std": assumed["init_std"]}


def build(config: dict, overrides: dict | None = None):
    """``(cfg, tree_fn)``: the program's ``Config`` and the seeded-weights
    builder ``tree_fn(key) -> params`` of the tree it serves, in the type
    the configuration holds parameters in."""
    from distributed_tensorflow_examples_tpu import models

    c = sizes(config)
    dtype = config["precision"]["params"]
    shape = {k: v for k, v in c.items() if k != "init_std"}
    cfg = models.jamba.Config(**shape, param_dtype=dtype, **(overrides or {}))
    return cfg, lambda key: jamba_ref.tree(c, key, jnp.dtype(dtype))


def apply_fn(cfg):
    from distributed_tensorflow_examples_tpu import models

    return lambda p, b: models.jamba.apply(cfg, p, b["x"])


def decode_fns(cfg):
    from distributed_tensorflow_examples_tpu import models

    return models.jamba.serve_decode_fns(cfg)


def max_len(config: dict) -> int:
    return config["program"]["max_seq_len"]


def token_vocab(config: dict) -> int:
    return config["published"]["vocab_size"]


def reference_logits_at(config: dict, seed: int, tokens, rows, cols,
                        mode: str = "float32"):
    return jamba_ref.logits_at(sizes(config), seed, tokens, rows, cols, mode)


def param_counts(config: dict) -> dict:
    """Parameters of one Mamba layer, one attention layer and the top (the
    table, which is the head too, and the final norm), from shapes."""
    c = sizes(config)
    D, F = c["hidden_size"], c["intermediate_size"]
    Di, N = c["mamba_expand"] * D, c["mamba_d_state"]
    R, K = c["mamba_dt_rank"], c["mamba_d_conv"]
    H, KV, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    shared = 2 * D + 3 * D * F
    mamba = (D * 2 * Di + K * Di + Di + Di * (R + 2 * N) + R + 2 * N
             + R * Di + Di + N * Di + Di + Di * D)
    attention = D * H * hd + 2 * D * KV * hd + H * hd * D
    return {"mamba": shared + mamba, "attention": shared + attention,
            "top": c["vocab_size"] * D + D}


def layer_counts(config: dict) -> dict:
    kinds = jamba_ref.layer_kinds(sizes(config))
    return {k: kinds.count(k) for k in ("mamba", "attention")}


def state_bytes_per_slot(config: dict) -> int:
    """What one session owns whatever its length: every Mamba layer's state
    ``[N, d_inner]`` and conv tail ``[d_conv - 1, d_inner]``, float32."""
    c = sizes(config)
    Di = c["mamba_expand"] * c["hidden_size"]
    per_layer = (c["mamba_d_state"] + c["mamba_d_conv"] - 1) * Di * 4
    return layer_counts(config)["mamba"] * per_layer


def decode_step_bytes(config: dict, *, slots: int, cache_rows: float) -> float:
    """Least bytes one batched decode step moves: every parameter once in
    the type the configuration holds them in - the table once, because it is
    the head (the ``slots`` rows the embedding gathers are part of it) -;
    the keys and values written so far of the seated sessions (the attention
    layers' K/V heads, in the parameters' type); and the state of EVERY
    slot read once and written once.  Every slot, not the live rows alone:
    the step's program has one fixed shape and is handed all the slots'
    state, and the count has no way to know how many rows were live - at
    the load of the cell that reads this (some 25 of 32 slots taken) that
    overstates the least by about 2 % of the step's bytes."""
    c = sizes(config)
    width = jnp.dtype(config["precision"]["params"]).itemsize
    n, per = layer_counts(config), param_counts(config)
    params = n["mamba"] * per["mamba"] + n["attention"] * per["attention"] + per["top"]
    row = n["attention"] * 2 * c["num_key_value_heads"] * c["head_dim"] * width
    return params * width + cache_rows * row + 2 * slots * state_bytes_per_slot(config)


def scan_chunk_bytes(config: dict, chunk: int) -> int:
    """Least bytes one call of the selective-scan kernel moves for a chunk
    of ``chunk`` tokens of one slot, from shapes, all float32: ``x`` and
    ``dt [chunk, d_inner]``, ``B`` and ``C [chunk, N]``, ``A [N, d_inner]``,
    ``D [d_inner]`` and the carried state read once; ``y [chunk, d_inner]``
    and the state written once."""
    c = sizes(config)
    Di, N = c["mamba_expand"] * c["hidden_size"], c["mamba_d_state"]
    return 4 * (3 * chunk * Di + 2 * chunk * N + 3 * N * Di + Di)


def scan_chunk_vector_ops(config: dict, chunk: int) -> dict:
    """What the kernel's vector unit does for one chunk, from shapes: per
    time step, channel and state dimension one ``exp`` and six multiplies or
    adds (``dt A``, ``exp * h``, ``u B``, the sum, ``h C``, the sum into
    ``y``); per step and channel two more (``dt x``, ``D x``)."""
    c = sizes(config)
    Di, N = c["mamba_expand"] * c["hidden_size"], c["mamba_d_state"]
    return {"exp": chunk * Di * N, "mul_add": chunk * Di * (6 * N + 2)}


def tiny(config: dict) -> dict:
    """The configuration at the rehearsal's size, with the limits read at
    it under ``rehearsal``."""
    out = copy.deepcopy(config)
    out["published"].update(TINY_PUBLISHED)
    # 1 / sqrt(64): at the tiny width the layers weigh what they weigh at
    # the published one (reference/jamba_ref.py ``init_std``).
    out["assumed"].update(head_dim=16, init_std=0.125)
    out["program"] = {"max_seq_len": 128}
    out["rehearsal"] = {"limits": dict(TINY_LIMITS)}
    return out
