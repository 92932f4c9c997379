"""AFMoE (``"model": "afmoe"``: arcee-ai's Trinity family): gated
grouped-query attention behind QK-norm over sliding-window and full layers
mixed, and whole expert layers under a sigmoid router, ONE PIPELINE STAGE of
the model served.

The only file that names the program's ``models/afmoe.py`` and the reference
``reference/afmoe_ref.py``.  A configuration's ``published`` group holds the
source's ``config.json`` keys whole; ``program`` the most positions a
session may hold and THE SHARE this chip has of the deployment the file
states - ``held_layers``, the published layers that live here, each whole
(every expert, the whole vocabulary); the file's top-level
``num_hidden_layers``, ``num_dense_layers`` and ``layer_types`` (the keys
``reduced`` lists) say the same of the stage: how many layers it has, how
many of them are leading dense ones, and their kinds.
"""

from __future__ import annotations

import copy

import jax
import jax.numpy as jnp

from benchmarks.reference import afmoe_ref

#: The source's keys the program's ``Config`` and the reference read as they
#: are (``layer_types`` beside them, as a tuple).
KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "moe_intermediate_size",
    "num_hidden_layers", "num_dense_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "sliding_window", "num_experts",
    "num_experts_per_tok", "num_shared_experts", "route_scale", "rms_norm_eps",
    "rope_theta",
)
#: What the program builds whatever the source says; a source that says
#: otherwise is another model.
FIXED = {"hidden_act": "silu", "score_func": "sigmoid", "route_norm": True,
         "mup_enabled": True, "n_group": 1, "topk_group": 1, "rope_scaling": None,
         "tie_word_embeddings": False}
#: What the seeded leaves are drawn with (``assumed`` in the file).
SEEDED = ("init_std", "router_std_factor", "expert_bias_std", "expert_down_factor")

#: The rehearsal's size: a dense sliding layer, a sliding and a full expert
#: layer at tiny widths, every one of 8 experts held, 2 a token
#: (``route_scale`` as published: a choice weighs 1.4, the shared expert 1).
#: THE WINDOW IS 128, UNDER THE MIX'S PROMPTS (an eighth of the cell's:
#: median 384, to 1920): a ring of 128 + 512 rows wraps in the sessions past
#: 640 positions, and most sessions see less than their whole prompt.  The
#: limit was read at this size on the CPU (tests/test_benchmark_families.py
#: rehearses the cell; tests/test_afmoe.py holds the planted faults).
TINY_PUBLISHED = {
    "vocab_size": 1000, "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_hidden_layers": 3, "num_dense_layers": 1,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "layer_types": ["sliding_attention", "sliding_attention", "full_attention"],
    "sliding_window": 128, "num_experts": 8, "num_experts_per_tok": 2,
    "global_attn_every_n_layers": 3,
}
TINY_PROGRAM = {"max_seq_len": 2048, "held_layers": [0, 1, 2]}
TINY_LIMITS = {"widest_gap": 0.005}


def stage(config: dict) -> dict:
    """What the top-level keys that ``reduced`` lists must say of the stage
    ``program.held_layers`` names."""
    pub, held = config["published"], config["program"]["held_layers"]
    return {
        "num_hidden_layers": len(held),
        "num_dense_layers": sum(i < pub["num_dense_layers"] for i in held),
        "layer_types": [pub["layer_types"][i] for i in held],
    }


def sizes(config: dict) -> dict:
    """The shape of the model and of the stage, as the program's ``Config``
    and the reference both take them."""
    pub = config["published"]
    for key, value in FIXED.items():
        if pub.get(key, value) != value:
            raise ValueError(f"the afmoe family builds {key} = {value!r}, "
                             f"the configuration says {pub[key]!r}")
    for key, value in stage(config).items():
        if key in config and config[key] != value:
            raise ValueError(
                f"the configuration's {key} = {config[key]!r} and its "
                f"program.held_layers state two stages ({value!r})")
    return {
        **{k: pub[k] for k in KEYS}, "layer_types": tuple(pub["layer_types"]),
        "held_layers": tuple(config["program"]["held_layers"]),
        **{k: config["assumed"][k] for k in SEEDED},
    }


def build(config: dict, overrides: dict | None = None):
    """``(cfg, tree_fn)``: the program's ``Config`` and the seeded-weights
    builder ``tree_fn(key) -> params`` of the tree it serves, in the type
    the configuration holds parameters in."""
    from distributed_tensorflow_examples_tpu import models

    c = sizes(config)
    dtype = config["precision"]["params"]
    shape = {k: v for k, v in c.items() if k not in SEEDED}
    cfg = models.afmoe.Config(**shape, param_dtype=dtype, **(overrides or {}))
    # Each leaf rounded once to bfloat16, as the reference rounds it, THEN
    # held in the parameters' type: the rehearsal's float32 tree has the
    # reference's values to the bit.
    return cfg, lambda key: jax.tree.map(
        lambda a: a.astype(jnp.dtype(dtype)), afmoe_ref.tree(c, key))


def apply_fn(cfg):
    from distributed_tensorflow_examples_tpu import models

    return lambda p, b: models.afmoe.apply(cfg, p, b["x"])


def decode_fns(cfg):
    from distributed_tensorflow_examples_tpu import models

    return models.afmoe.serve_decode_fns(cfg)


def max_len(config: dict) -> int:
    return config["program"]["max_seq_len"]


def token_vocab(config: dict) -> int:
    return config["published"]["vocab_size"]


def reference_logits_at(config: dict, seed: int, tokens, rows, cols,
                        mode: str = "float32"):
    return afmoe_ref.logits_at(sizes(config), seed, tokens, rows, cols, mode)


def param_counts(config: dict) -> dict:
    """Parameters, from shapes: a layer's attention (``q``, ``k``, ``v``,
    the output gate, ``o`` and the two head norms), the dense feed-forward,
    the shared expert, the router (kernel and bias), one routed expert, an
    expert layer but for its routed experts, the dense layer, and the top
    (table, head, final norm)."""
    c = sizes(config)
    D, H, KV, hd = (c["hidden_size"], c["num_attention_heads"],
                    c["num_key_value_heads"], c["head_dim"])
    F = c["moe_intermediate_size"]
    attn = 3 * D * H * hd + 2 * D * KV * hd + 2 * hd
    dense = 3 * D * c["intermediate_size"]
    shared = 3 * D * c["num_shared_experts"] * F
    router = (D + 1) * c["num_experts"]
    return {
        "attn": attn, "dense": dense, "shared": shared, "router": router,
        "expert": 3 * D * F,
        "moe_layer": attn + shared + router + 4 * D,
        "dense_layer": attn + dense + 4 * D,
        "top": 2 * c["vocab_size"] * D + D,
    }


def _layers(c: dict) -> tuple[int, int]:
    """``(dense layers, expert layers)`` of the stage."""
    n_dense = sum(afmoe_ref.is_dense(c, i) for i in c["held_layers"])
    return n_dense, len(c["held_layers"]) - n_dense


def _kinds(c: dict) -> tuple[int, int]:
    """``(sliding layers, full layers)`` of the stage."""
    n_full = sum(c["layer_types"][i] == afmoe_ref.FULL for i in c["held_layers"])
    return len(c["held_layers"]) - n_full, n_full


def share_counts(config: dict) -> dict:
    """What the chip holds: parameters outside the routed experts, in them,
    a position's bytes in one layer's cache (keys and values of the K/V
    heads), and what a slot's cache takes by kind of layer as the program
    lays it out - a ring a sliding layer, ``max_seq_len`` rows a full one
    (models/afmoe.py ``Config.cache_rows``)."""
    c, per = sizes(config), param_counts(config)
    width = jnp.dtype(config["precision"]["params"]).itemsize
    n_dense, n_moe = _layers(c)
    row = 2 * c["num_key_value_heads"] * c["head_dim"] * width
    cfg, _ = build(config)
    rows = sum(cfg.cache_rows(i, max_len(config)) for i in cfg.layers)
    return {
        "non_expert": n_dense * per["dense_layer"] + n_moe * per["moe_layer"] + per["top"],
        "experts": n_moe * c["num_experts"] * per["expert"],
        "cache_bytes_per_position": row,
        "cache_bytes_per_slot": rows * row,
    }


def decode_step_bytes(config: dict, *, slots: int, cache_rows: float) -> float:
    """A FLOOR on the bytes one batched decode step moves, whatever the
    routing and however the seated sessions' depths lie: every parameter
    outside the ROUTED experts once in the type the configuration holds them
    in - the layers with their attention, router and shared expert, the
    dense feed-forward, the head and the final norm, and of the table the
    ``slots`` rows the embedding gathers - plus the key/value rows the
    seated sessions NEED, BY KIND of layer: a full layer every row written
    so far (``cache_rows``, summed over the sessions); a sliding layer
    ``min(rows, sliding_window)`` of each session, of which only the sum is
    known here - so the least that sessions of at most ``max_seq_len``
    positions holding ``cache_rows`` between them can need (the rows in as
    few sessions as hold them).  NO routed-expert byte: this function is not
    told how many experts a step touched, and they are most of what a step
    reads here (a step's hundred live choices touch most of 128 experts a
    layer, 12.6 MB each).  ``decode_roofline_share`` is therefore a floor in
    this family's cells too; ``expert_call_bytes`` and the
    ``expert_roofline`` reader carry the experts."""
    c, per = sizes(config), param_counts(config)
    width = jnp.dtype(config["precision"]["params"]).itemsize
    D, W, T = c["hidden_size"], c["sliding_window"], max_len(config)
    n_dense, n_moe = _layers(c)
    n_sliding, n_full = _kinds(c)
    params = (n_dense * per["dense_layer"] + n_moe * per["moe_layer"]
              + c["vocab_size"] * D + D + slots * D)
    window_rows = (cache_rows // T) * min(W, T) + min(cache_rows % T, W)
    row = share_counts(config)["cache_bytes_per_position"]
    return params * width + (n_full * cache_rows + n_sliding * window_rows) * row


def expert_call_bytes(config: dict, touched: float, rows: float) -> float:
    """Least bytes one call of the grouped feed-forward kernel moves when
    ``touched`` of the experts have rows, ``rows`` in all: each touched
    expert's three matrices once, the rows read in the parameters' type and
    their results written in float32."""
    c, per = sizes(config), param_counts(config)
    width = jnp.dtype(config["precision"]["params"]).itemsize
    return touched * per["expert"] * width + rows * c["hidden_size"] * (width + 4)


def expert_call_flops(config: dict, rows: float) -> float:
    """Operations of one call for ``rows`` rows: three products a row, a
    multiply-add two operations."""
    return rows * 2 * param_counts(config)["expert"]


def tiny(config: dict) -> dict:
    """The configuration at the rehearsal's size, with the limits read at
    it under ``rehearsal``."""
    out = copy.deepcopy(config)
    out["published"].update(TINY_PUBLISHED)
    out["program"] = copy.deepcopy(TINY_PROGRAM)
    out.update(stage(out))
    # 1 / sqrt(64): at the tiny width a product of unit inputs has unit size
    # (reference/afmoe_ref.py ``init_std``).
    out["assumed"]["init_std"] = 0.125
    # Held in float32 at this size, as families/longcat/serve.py ``tiny``
    # has it and for its reason (XLA's CPU backend rewrites a whole bfloat16
    # buffer for every row written into it).  The leaves are the same
    # bfloat16 roundings (``build``); tests/test_afmoe.py runs the bfloat16
    # program.
    out["precision"] = dict(out["precision"], params="float32")
    out["rehearsal"] = {"limits": dict(TINY_LIMITS)}
    return out
