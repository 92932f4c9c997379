"""SmallThinker (``"model": "smallthinker"``: PowerInfer's SmallThinker
family): a router that reads its layer's input before attention, whole
layers of ReLU-gated experts with nothing beside them, one global layer
without positional encoding to three rotary window layers, THE FIRST
PIPELINE STAGE of the model served.

The only file that names the program's ``models/smallthinker.py`` and the
reference ``reference/smallthinker_ref.py``.  A configuration's ``published``
group holds the source's ``config.json`` keys whole; ``program`` the most
positions a session may hold and THE SHARE this chip has of the deployment
the file states - ``held_layers``, the published layers that live here, each
whole (every expert, the whole vocabulary); the file's top-level
``num_hidden_layers``, ``sliding_window_layout`` and ``rope_layout`` (the
keys ``reduced`` lists) say the same of the stage: how many layers it has
and their kinds.
"""

from __future__ import annotations

import copy

import jax
import jax.numpy as jnp

from benchmarks.reference import smallthinker_ref

#: The source's keys the program's ``Config`` and the reference read as they
#: are (the two layouts beside them, as tuples).
KEYS = (
    "vocab_size", "hidden_size", "moe_ffn_hidden_size", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "head_dim",
    "sliding_window_size", "moe_num_primary_experts",
    "moe_num_active_primary_experts", "rms_norm_eps", "rope_theta",
)
LAYOUTS = ("sliding_window_layout", "rope_layout")
#: What the program builds whatever the source says; a source that says
#: otherwise is another model.
FIXED = {"moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
         "rope_scaling": None, "tie_word_embeddings": False}
#: What the seeded leaves are drawn with (``assumed`` in the file).
SEEDED = ("init_std", "router_spread", "out_std_factor")

#: The rehearsal's size: the first TWO layers at tiny widths - the global
#: layer FIRST, then a window layer (a whole period steps twice as slowly,
#: and with answers of 24-192 tokens no session then finishes inside a
#: rehearsal's seconds on a loaded CPU; tests/test_smallthinker.py runs two
#: whole periods) - 7 query heads a K/V head, every one of 8 experts held, 2
#: a token.  THE WINDOW IS 128, UNDER THE MIX'S PROMPTS (an eighth of the
#: cell's: median 256, to 1536): a ring of 128 + 512 rows wraps in the
#: sessions past 640 positions, and most sessions see less than their whole
#: prompt in the window layer.  A tiny width is under a
#: block of the grouped product and taken whole: tests/test_grouped_ffn.py
#: walks the narrower blocks at 2560 and 768.  The limit was read at this
#: size on the CPU (tests/test_benchmark_families.py rehearses the cell;
#: tests/test_smallthinker.py holds the planted faults).
TINY_PUBLISHED = {
    "vocab_size": 1000, "hidden_size": 64, "moe_ffn_hidden_size": 32,
    "num_hidden_layers": 2, "num_attention_heads": 14, "num_key_value_heads": 2,
    "head_dim": 16, "sliding_window_layout": [0, 1], "rope_layout": [0, 1],
    "sliding_window_size": 128, "moe_num_primary_experts": 8,
    "moe_num_active_primary_experts": 2,
}
TINY_PROGRAM = {"max_seq_len": 2048, "held_layers": [0, 1]}
TINY_LIMITS = {"widest_gap": 0.005}


def stage(config: dict) -> dict:
    """What the top-level keys that ``reduced`` lists must say of the stage
    ``program.held_layers`` names."""
    pub, held = config["published"], config["program"]["held_layers"]
    return {"num_hidden_layers": len(held),
            **{k: [pub[k][i] for i in held] for k in LAYOUTS}}


def sizes(config: dict) -> dict:
    """The shape of the model and of the stage, as the program's ``Config``
    and the reference both take them."""
    pub = config["published"]
    for key, value in FIXED.items():
        if pub.get(key, value) != value:
            raise ValueError(f"the smallthinker family builds {key} = {value!r}, "
                             f"the configuration says {pub[key]!r}")
    for key, value in stage(config).items():
        if key in config and config[key] != value:
            raise ValueError(
                f"the configuration's {key} = {config[key]!r} and its "
                f"program.held_layers state two stages ({value!r})")
    return {
        **{k: pub[k] for k in KEYS}, **{k: tuple(pub[k]) for k in LAYOUTS},
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
    cfg = models.smallthinker.Config(**shape, param_dtype=dtype, **(overrides or {}))
    # Each leaf rounded once to bfloat16, as the reference rounds it, THEN
    # held in the parameters' type: the rehearsal's float32 tree has the
    # reference's values to the bit.
    return cfg, lambda key: jax.tree.map(
        lambda a: a.astype(jnp.dtype(dtype)), smallthinker_ref.tree(c, key))


def apply_fn(cfg):
    from distributed_tensorflow_examples_tpu import models

    return lambda p, b: models.smallthinker.apply(cfg, p, b["x"])


def decode_fns(cfg):
    from distributed_tensorflow_examples_tpu import models

    return models.smallthinker.serve_decode_fns(cfg)


def max_len(config: dict) -> int:
    return config["program"]["max_seq_len"]


def token_vocab(config: dict) -> int:
    return config["published"]["vocab_size"]


def reference_logits_at(config: dict, seed: int, tokens, rows, cols,
                        mode: str = "float32"):
    return smallthinker_ref.logits_at(sizes(config), seed, tokens, rows, cols, mode)


def param_counts(config: dict) -> dict:
    """Parameters, from shapes: a layer's attention (``q``, ``k``, ``v``,
    ``o``), the router, one expert (three matrices), a layer but for its
    experts (attention, router, two norms), and the top (table, head, final
    norm)."""
    c = sizes(config)
    D, H, KV, hd = (c["hidden_size"], c["num_attention_heads"],
                    c["num_key_value_heads"], c["head_dim"])
    attn = 2 * D * H * hd + 2 * D * KV * hd
    router = D * c["moe_num_primary_experts"]
    return {
        "attn": attn, "router": router,
        "expert": 3 * D * c["moe_ffn_hidden_size"],
        "moe_layer": attn + router + 2 * D,
        "top": 2 * c["vocab_size"] * D + D,
    }


def _kinds(c: dict) -> tuple[int, int]:
    """``(window layers, global layers)`` of the stage."""
    n_window = sum(c["sliding_window_layout"][i] for i in c["held_layers"])
    return n_window, len(c["held_layers"]) - n_window


def _row_bytes(config: dict) -> int:
    """A position's bytes in one layer's cache: keys and values of the K/V
    heads in the parameters' type."""
    pub = config["published"]
    width = jnp.dtype(config["precision"]["params"]).itemsize
    return 2 * pub["num_key_value_heads"] * pub["head_dim"] * width


def share_counts(config: dict) -> dict:
    """What the chip holds: parameters outside the experts, in them, a
    position's bytes in one layer's cache (keys and values of the K/V
    heads), and what a slot's cache takes by kind of layer as the program
    lays it out - a ring a window layer, ``max_seq_len`` rows a global one
    (models/smallthinker.py ``Config.cache_rows``)."""
    c, per = sizes(config), param_counts(config)
    n_layers = len(c["held_layers"])
    row = _row_bytes(config)
    cfg, _ = build(config)
    rows = sum(cfg.cache_rows(i, max_len(config)) for i in cfg.layers)
    return {
        "non_expert": n_layers * per["moe_layer"] + per["top"],
        "experts": n_layers * c["moe_num_primary_experts"] * per["expert"],
        "cache_bytes_per_position": row,
        "cache_bytes_per_slot": rows * row,
    }


def decode_step_bytes(config: dict, *, slots: int, cache_rows: float) -> float:
    """A FLOOR on the bytes one batched decode step moves, whatever the
    routing and however the seated sessions' depths lie: every parameter
    outside the EXPERTS once in the type the configuration holds them in -
    the layers' attention, router and norms, the head and the final norm,
    and of the table the ``slots`` rows the embedding gathers - plus the
    key/value rows the seated sessions NEED, BY KIND of layer: a global
    layer every row written so far (``cache_rows``, summed over the
    sessions); a window layer ``min(rows, sliding_window_size)`` of each
    session, of which only the sum is known here - so the least that
    sessions of at most ``max_seq_len`` positions holding ``cache_rows``
    between them can need (the rows in as few sessions as hold them).  NO
    expert's byte: this function is not told how many experts a step
    touched, and they are most of what a step reads here (a step's 26 live
    rows touch some 59 of 64 experts a layer, 11.8 MB each, with nothing
    beside them).  ``decode_roofline_share`` is therefore a floor in this
    family's cells too; ``expert_call_bytes`` and the ``expert_roofline``
    reader carry the experts."""
    c, per = sizes(config), param_counts(config)
    width = jnp.dtype(config["precision"]["params"]).itemsize
    D, W, T = c["hidden_size"], c["sliding_window_size"], max_len(config)
    n_window, n_global = _kinds(c)
    params = (len(c["held_layers"]) * per["moe_layer"]
              + c["vocab_size"] * D + D + slots * D)
    window_rows = (cache_rows // T) * min(W, T) + min(cache_rows % T, W)
    row = _row_bytes(config)
    return params * width + (n_global * cache_rows + n_window * window_rows) * row


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
    # (reference/smallthinker_ref.py ``init_std``).
    out["assumed"]["init_std"] = 0.125
    # ... and an expert's write as large beside the table row as at the
    # published width: sqrt(ffn / 2) x out_std_factor = 0.25 at both.
    out["assumed"]["out_std_factor"] = 0.064
    # Held in float32 at this size, as families/longcat/serve.py ``tiny``
    # has it and for its reason (XLA's CPU backend rewrites a whole bfloat16
    # buffer for every row written into it).  The leaves are the same
    # bfloat16 roundings (``build``).
    out["precision"] = dict(out["precision"], params="float32")
    out["rehearsal"] = {"limits": dict(TINY_LIMITS)}
    return out
