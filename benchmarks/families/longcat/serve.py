"""LongCat-Flash (``"model": "longcat"``): latent attention and one chip's
share of a dropless expert layer with zero-compute experts, served.

The only file that names the program's ``models/longcat.py`` and the
reference ``reference/longcat_ref.py``.  A configuration's ``published``
group holds the source's ``config.json`` keys whole; ``program`` the most
positions a session may hold and THE SHARE this chip has of the deployment
the file states - ``num_layers`` of the published depth, ``experts_held``
routed experts from ``expert_first`` on, ``vocab_rows`` of the vocabulary;
the file's top-level ``num_layers``, ``n_routed_experts`` and ``vocab_size``
(the keys ``reduced`` lists) say the same.  The router keeps its published
width: a choice on an expert that is not held adds nothing, in the program
and in the reference alike.
"""

from __future__ import annotations

import copy

import jax.numpy as jnp

from benchmarks.reference import longcat_ref

#: The source's keys the program's ``Config`` and the reference read.
KEYS = (
    "vocab_size", "hidden_size", "ffn_hidden_size", "expert_ffn_hidden_size",
    "num_layers", "num_attention_heads", "kv_lora_rank", "q_lora_rank",
    "qk_rope_head_dim", "qk_nope_head_dim", "v_head_dim", "mla_scale_q_lora",
    "mla_scale_kv_lora", "routed_scaling_factor", "n_routed_experts",
    "zero_expert_num", "moe_topk", "rms_norm_eps", "rope_theta",
)
#: What the program builds whatever the source says; a source that says
#: otherwise is another model.
FIXED = {"attention_bias": False, "attention_method": "MLA",
         "zero_expert_type": "identity"}
#: The share's keys in ``program`` and the top-level key each restates.
SHARE = {"num_layers": "num_layers", "experts_held": "n_routed_experts",
         "vocab_rows": "vocab_size"}

#: The rehearsal's size: two double layers at tiny widths, a quarter of a
#: tiny vocabulary - and THE ROUTER AS PUBLISHED, 512 + 256 outputs, 12
#: choices, 4 experts held: a choice's weight (``6 s_i``) is then the 0.06
#: it is at the real size, where among two dozen experts it would be 0.9
#: and one choice at a near-tie that bfloat16 turns the other way would
#: move a logit as far as fp8 moves it.  The limit was read at this size on
#: the CPU (benchmarks/tests/test_longcat_family.py holds the readings).
TINY_PUBLISHED = {
    "vocab_size": 1000, "hidden_size": 64, "ffn_hidden_size": 128,
    "expert_ffn_hidden_size": 32, "num_layers": 2, "num_attention_heads": 4,
    "kv_lora_rank": 32, "q_lora_rank": 48, "qk_rope_head_dim": 8,
    "qk_nope_head_dim": 16, "v_head_dim": 16,
}
TINY_PROGRAM = {"max_seq_len": 1024, "num_layers": 2, "experts_held": 4,
                "expert_first": 8, "vocab_rows": 250}
TINY_LIMITS = {"widest_gap": 0.2}


def sizes(config: dict) -> dict:
    """The shape of the model and of the share, as the program's ``Config``
    and the reference both take them."""
    pub, prog = config["published"], config["program"]
    for key, value in FIXED.items():
        if pub.get(key, value) != value:
            raise ValueError(f"the longcat family builds {key} = {value!r}, "
                             f"the configuration says {pub[key]!r}")
    for key, top in SHARE.items():
        if top in config and config[top] != prog[key]:
            raise ValueError(
                f"the configuration's {top} = {config[top]!r} and its "
                f"program.{key} = {prog[key]!r} state two shares")
    return {
        **{k: pub[k] for k in KEYS}, "num_layers": prog["num_layers"],
        "experts_held": prog["experts_held"], "expert_first": prog["expert_first"],
        "vocab_rows": prog["vocab_rows"], "init_std": config["assumed"]["init_std"],
    }


def build(config: dict, overrides: dict | None = None):
    """``(cfg, tree_fn)``: the program's ``Config`` and the seeded-weights
    builder ``tree_fn(key) -> params`` of the tree it serves, in the type
    the configuration holds parameters in."""
    from distributed_tensorflow_examples_tpu import models

    c = sizes(config)
    dtype = config["precision"]["params"]
    shape = {k: v for k, v in c.items() if k != "init_std"}
    cfg = models.longcat.Config(**shape, param_dtype=dtype, **(overrides or {}))
    return cfg, lambda key: longcat_ref.tree(c, key, jnp.dtype(dtype))


def apply_fn(cfg):
    from distributed_tensorflow_examples_tpu import models

    return lambda p, b: models.longcat.apply(cfg, p, b["x"])


def decode_fns(cfg):
    from distributed_tensorflow_examples_tpu import models

    return models.longcat.serve_decode_fns(cfg)


def max_len(config: dict) -> int:
    return config["program"]["max_seq_len"]


def token_vocab(config: dict) -> int:
    """The slice of the vocabulary that is here: ids are drawn from it."""
    return config["program"]["vocab_rows"]


def reference_logits_at(config: dict, seed: int, tokens, rows, cols,
                        mode: str = "float32"):
    return longcat_ref.logits_at(sizes(config), seed, tokens, rows, cols, mode)


def param_counts(config: dict) -> dict:
    """Parameters, from shapes: one latent-attention sub-layer, one dense
    feed-forward, the router (with its bias), one expert, a double layer
    but for its experts, and the top (table rows, head columns, final
    norm) of the share."""
    c = sizes(config)
    D, H = c["hidden_size"], c["num_attention_heads"]
    Rq, Rkv = c["q_lora_rank"], c["kv_lora_rank"]
    nope, rope, vd = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    n_all = c["n_routed_experts"] + c["zero_expert_num"]
    mla = (D * Rq + Rq + Rq * H * (nope + rope) + D * (Rkv + rope) + Rkv
           + Rkv * H * (nope + vd) + H * vd * D)
    dense = 3 * D * c["ffn_hidden_size"]
    router = D * n_all + n_all
    return {
        "mla": mla, "dense": dense, "router": router,
        "expert": 3 * D * c["expert_ffn_hidden_size"],
        "layer": 2 * mla + 2 * dense + router + 4 * D,
        "top": 2 * c["vocab_rows"] * D + D,
    }


def share_counts(config: dict) -> dict:
    """What the chip holds: parameters outside the experts, in them, and a
    position's bytes in the cache (every sub-layer's latent row)."""
    c, per = sizes(config), param_counts(config)
    width = jnp.dtype(config["precision"]["params"]).itemsize
    L = c["num_layers"]
    return {
        "non_expert": L * per["layer"] + per["top"],
        "experts": L * c["experts_held"] * per["expert"],
        "cache_bytes_per_position": 2 * L * (c["kv_lora_rank"] + c["qk_rope_head_dim"]) * width,
    }


def decode_step_bytes(config: dict, *, slots: int, cache_rows: float) -> float:
    """A FLOOR on the bytes one batched decode step moves, whatever the
    routing: every parameter outside the experts once in the type the
    configuration holds them in - the layers, the head's columns and the
    final norm, and of the table the ``slots`` rows the embedding gathers -
    plus the latent rows written so far of the seated sessions.  NO expert
    bytes: this function is not told how many experts a step touched, a
    step may touch none, and a count of all that are held would read over
    100 % the day the kernel skips well.  ``decode_roofline_share`` is
    therefore a floor in this family's cells; ``expert_call_bytes`` and the
    ``expert_roofline`` reader carry the experts."""
    c, per = sizes(config), param_counts(config)
    width = jnp.dtype(config["precision"]["params"]).itemsize
    D = c["hidden_size"]
    params = c["num_layers"] * per["layer"] + c["vocab_rows"] * D + D + slots * D
    return params * width + cache_rows * share_counts(config)["cache_bytes_per_position"]


def expert_call_bytes(config: dict, touched: float, rows: float) -> float:
    """Least bytes one call of the grouped feed-forward kernel moves when
    ``touched`` of the held experts have rows, ``rows`` in all: each touched
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
    out["program"] = dict(TINY_PROGRAM)
    for key, top in SHARE.items():
        out[top] = TINY_PROGRAM[key]
    # 1 / sqrt(64): at the tiny width the layers weigh what they weigh at
    # the published one (reference/longcat_ref.py ``init_std``).
    out["assumed"]["init_std"] = 0.125
    # Held in float32 at this size: XLA's CPU backend rewrites a whole
    # bfloat16 buffer for every row written into it (30 ms a sub-layer at
    # 32 slots x 1024, against 0.04 in float32), and the rehearsal would
    # finish no request in its window.  The leaves are the same bfloat16
    # roundings; tests/test_longcat.py runs the bfloat16 program.
    out["precision"] = dict(out["precision"], params="float32")
    out["rehearsal"] = {"limits": dict(TINY_LIMITS)}
    return out
