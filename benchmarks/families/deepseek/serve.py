"""DeepSeek-V2 (``"model": "deepseek"``): latent attention with scaled
rotary positions and one chip's share of expert layers whose choice is
limited to groups, with shared experts beside them, served.

The only file that names the program's ``models/deepseek.py`` and the
reference ``reference/deepseek_ref.py``.  A configuration's ``published``
group holds the source's ``config.json`` keys whole; ``program`` the most
positions a session may hold and THE SHARE this chip has of the deployment
the file states - ``num_hidden_layers`` of the published depth,
``experts_held`` routed experts from ``expert_first`` on (whole routing
groups), ``vocab_rows`` of the vocabulary; the file's top-level
``num_hidden_layers``, ``n_routed_experts`` and ``vocab_size`` (the keys
``reduced`` lists) say the same.  The router keeps its published width and
its groups: a choice on an expert that is not held adds nothing, in the
program and in the reference alike.  The shared experts and the leading
dense layer are the model's, whole on every chip.
"""

from __future__ import annotations

import copy

import jax.numpy as jnp

from benchmarks.reference import deepseek_ref

#: The source's keys the program's ``Config`` and the reference read as they
#: are, and ``rope_scaling``'s, which both take flattened to ``rope_<key>``.
KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "moe_intermediate_size",
    "num_hidden_layers", "first_k_dense_replace", "moe_layer_freq",
    "num_attention_heads", "kv_lora_rank", "q_lora_rank", "qk_rope_head_dim",
    "qk_nope_head_dim", "v_head_dim", "n_routed_experts", "n_shared_experts",
    "num_experts_per_tok", "n_group", "topk_group", "routed_scaling_factor",
    "rms_norm_eps", "rope_theta",
)
ROPE_KEYS = ("factor", "original_max_position_embeddings", "beta_fast", "beta_slow",
             "mscale", "mscale_all_dim")
#: What the program builds whatever the source says; a source that says
#: otherwise is another model.
FIXED = {"attention_bias": False, "hidden_act": "silu", "scoring_func": "softmax",
         "topk_method": "group_limited_greedy", "norm_topk_prob": False,
         "tie_word_embeddings": False}
#: The share's keys in ``program`` and the top-level key each restates.
SHARE = {"num_hidden_layers": "num_hidden_layers", "experts_held": "n_routed_experts",
         "vocab_rows": "vocab_size"}

#: The rehearsal's size: a dense layer and two expert layers at tiny widths,
#: a quarter of a tiny vocabulary, sessions of 512 positions, past the tiny
#: ``original_max_position_embeddings``.  THE ROUTER IS CUT TOO, unlike the
#: longcat family's: 16 experts in 4 groups, 2 groups and 2 choices a token,
#: group 1 held.  The mix seats 64 sessions at once and none of its requests
#: is shorter than 64 steps; on the CPU the expert kernel is interpreted a
#: block at a time, and with the published router (26 blocks a step, 44 a
#: chunk) no request ended inside the rehearsal's four seconds.
#: ``routed_scaling_factor`` 1.6 = 16 x 16 / 160 keeps a choice's weight
#: (``1.6 s_i``) the 0.1 it is at the real size.  The limit was read at this
#: size on the CPU (benchmarks/tests/test_deepseek_family.py holds the
#: readings).
TINY_PUBLISHED = {
    "vocab_size": 1000, "hidden_size": 32, "intermediate_size": 64,
    "moe_intermediate_size": 16, "num_hidden_layers": 3, "num_attention_heads": 2,
    "kv_lora_rank": 16, "q_lora_rank": 24, "qk_rope_head_dim": 8,
    "qk_nope_head_dim": 16, "v_head_dim": 16, "n_routed_experts": 16,
    "n_group": 4, "topk_group": 2, "num_experts_per_tok": 2,
    "routed_scaling_factor": 1.6,
}
TINY_ROPE = {"original_max_position_embeddings": 64}
TINY_PROGRAM = {"max_seq_len": 512, "num_hidden_layers": 3, "experts_held": 4,
                "expert_first": 4, "vocab_rows": 250}
TINY_LIMITS = {"widest_gap": 0.1}


def sizes(config: dict) -> dict:
    """The shape of the model and of the share, as the program's ``Config``
    and the reference both take them."""
    pub, prog = config["published"], config["program"]
    for key, value in FIXED.items():
        if pub.get(key, value) != value:
            raise ValueError(f"the deepseek family builds {key} = {value!r}, "
                             f"the configuration says {pub[key]!r}")
    if pub["rope_scaling"].get("type") != "yarn":
        raise ValueError("the deepseek family builds rope_scaling.type = 'yarn', "
                         f"the configuration says {pub['rope_scaling'].get('type')!r}")
    for key, top in SHARE.items():
        if top in config and config[top] != prog[key]:
            raise ValueError(
                f"the configuration's {top} = {config[top]!r} and its "
                f"program.{key} = {prog[key]!r} state two shares")
    return {
        **{k: pub[k] for k in KEYS},
        **{f"rope_{k}": pub["rope_scaling"][k] for k in ROPE_KEYS},
        "num_hidden_layers": prog["num_hidden_layers"],
        "experts_held": prog["experts_held"], "expert_first": prog["expert_first"],
        "vocab_rows": prog["vocab_rows"], "init_std": config["assumed"]["init_std"],
        "router_std_factor": config["assumed"]["router_std_factor"],
    }


def build(config: dict, overrides: dict | None = None):
    """``(cfg, tree_fn)``: the program's ``Config`` and the seeded-weights
    builder ``tree_fn(key) -> params`` of the tree it serves, in the type
    the configuration holds parameters in."""
    from distributed_tensorflow_examples_tpu import models

    c = sizes(config)
    dtype = config["precision"]["params"]
    shape = {k: v for k, v in c.items() if k not in ("init_std", "router_std_factor")}
    cfg = models.deepseek.Config(**shape, param_dtype=dtype, **(overrides or {}))
    return cfg, lambda key: deepseek_ref.tree(c, key, jnp.dtype(dtype))


def apply_fn(cfg):
    from distributed_tensorflow_examples_tpu import models

    return lambda p, b: models.deepseek.apply(cfg, p, b["x"])


def decode_fns(cfg):
    from distributed_tensorflow_examples_tpu import models

    return models.deepseek.serve_decode_fns(cfg)


def max_len(config: dict) -> int:
    return config["program"]["max_seq_len"]


def token_vocab(config: dict) -> int:
    """The slice of the vocabulary that is here: ids are drawn from it."""
    return config["program"]["vocab_rows"]


def reference_logits_at(config: dict, seed: int, tokens, rows, cols,
                        mode: str = "float32"):
    return deepseek_ref.logits_at(sizes(config), seed, tokens, rows, cols, mode)


def param_counts(config: dict) -> dict:
    """Parameters, from shapes: the latent attention, the dense
    feed-forward, the shared experts, the router (no bias), one routed
    expert, an expert layer but for its routed experts, the dense layer, and
    the top (table rows, head columns, final norm) of the share."""
    c = sizes(config)
    D, H = c["hidden_size"], c["num_attention_heads"]
    Rq, Rkv = c["q_lora_rank"], c["kv_lora_rank"]
    nope, rope, vd = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    F = c["moe_intermediate_size"]
    mla = (D * Rq + Rq + Rq * H * (nope + rope) + D * (Rkv + rope) + Rkv
           + Rkv * H * (nope + vd) + H * vd * D)
    dense = 3 * D * c["intermediate_size"]
    shared = 3 * D * c["n_shared_experts"] * F
    router = D * c["n_routed_experts"]
    return {
        "mla": mla, "dense": dense, "shared": shared, "router": router,
        "expert": 3 * D * F,
        "moe_layer": mla + shared + router + 2 * D,
        "dense_layer": mla + dense + 2 * D,
        "top": 2 * c["vocab_rows"] * D + D,
    }


def _layers(c: dict) -> tuple[int, int]:
    """``(dense layers, expert layers)`` of the share."""
    kinds = [deepseek_ref.layer_kind(c, i) for i in range(c["num_hidden_layers"])]
    return kinds.count("dense"), kinds.count("moe")


def share_counts(config: dict) -> dict:
    """What the chip holds: parameters outside the routed experts, in them,
    and a position's bytes in the cache (every layer's latent row)."""
    c, per = sizes(config), param_counts(config)
    width = jnp.dtype(config["precision"]["params"]).itemsize
    n_dense, n_moe = _layers(c)
    return {
        "non_expert": n_dense * per["dense_layer"] + n_moe * per["moe_layer"] + per["top"],
        "experts": n_moe * c["experts_held"] * per["expert"],
        "cache_bytes_per_position":
            (n_dense + n_moe) * (c["kv_lora_rank"] + c["qk_rope_head_dim"]) * width,
    }


def decode_step_bytes(config: dict, *, slots: int, cache_rows: float) -> float:
    """A FLOOR on the bytes one batched decode step moves, whatever the
    routing: every parameter outside the ROUTED experts once in the type the
    configuration holds them in - the layers with their shared experts, the
    head's columns and the final norm, and of the table the ``slots`` rows
    the embedding gathers - plus the latent rows written so far of the
    seated sessions.  NO routed-expert byte: this function is not told how
    many experts a step touched, a step may touch none, and a count of all
    that are held would read over 100 % the day the kernel skips well.
    ``decode_roofline_share`` is therefore a floor in this family's cells;
    ``expert_call_bytes`` and the ``expert_roofline`` reader carry the
    experts."""
    c, per = sizes(config), param_counts(config)
    width = jnp.dtype(config["precision"]["params"]).itemsize
    D = c["hidden_size"]
    n_dense, n_moe = _layers(c)
    params = (n_dense * per["dense_layer"] + n_moe * per["moe_layer"]
              + c["vocab_rows"] * D + D + slots * D)
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
    out["published"]["rope_scaling"].update(TINY_ROPE)
    out["program"] = dict(TINY_PROGRAM)
    for key, top in SHARE.items():
        out[top] = TINY_PROGRAM[key]
    # 1 / sqrt(32): at the tiny width the layers weigh what they weigh at
    # the published one (reference/deepseek_ref.py ``init_std``).
    out["assumed"]["init_std"] = 0.177
    # Held in float32 at this size, as families/longcat/serve.py ``tiny``
    # has it and for its reason (XLA's CPU backend rewrites a whole bfloat16
    # buffer for every row written into it).  The leaves are the same
    # bfloat16 roundings; tests/test_deepseek.py runs the bfloat16 program.
    out["precision"] = dict(out["precision"], params="float32")
    out["rehearsal"] = {"limits": dict(TINY_LIMITS)}
    return out
