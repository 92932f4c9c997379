"""Nemotron-H (``"model": "nemotron_h"``: NVIDIA's Nemotron-H family): layers
of one sub-layer each - Mamba-2 mixers whose state is a matrix a head,
grouped-query attention without positional encoding, LatentMoE feed-forwards
of ungated squared-ReLU experts in a narrower latent beside a shared expert -
ONE CHIP'S SHARE OF THE FIRST PIPELINE STAGE of the model served.

The only file that names the program's ``models/nemotron_h.py`` and the
reference ``reference/nemotron_h_ref.py``.  A configuration's ``published``
group holds the source's ``config.json`` keys whole; ``program`` the most
positions a session may hold and THE SHARE this chip has of the deployment
the file states - ``held_layers``, the published layers that live here,
``experts_held`` routed experts from ``expert_first`` on in each ``E`` layer
of them, ``vocab_rows`` of the vocabulary; the file's top-level
``num_hidden_layers``, ``n_routed_experts`` and ``vocab_size`` (the keys
``reduced`` lists) say the same.  The router keeps its published width and
its experts per token: a choice on an expert that is not held adds nothing,
in the program and in the reference alike.

Kept with the benchmark, the counts from shapes the kernels' shares are read
against: ``expert_call_bytes`` / ``expert_call_flops`` (the ungated expert:
TWO matrices), ``ssd_chunk_flops`` / ``ssd_chunk_bytes`` (the chunked
recurrence: its products, and the carried state each way - all of it that
must cross HBM), ``state_step_bytes`` (the live slots' states, each way).
"""

from __future__ import annotations

import copy

import jax
import jax.numpy as jnp

from benchmarks.reference import nemotron_h_ref

#: The source's keys the program's ``Config`` and the reference read as they
#: are.
KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers", "hybrid_override_pattern",
    "mamba_num_heads", "mamba_head_dim", "ssm_state_size", "n_groups", "conv_kernel",
    "chunk_size", "num_attention_heads", "num_key_value_heads", "head_dim",
    "n_routed_experts", "num_experts_per_tok", "moe_latent_size",
    "moe_intermediate_size", "moe_shared_expert_intermediate_size",
    "routed_scaling_factor", "layer_norm_epsilon",
)
#: What the program builds whatever the source says; a source that says
#: otherwise is another model.
FIXED = {"mlp_hidden_act": "relu2", "mamba_hidden_act": "silu", "n_group": 1,
         "topk_group": 1, "norm_topk_prob": True, "n_shared_experts": 1,
         "use_conv_bias": True, "use_bias": False, "mamba_proj_bias": False,
         "mlp_bias": False, "attention_bias": False, "tie_word_embeddings": False,
         "sliding_window": None}
#: The share's keys in ``program`` and the top-level key each restates
#: (``held_layers`` by its length).
SHARE = {"experts_held": "n_routed_experts", "vocab_rows": "vocab_size"}
#: What the seeded leaves are drawn with (``assumed`` in the file).
SEEDED = ("table_std", "out_factor", "expert_down_factor", "router_spread",
          "expert_bias_std", "conv_std", "conv_bias_std")

#: The rehearsal's size: every kind of layer, ``M E * E``, at tiny widths - 4
#: Mamba heads of 8 channels in 2 groups, a state of 16 columns; 4 query
#: heads on 2 K/V heads; a latent of 32 under a hidden of 64; 16 experts, 4
#: a token, experts 4-11 held (neither end of the router's range); a quarter
#: of a tiny vocabulary.  ``chunk_size`` stays the published 128: the
#: engine's chunks are 256 and 512 wide, whole blocks of it.  The limit was
#: read at this size on the CPU (tests/test_benchmark_families.py rehearses
#: the cell; tests/test_nemotron_h.py holds the planted faults).
TINY_PUBLISHED = {
    "vocab_size": 1000, "hidden_size": 64, "num_hidden_layers": 4,
    "hybrid_override_pattern": "ME*E", "mamba_num_heads": 4, "mamba_head_dim": 8,
    "ssm_state_size": 16, "n_groups": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "n_routed_experts": 16,
    "num_experts_per_tok": 4, "moe_latent_size": 32, "moe_intermediate_size": 48,
    "moe_shared_expert_intermediate_size": 96,
}
TINY_PROGRAM = {"max_seq_len": 4096, "held_layers": [0, 1, 2, 3], "experts_held": 8,
                "expert_first": 4, "vocab_rows": 250}
TINY_LIMITS = {"widest_gap": 0.06}


def sizes(config: dict) -> dict:
    """The shape of the model and of the share, as the program's ``Config``
    and the reference both take them."""
    pub, prog = config["published"], config["program"]
    for key, value in FIXED.items():
        if pub.get(key, value) != value:
            raise ValueError(f"the nemotron_h family builds {key} = {value!r}, "
                             f"the configuration says {pub[key]!r}")
    stated = {"num_hidden_layers": len(prog["held_layers"]),
              **{top: prog[key] for key, top in SHARE.items()}}
    for top, value in stated.items():
        if top in config and config[top] != value:
            raise ValueError(
                f"the configuration's {top} = {config[top]!r} and its program "
                f"group state two shares ({value!r})")
    return {
        **{k: pub[k] for k in KEYS},
        "held_layers": tuple(prog["held_layers"]), "experts_held": prog["experts_held"],
        "expert_first": prog["expert_first"], "vocab_rows": prog["vocab_rows"],
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
    cfg = models.nemotron_h.Config(**shape, param_dtype=dtype, **(overrides or {}))
    # Each leaf rounded once to bfloat16, as the reference rounds it, THEN
    # held in the parameters' type: the rehearsal's float32 tree has the
    # reference's values to the bit.
    return cfg, lambda key: jax.tree.map(
        lambda a: a.astype(jnp.dtype(dtype)), nemotron_h_ref.tree(c, key))


def apply_fn(cfg):
    from distributed_tensorflow_examples_tpu import models

    return lambda p, b: models.nemotron_h.apply(cfg, p, b["x"])


def decode_fns(cfg):
    from distributed_tensorflow_examples_tpu import models

    return models.nemotron_h.serve_decode_fns(cfg)


def max_len(config: dict) -> int:
    return config["program"]["max_seq_len"]


def token_vocab(config: dict) -> int:
    """The slice of the vocabulary that is here: ids are drawn from it."""
    return config["program"]["vocab_rows"]


def reference_logits_at(config: dict, seed: int, tokens, rows, cols,
                        mode: str = "float32"):
    return nemotron_h_ref.logits_at(sizes(config), seed, tokens, rows, cols, mode)


def layer_counts(config: dict) -> dict:
    """How many of the held layers are of each kind: ``M``, ``E``, ``*``."""
    c = sizes(config)
    kinds = [c["hybrid_override_pattern"][i] for i in c["held_layers"]]
    return {k: kinds.count(k) for k in "ME*"}


def param_counts(config: dict) -> dict:
    """Parameters, from shapes: a Mamba layer, an attention layer, an expert
    layer but for its routed experts (router with its bias, the two latent
    projections, the shared expert), one routed expert (TWO matrices), each
    layer with its norm; and the top of the share (table rows, head columns,
    final norm)."""
    c = sizes(config)
    D, H, N, G = c["hidden_size"], c["mamba_num_heads"], c["ssm_state_size"], c["n_groups"]
    Di = H * c["mamba_head_dim"]
    Cd = Di + 2 * G * N
    A, KV, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    Lt, F, Fs = (c["moe_latent_size"], c["moe_intermediate_size"],
                 c["moe_shared_expert_intermediate_size"])
    return {
        "mamba": D + D * (Di + Cd + H) + c["conv_kernel"] * Cd + Cd + 3 * H + Di + Di * D,
        "attn": D + 2 * D * A * hd + 2 * D * KV * hd,
        "moe_layer": D + D * c["n_routed_experts"] + c["n_routed_experts"]
                     + 2 * D * Lt + 2 * D * Fs,
        "expert": 2 * Lt * F,
        "top": 2 * c["vocab_rows"] * D + D,
    }


def state_bytes_per_slot(config: dict) -> int:
    """What one session owns whatever its length: every Mamba layer's state
    ``[H, P, N]`` and conv tail ``[conv_kernel - 1, d_inner + 2 G N]``,
    float32."""
    c = sizes(config)
    H, P, N = c["mamba_num_heads"], c["mamba_head_dim"], c["ssm_state_size"]
    tail = (c["conv_kernel"] - 1) * (H * P + 2 * c["n_groups"] * N)
    return layer_counts(config)["M"] * (H * P * N + tail) * 4


def _row_bytes(config: dict) -> int:
    """A position's bytes in one attention layer's cache."""
    c = sizes(config)
    width = jnp.dtype(config["precision"]["params"]).itemsize
    return 2 * c["num_key_value_heads"] * c["head_dim"] * width


def share_counts(config: dict) -> dict:
    """What the chip holds: parameters outside the routed experts, in them, a
    position's bytes in the cache (the attention layers' rows) and a slot's
    bytes whatever its length (states and tails)."""
    c, per, n = sizes(config), param_counts(config), layer_counts(config)
    return {
        "non_expert": n["M"] * per["mamba"] + n["*"] * per["attn"]
                      + n["E"] * per["moe_layer"] + per["top"],
        "experts": n["E"] * c["experts_held"] * per["expert"],
        "cache_bytes_per_position": n["*"] * _row_bytes(config),
        "state_bytes_per_slot": state_bytes_per_slot(config),
    }


def decode_step_bytes(config: dict, *, slots: int, cache_rows: float) -> float:
    """A FLOOR on the bytes one batched decode step moves, whatever the
    routing and however many rows are live: every parameter outside the
    ROUTED experts once in the type the configuration holds them in - the
    layers, the head's columns and the final norm, and of the table the
    ``slots`` rows the embedding gathers - plus the attention layers' rows
    written so far of the seated sessions.  NO routed-expert byte and NO
    state's byte: this function is told neither how many experts a step
    touched nor how many rows were live (``expert_call_bytes`` with the
    ``expert_roofline`` reader and ``state_step_bytes`` with the
    ``state_step_roofline`` reader carry them), and they are most of what a
    step reads here.  ``decode_roofline_share`` is therefore a floor in this
    family's cells."""
    c, per, n = sizes(config), param_counts(config), layer_counts(config)
    width = jnp.dtype(config["precision"]["params"]).itemsize
    D = c["hidden_size"]
    params = (n["M"] * per["mamba"] + n["*"] * per["attn"] + n["E"] * per["moe_layer"]
              + c["vocab_rows"] * D + D + slots * D)
    return params * width + cache_rows * n["*"] * _row_bytes(config)


def expert_call_bytes(config: dict, touched: float, rows: float) -> float:
    """Least bytes one call of the grouped feed-forward kernel moves when
    ``touched`` of the held experts have rows, ``rows`` in all: each touched
    expert's TWO matrices once, the latent rows read in the parameters' type
    and their results written in float32."""
    c, per = sizes(config), param_counts(config)
    width = jnp.dtype(config["precision"]["params"]).itemsize
    return touched * per["expert"] * width + rows * c["moe_latent_size"] * (width + 4)


def expert_call_flops(config: dict, rows: float) -> float:
    """Operations of one call for ``rows`` rows: two products a row, a
    multiply-add two operations."""
    return rows * 2 * param_counts(config)["expert"]


def ssd_chunk_bytes(config: dict, chunk: float) -> float:
    """Bytes one call of the chunked-recurrence kernel CANNOT keep off HBM,
    whatever ``chunk``: the carried state ``[H, P, N]`` float32 read from the
    cache and written back to it - the cache's arrays are what lives in HBM
    between two programs.  Every other operand (``x`` and ``y [chunk, H,
    P]``, ``B`` and ``C [chunk, G, N]``, the decays and ``dt [chunk, H]``;
    38.8 MB of the call's 47.2 MB at 512 positions) is made and used inside
    the chunk's program, where it crosses HBM only if the compiler puts it
    there - and it does not: compiled for a v5e, all six inputs and ``y`` of
    each of the five calls lie in the chip's 128 MiB of VMEM (``S(1)`` in the
    optimised HLO; PERF.md section 6 has the lines), the state written is the
    one operand in HBM.  Every operand over the HBM bandwidth is therefore no
    least time for this call (it read 110 % on the chip), and the share's
    bound is the operations' at the widths served: 17.0 us against the
    state's 10.2 at 512 positions."""
    c = sizes(config)
    return 4 * 2 * c["mamba_num_heads"] * c["mamba_head_dim"] * c["ssm_state_size"]


def ssd_chunk_flops(config: dict, chunk: float) -> float:
    """Operations of one call for ``chunk`` positions, a multiply-add two:
    per block of ``chunk_size`` positions ``B C^T`` once a group, and a head
    three products - the block's own part ``[P, L] x [L, L]``, the state's
    part ``[P, N] x [N, L]`` and the block's contribution to the state ``[P,
    L] x [L, N]``."""
    c = sizes(config)
    H, P, N, G, L = (c["mamba_num_heads"], c["mamba_head_dim"], c["ssm_state_size"],
                     c["n_groups"], c["chunk_size"])
    per_block = 2 * (G * L * L * N + H * P * L * (L + 2 * N))
    return chunk / L * per_block


def state_step_bytes(config: dict, live: float) -> float:
    """Least bytes one call of the state-step kernel moves when ``live``
    slots are live: each one's state ``[H, P, N]`` float32 read once and
    written once, ``dt x`` read and ``y`` written ``[H, P]``, its ``B`` and
    ``C``."""
    c = sizes(config)
    H, P, N, G = c["mamba_num_heads"], c["mamba_head_dim"], c["ssm_state_size"], c["n_groups"]
    return live * 4 * (2 * H * P * N + 2 * H * P + 2 * G * N + H)


def tiny(config: dict) -> dict:
    """The configuration at the rehearsal's size, with the limits read at
    it under ``rehearsal``."""
    out = copy.deepcopy(config)
    out["published"].update(TINY_PUBLISHED)
    out["program"] = copy.deepcopy(TINY_PROGRAM)
    out["num_hidden_layers"] = len(TINY_PROGRAM["held_layers"])
    for key, top in SHARE.items():
        out[top] = TINY_PROGRAM[key]
    # Held in float32 at this size, as families/longcat/serve.py ``tiny``
    # has it and for its reason (XLA's CPU backend rewrites a whole bfloat16
    # buffer for every row written into it).  The leaves are the same
    # bfloat16 roundings (``build``).
    out["precision"] = dict(out["precision"], params="float32")
    out["rehearsal"] = {"limits": dict(TINY_LIMITS)}
    return out
