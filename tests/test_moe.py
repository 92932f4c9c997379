"""MoE / expert parallelism (ops/moe.py): routing math, parity, training.

Numerics strategy (SURVEY.md §4): with capacity high enough that nothing
drops, the dispatch/combine einsum formulation must equal the dense
reference — every token's output is the gate-weighted sum of its top-k
experts' FFNs.
"""

import numpy as np
import jax
import jax.numpy as jnp
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from distributed_tensorflow_examples_tpu import models, train
from distributed_tensorflow_examples_tpu.ops import moe as moe_ops
from distributed_tensorflow_examples_tpu.parallel import local_mesh_for_testing


@pytest.fixture(scope="module")
def mesh_expert():
    return local_mesh_for_testing({"data": 2, "expert": 4})


def _dense_reference(p, x, moe):
    """Per-token loop over all experts: y = sum_k gate_k * FFN_{e_k}(x)."""
    B, T, D = x.shape
    tokens = x.reshape(-1, D)
    logits = tokens @ p["router"]["kernel"]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, moe.top_k)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    def ffn(e, t):
        h = jax.nn.gelu(t @ p["w1"][e] + p["b1"][e])
        return h @ p["w2"][e] + p["b2"][e]

    all_out = jnp.stack([ffn(e, tokens) for e in range(moe.n_experts)])  # [E,N,D]
    y = jnp.zeros_like(tokens)
    for j in range(moe.top_k):
        sel = jnp.take_along_axis(
            all_out, expert_idx[None, :, j, None], axis=0
        )[0]
        y = y + gate_vals[:, j, None] * sel
    return y.reshape(B, T, D)


def test_moe_matches_dense_reference_no_drops():
    moe = moe_ops.MoEConfig(n_experts=4, top_k=2, capacity_factor=8.0)
    p = moe_ops.init(jax.random.key(0), 16, 32, moe)
    x = jax.random.normal(jax.random.key(1), (2, 8, 16), jnp.float32)
    y, aux = moe_ops.apply(p, x, moe)
    ref = _dense_reference(p, x, moe)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=1e-4, atol=1e-5)
    assert float(aux) > 0


def test_moe_capacity_drops_tokens():
    """capacity_factor ~0 forces drops: outputs are zero for overflow tokens,
    never NaN, and the layer still differentiates."""
    moe = moe_ops.MoEConfig(n_experts=2, top_k=1, capacity_factor=0.1)
    p = moe_ops.init(jax.random.key(0), 8, 16, moe)
    x = jax.random.normal(jax.random.key(1), (2, 16, 8), jnp.float32)
    y, aux = moe_ops.apply(p, x, moe)
    assert np.isfinite(np.asarray(y)).all()
    # C = max(4, ceil(32/2*0.1)) = 4 slots per expert => at most 8 of 32
    # tokens routed; most rows are exactly zero (dropped).
    zero_rows = np.sum(np.all(np.asarray(y.reshape(-1, 8)) == 0, axis=-1))
    assert zero_rows >= 32 - 2 * 4, zero_rows
    g = jax.grad(lambda p: moe_ops.apply(p, x, moe)[0].sum())(p)
    assert all(np.isfinite(np.asarray(l)).all() for l in jax.tree.leaves(g))


def test_moe_aux_loss_balanced_is_one():
    """Perfectly uniform router => aux == E * E * (1/E)*(1/E) == 1."""
    moe = moe_ops.MoEConfig(n_experts=4, top_k=1)
    p = moe_ops.init(jax.random.key(0), 8, 16, moe)
    p["router"]["kernel"] = jnp.zeros_like(p["router"]["kernel"])
    x = jax.random.normal(jax.random.key(1), (4, 16, 8), jnp.float32)
    _, aux = moe_ops.apply(p, x, moe)
    # Uniform probs: mean_prob = 1/E exactly; first-choice fractions follow
    # top_k tie-breaking (argmax of equal logits -> expert 0), so aux =
    # E * sum_e f_e * (1/E) = 1.0 regardless of f.
    np.testing.assert_allclose(float(aux), 1.0, rtol=1e-5)


def test_moe_expert_sharded_matches_replicated(mesh_expert):
    """The GShard einsums must be placement-invariant: expert-sharded
    weights on a data×expert mesh give the same outputs as unsharded."""
    moe = moe_ops.MoEConfig(n_experts=4, top_k=2, capacity_factor=4.0)
    p = moe_ops.init(jax.random.key(0), 16, 32, moe)
    x = jax.random.normal(jax.random.key(1), (4, 8, 16), jnp.float32)
    ref, _ = moe_ops.apply(p, x, moe)

    shard = lambda t, spec: jax.device_put(t, NamedSharding(mesh_expert, spec))
    p_sharded = {
        "router": {"kernel": shard(p["router"]["kernel"], P(None, None))},
        "w1": shard(p["w1"], P("expert", None, None)),
        "b1": shard(p["b1"], P("expert", None)),
        "w2": shard(p["w2"], P("expert", None, None)),
        "b2": shard(p["b2"], P("expert", None)),
    }
    x_sharded = jax.device_put(x, NamedSharding(mesh_expert, P("data", None, None)))
    got, _ = jax.jit(lambda p, x: moe_ops.apply(p, x, moe))(p_sharded, x_sharded)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-4, atol=1e-5)


def test_transformer_moe_trains(mesh_expert):
    """MoE transformer end-to-end on a data×expert mesh: loss falls, aux
    reported, expert weights stay expert-sharded."""
    cfg = models.transformer.Config(
        vocab_size=64, dim=32, n_layers=2, n_heads=2, max_seq_len=16,
        attention="xla", compute_dtype="float32",
        moe_experts=4, moe_top_k=2,
    )
    opt = optax.adam(1e-2)
    state, shardings = train.create_sharded_state(
        lambda r: models.transformer.init(cfg, r),
        opt,
        jax.random.key(0),
        mesh=mesh_expert,
        rules=models.transformer.sharding_rules(cfg),
    )
    spec = shardings.params["block_0"]["moe"]["w1"].spec
    assert spec[0] == "expert", spec
    step = train.build_train_step(
        models.transformer.loss_fn(cfg, mesh=mesh_expert),
        opt,
        mesh=mesh_expert,
        state_shardings=shardings,
    )
    from distributed_tensorflow_examples_tpu.data.pipeline import as_global

    rng = np.random.default_rng(0)
    first = last = None
    for _ in range(12):
        xy = rng.integers(0, 64, size=(8, 17)).astype(np.int32)
        b = as_global({"x": xy[:, :-1], "y": xy[:, 1:]}, mesh_expert)
        state, m = step(state, b)
        if first is None:
            first = float(m["loss"])
        last = float(m["loss"])
        assert "moe_aux" in m
    assert last < first, (first, last)


def test_moe_pipeline_combination_rejected():
    cfg = models.transformer.Config(
        n_layers=4, moe_experts=4, pipeline_stages=2
    )
    with pytest.raises(ValueError, match="compose"):
        models.transformer.init(cfg, jax.random.key(0))


def test_moe_composes_with_sequence_parallelism():
    """MoE (batch over ('data','expert'), GShard all_to_all dispatch) and
    ring attention (activations sharded over 'seq') must COMPOSE: one real
    train step on a data=2 x expert=2 x seq=2 mesh, finite loss, and the
    expert dispatch still lowers to all-to-all in the compiled HLO."""
    from distributed_tensorflow_examples_tpu.data.pipeline import as_global
    from distributed_tensorflow_examples_tpu.utils import hlo_analysis

    mesh = local_mesh_for_testing({"data": 2, "expert": 2, "seq": 2})
    cfg = models.transformer.Config(
        vocab_size=256, dim=64, n_layers=2, n_heads=4, max_seq_len=64,
        compute_dtype="float32", attention="xla", moe_experts=4,
    )
    opt = optax.sgd(0.1)
    state, sh = train.create_sharded_state(
        lambda r: models.transformer.init(cfg, r), opt, jax.random.key(0),
        mesh=mesh, rules=models.transformer.sharding_rules(cfg),
    )
    step = train.build_train_step(
        models.transformer.loss_fn(cfg, mesh=mesh), opt, mesh=mesh,
        state_shardings=sh, batch_spec=models.transformer.batch_spec(cfg),
    )
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, size=(8, 65)).astype(np.int32)
    batch = as_global(
        {"x": toks[:, :-1], "y": toks[:, 1:]}, mesh,
        spec=models.transformer.batch_spec(cfg),
    )
    compiled = step.lower(state, batch).compile()
    s = hlo_analysis.summarize(
        hlo_analysis.parse_collectives(compiled.as_text())
    )
    assert "all-to-all" in s, f"no all-to-all under moe x seq; saw {sorted(s)}"
    state, m = compiled(state, batch)
    assert np.isfinite(float(m["loss"])), m


def test_moe_warns_on_nondividing_shapes(mesh_expert):
    """VERDICT r3 weak #3: when the token count cannot be grouped into a
    multiple of the mesh's token shards, the ('data','expert') pin / expert
    constraint are skipped BY DESIGN — but never silently: either the
    compiled step still contains the all_to_all, or the layout-degradation
    warning must have fired so the user can trace the HLO-level change."""
    import warnings as _warnings

    from distributed_tensorflow_examples_tpu.utils import hlo_analysis

    moe = moe_ops.MoEConfig(n_experts=4, top_k=2, capacity_factor=4.0)
    p = moe_ops.init(jax.random.key(0), 16, 32, moe)
    # B*T = 6 tokens over data=2 x expert=4 (8 shards): no group size makes
    # the group count a shard multiple, and G=1 divides neither 8 nor the
    # data axis — all three skip paths are reachable.
    x = jax.random.normal(jax.random.key(1), (2, 3, 16), jnp.float32)

    fn = jax.jit(lambda p, x: moe_ops.apply(p, x, moe, mesh=mesh_expert))
    with _warnings.catch_warnings(record=True) as ws:
        _warnings.simplefilter("always")
        hlo = fn.lower(p, x).compile().as_text()
    summary = hlo_analysis.summarize(hlo_analysis.parse_collectives(hlo))
    moe_warnings = [w for w in ws if "moe:" in str(w.message)]
    assert "all-to-all" in summary or moe_warnings, (
        f"layout degraded silently: collectives={sorted(summary)}, "
        f"warnings={[str(w.message) for w in ws]}"
    )
    # At THIS shape the skip paths are known-taken, so the warnings must be
    # present (the all_to_all arm covers future shapes where grouping works).
    assert any("pad batch*seq" in str(w.message) for w in moe_warnings)
    assert any("token pin" in str(w.message) for w in moe_warnings)

    # The degraded layout must still be CORRECT (placement-invariance).
    y, _ = fn(p, x)
    ref, _ = moe_ops.apply(p, x, moe)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=1e-4, atol=1e-5)


def test_moe_decode_matches_training_forward():
    """VERDICT r3 missing #4: MoE models must decode.  Per-position parity:
    teacher-forcing the same tokens through the KV-cache decode_step must
    reproduce the training forward's logits (capacity high enough that
    training drops nothing — decode capacity is per-step and effectively
    never drops, so parity is only defined in the no-drop regime)."""
    cfg = models.transformer.Config(
        vocab_size=211, dim=32, n_layers=2, n_heads=4, max_seq_len=32,
        compute_dtype="float32", attention="xla",
        moe_experts=4, moe_capacity_factor=8.0,
    )
    params = models.transformer.init(cfg, jax.random.key(0))
    rng = np.random.default_rng(1)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(2, 10)), jnp.int32)

    logits_train = models.transformer.apply(cfg, params, toks)  # [B, T, V]
    cache = models.transformer.init_cache(cfg, 2, 10)
    for pos in range(10):
        l, cache = models.transformer.decode_step(
            cfg, params, cache, toks[:, pos], pos
        )
        np.testing.assert_allclose(
            np.asarray(l), np.asarray(logits_train[:, pos]),
            atol=2e-4, rtol=1e-4,
        )


def test_moe_generate_expert_sharded_matches_replicated(mesh_expert):
    """Sharded MoE decoding end-to-end: generate() on a data=2 x expert=4
    mesh (batch over ('data','expert'), expert FFN weights on their ranks,
    T=1 GShard dispatch per step) must produce the SAME greedy tokens as
    the replicated path."""
    import optax

    cfg = models.transformer.Config(
        vocab_size=211, dim=32, n_layers=2, n_heads=4, max_seq_len=48,
        compute_dtype="float32", attention="xla",
        moe_experts=4, moe_capacity_factor=8.0,
    )
    state, _ = train.create_sharded_state(
        lambda r: models.transformer.init(cfg, r),
        optax.sgd(0.1),
        jax.random.key(0),
        mesh=mesh_expert,
        rules=models.transformer.sharding_rules(cfg),
    )
    params_sharded = state.params
    params_local = jax.device_get(params_sharded)

    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, size=(8, 6)).astype(np.int32)

    out_rep = models.transformer.generate(
        cfg, params_local, prompt, max_new_tokens=10
    )
    out_moe = models.transformer.generate(
        cfg, params_sharded, prompt, max_new_tokens=10, mesh=mesh_expert
    )
    np.testing.assert_array_equal(np.asarray(out_rep), np.asarray(out_moe))


def test_moe_composes_with_ulysses():
    """MoE (batch over ('data','expert')) x Ulysses all-to-all CP (r4) on a
    data=2 x expert=2 x seq=2 mesh: one real step, finite loss, and BOTH
    all_to_all families present (the expert dispatch and the seq<->head
    reshard are each all_to_alls — at least 2 layers' worth must appear)."""
    from distributed_tensorflow_examples_tpu.data.pipeline import as_global
    from distributed_tensorflow_examples_tpu.utils import hlo_analysis

    mesh = local_mesh_for_testing({"data": 2, "expert": 2, "seq": 2})
    cfg = models.transformer.Config(
        vocab_size=256, dim=64, n_layers=2, n_heads=4, max_seq_len=64,
        compute_dtype="float32", attention="ulysses", moe_experts=4,
    )
    opt = optax.sgd(0.1)
    state, sh = train.create_sharded_state(
        lambda r: models.transformer.init(cfg, r), opt, jax.random.key(0),
        mesh=mesh, rules=models.transformer.sharding_rules(cfg),
    )
    step = train.build_train_step(
        models.transformer.loss_fn(cfg, mesh=mesh), opt, mesh=mesh,
        state_shardings=sh, batch_spec=models.transformer.batch_spec(cfg),
    )
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, size=(8, 65)).astype(np.int32)
    batch = as_global(
        {"x": toks[:, :-1], "y": toks[:, 1:]}, mesh,
        spec=models.transformer.batch_spec(cfg),
    )
    compiled = step.lower(state, batch).compile()
    s = hlo_analysis.summarize(hlo_analysis.parse_collectives(compiled.as_text()))
    assert s.get("all-to-all", {}).get("count", 0) >= 2, sorted(s)
    state, m = compiled(state, batch)
    assert np.isfinite(float(m["loss"])), m


def test_moe_group_size_plumbs_from_transformer_config():
    """r5: Config.moe_group_size reaches ops.moe.MoEConfig (the dispatch-
    share knob) — and both group sizes train finite."""
    import numpy as np

    from distributed_tensorflow_examples_tpu import models
    from distributed_tensorflow_examples_tpu.models.transformer import _moe_cfg

    for g in (32, 64):
        cfg = models.transformer.Config(
            vocab_size=64, dim=32, n_layers=1, n_heads=4, max_seq_len=64,
            compute_dtype="float32", moe_experts=4, moe_group_size=g,
        )
        assert _moe_cfg(cfg).group_size == g
        p = models.transformer.init(cfg, jax.random.key(0))
        batch = {"x": np.zeros((2, 64), np.int32), "y": np.zeros((2, 64), np.int32)}
        loss, _ = models.transformer.loss_fn(cfg)(p, None, batch, jax.random.key(1))
        assert np.isfinite(float(loss))


# ----------------------------------------------------------------------------
# One chip's share of a dropless expert layer (``apply_share``)
# ----------------------------------------------------------------------------
# Seeded weights and the plain reference of benchmarks/reference/
# longcat_ref.py, float32 operands on both sides so that a choice at a
# near-tie falls the same way: what is left is the order of the sums, and
# the results (of unit size) agree to 2e-5.

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.reference import longcat_ref, weights as ref_weights  # noqa: E402

SHARE_TOL = 2e-5
#: 32 routed experts beside 16 zero-compute ones, 6 choices a token.
C_SHARE = dict(
    hidden_size=64, ffn_hidden_size=128, expert_ffn_hidden_size=32, num_layers=1,
    num_attention_heads=4, kv_lora_rank=32, q_lora_rank=48, qk_rope_head_dim=8,
    qk_nope_head_dim=16, v_head_dim=16, routed_scaling_factor=6, n_routed_experts=32,
    zero_expert_num=16, moe_topk=6, vocab_size=100, rms_norm_eps=1e-5, init_std=0.125,
)


def _share_params(first, held, seed=11):
    """The expert layer's leaves for ``held`` experts from ``first`` on, as
    the reference seeds them (an expert's by its global id), in float32."""
    key = ref_weights.base_key(seed)
    p = longcat_ref.build(longcat_ref.layer_spec(C_SHARE), key, layer=0)["moe"]
    p.update(jax.vmap(lambda e: longcat_ref.expert(C_SHARE, key, 0, e))(
        first + jnp.arange(held)))
    return jax.tree.map(lambda a: a.astype(jnp.float32), p)


def _share(first, held):
    return moe_ops.ShareConfig(
        n_experts=32, n_zero=16, top_k=6, scale=6.0, first=first, held=held)


def _reference_layer(c, u, seed=11):
    key = ref_weights.base_key(seed)
    p = jax.tree.map(
        lambda a: a.astype(jnp.float32),
        longcat_ref.build(longcat_ref.layer_spec(c), key, layer=0)["moe"])
    expert = lambda e: jax.tree.map(
        lambda a: a.astype(jnp.float32), longcat_ref.expert(c, key, 0, e))
    return p, np.asarray(longcat_ref.moe(c, p, expert, u, "float32"))


def test_the_shares_of_four_ranks_add_up_to_the_uncut_layer():
    """THE SHARE TIES TO THE MODEL: the routed parts that ranks 0-3 compute,
    8 of the 32 experts each from one seed, plus the zero-compute part
    counted once, are the uncut reference layer."""
    u = jax.random.normal(jax.random.key(3), (40, 64))
    p_ref, whole = _reference_layer(dict(C_SHARE), u)
    # The zero-compute part alone, from the reference's own routing.
    choice, w = longcat_ref.route(C_SHARE, p_ref, u)
    zero = np.asarray(jnp.sum(jnp.where(choice >= 32, w, 0.0), -1, keepdims=True) * u)
    routed = np.zeros_like(whole)
    counts = []
    for rank in range(4):
        m, c = moe_ops.apply_share(
            _share_params(8 * rank, 8), u, _share(8 * rank, 8), dtype=jnp.float32)
        # Each rank's own result against the reference given the same share.
        _, part = _reference_layer(dict(C_SHARE, experts_held=8, expert_first=8 * rank), u)
        assert np.abs(np.asarray(m) - part).max() < SHARE_TOL
        routed += np.asarray(m) - zero
        counts.append({k: int(v) for k, v in c.items()})
    assert np.abs(whole).max() > 0.5
    assert np.abs(routed + zero - whole).max() < 4 * SHARE_TOL
    # Every choice is on some rank's expert or on a zero-compute one.
    assert all(c["choices"] == 40 * 6 and c["calls"] == 1 for c in counts)
    assert sum(c["choices_held"] for c in counts) + counts[0]["choices_zero"] == 40 * 6
    assert all(c["choices_zero"] == counts[0]["choices_zero"] for c in counts)
    assert all(0 < c["experts_touched"] <= 8 for c in counts)


def _by_hand(p, u, share, live=None):
    """The layer in NumPy, a token and a choice at a time."""
    u = np.asarray(u, np.float64)
    router = np.asarray(p["router"]["kernel"], np.float64)
    z = u @ router
    s = np.exp(z - z.max(-1, keepdims=True))
    s /= s.sum(-1, keepdims=True)
    out = np.zeros_like(u)
    silu = lambda x: x / (1 + np.exp(-x))
    chosen = []
    for t in range(u.shape[0]):
        order = np.argsort(-(s[t] + np.asarray(p["router"]["bias"], np.float64)), kind="stable")
        chosen.append(order[:share.top_k].tolist())
        if live is not None and not live[t]:
            continue
        for e in order[:share.top_k]:
            w = share.scale * s[t, e]
            if e >= share.n_experts:
                out[t] += w * u[t]
            elif share.first <= e < share.first + share.held:
                g, up, down = (np.asarray(p[k][e - share.first], np.float64)
                               for k in ("gate", "up", "down"))
                out[t] += w * ((silu(u[t] @ g) * (u[t] @ up)) @ down)
    return out, chosen


def test_the_bias_moves_a_choice_and_not_a_weight():
    """With a bias that outweighs every score, each token's choices are the
    biased experts - and each still weighs ``scale x s``, its own score."""
    u = jax.random.normal(jax.random.key(5), (12, 64))
    share = _share(8, 8)
    p = _share_params(8, 8)
    plain, chosen_plain = _by_hand(p, u, share)
    favoured = [9, 40, 2, 47, 15, 33]  # held, zero, absent, zero, held, zero
    p_biased = dict(p, router=dict(
        p["router"], bias=jnp.zeros((48,)).at[jnp.asarray(favoured)].set(10.0)))
    want, chosen = _by_hand(p_biased, u, share)
    assert all(sorted(c) == sorted(favoured) for c in chosen)
    assert any(sorted(c) != sorted(favoured) for c in chosen_plain)
    m, counts = moe_ops.apply_share(p_biased, u, share, dtype=jnp.float32)
    assert np.abs(np.asarray(m) - want).max() < SHARE_TOL
    assert np.abs(want - plain).max() > 0.05
    assert int(counts["choices_held"]) == 2 * 12 and int(counts["choices_zero"]) == 3 * 12
    assert int(counts["experts_touched"]) == 2
    # Without the bias too, the layer is the sum by hand.
    m, _ = moe_ops.apply_share(p, u, share, dtype=jnp.float32)
    assert np.abs(np.asarray(m) - plain).max() < SHARE_TOL


def test_a_token_with_no_held_choice_gets_exactly_its_zero_compute_part():
    """Every choice steered onto absent and zero-compute experts: no expert
    row, no expert read, and the result is ``(w_a + w_b) u`` - and a row
    that is not live gets nothing and is not counted."""
    u = jax.random.normal(jax.random.key(6), (10, 64))
    share = _share(8, 8)
    p = _share_params(8, 8)
    steer = jnp.zeros((48,)).at[jnp.asarray([0, 1, 2, 30, 36, 45])].set(10.0)
    p = dict(p, router=dict(p["router"], bias=steer))
    live = np.array([True] * 7 + [False] * 3)
    m, counts = moe_ops.apply_share(p, u, share, jnp.asarray(live), dtype=jnp.float32)
    s = jax.nn.softmax(u @ p["router"]["kernel"], axis=-1)
    want = 6.0 * (s[:, 36] + s[:, 45])[:, None] * u
    np.testing.assert_allclose(np.asarray(m)[:7], np.asarray(want)[:7], rtol=1e-5, atol=1e-7)
    assert not np.asarray(m)[7:].any()
    assert {k: int(v) for k, v in counts.items()} == {
        "choices": 42, "choices_held": 0, "choices_zero": 14,
        "experts_touched": 0, "calls": 1, "tokens_reaching": 0}


# ----------------------------------------------------------------------------
# The choice limited to groups (DeepSeek-V2's device-limited routing)
# ----------------------------------------------------------------------------

import dataclasses  # noqa: E402

from benchmarks.reference import deepseek_ref  # noqa: E402
from distributed_tensorflow_examples_tpu.models import layers  # noqa: E402

#: 32 routed experts in 4 groups (a rank each), 2 groups and 6 choices a
#: token, two shared experts.
C_GROUPS = dict(
    hidden_size=64, intermediate_size=128, moe_intermediate_size=32, num_hidden_layers=2,
    first_k_dense_replace=1, moe_layer_freq=1, num_attention_heads=4, kv_lora_rank=32,
    q_lora_rank=48, qk_rope_head_dim=8, qk_nope_head_dim=16, v_head_dim=16,
    n_routed_experts=32, n_shared_experts=2, n_group=4, topk_group=2,
    num_experts_per_tok=6, routed_scaling_factor=16.0, vocab_size=100, rms_norm_eps=1e-6,
    init_std=0.125,
)


def _grouped(first=0, held=32, **over):
    return moe_ops.ShareConfig(**dict(dict(
        n_experts=32, n_zero=0, top_k=6, scale=16.0, first=first, held=held,
        n_group=4, top_groups=2), **over))


def _grouped_layer(first, held, seed=13):
    """Layer 1's ``moe`` leaves for ``held`` experts from ``first`` on and
    its ``shared`` leaves, as the reference seeds them, in float32."""
    key = ref_weights.base_key(seed)
    p = deepseek_ref.build(deepseek_ref.layer_spec(C_GROUPS, "moe"), key, layer=1)
    p["moe"].update(jax.vmap(lambda e: deepseek_ref.expert(C_GROUPS, key, 1, e))(
        first + jnp.arange(held)))
    return jax.tree.map(lambda a: a.astype(jnp.float32), p)


def test_the_grouped_shares_of_four_ranks_and_the_shared_expert_once_are_the_uncut_layer():
    """THE SHARE TIES TO THE MODEL: the routed parts that ranks 0-3 compute,
    a group of 8 experts each from one seed, plus the shared experts counted
    ONCE, are the uncut reference layer; every token's held choices lie on
    at most ``top_groups`` = 2 ranks.  In float32 on both sides: 2e-5 is
    the order of the sums, where a wrongly kept group changes the layer by
    0.1 and more."""
    u = jax.random.normal(jax.random.key(3), (40, 64))
    whole_p = _grouped_layer(0, 32)
    key = ref_weights.base_key(13)
    expert = lambda e: jax.tree.map(
        lambda a: a.astype(jnp.float32), deepseek_ref.expert(C_GROUPS, key, 1, e))
    whole = np.asarray(deepseek_ref.moe(C_GROUPS, whole_p, expert, u, "float32"))
    shared = np.asarray(layers.gated_mlp(whole_p["shared"], u, dtype=jnp.float32))
    total = shared.copy()
    reached = np.zeros((40, 4), bool)
    counts = []
    for rank in range(4):
        share = _grouped(8 * rank, 8)
        p = _grouped_layer(8 * rank, 8)["moe"]
        m, c = moe_ops.apply_share(p, u, share, dtype=jnp.float32)
        part = np.asarray(deepseek_ref.routed(
            dict(C_GROUPS, experts_held=8, expert_first=8 * rank), p, expert, u, "float32"))
        assert np.abs(np.asarray(m) - part).max() < SHARE_TOL
        total += np.asarray(m)
        reached[:, rank] = np.abs(np.asarray(m)).max(axis=-1) > 0
        counts.append({k: int(v) for k, v in c.items()})
        assert counts[-1]["tokens_reaching"] == reached[:, rank].sum()
    assert np.abs(whole - shared).max() > 0.1  # the routed part weighs
    assert np.abs(total - whole).max() < 4 * SHARE_TOL
    assert (reached.sum(axis=1) <= 2).all() and (reached.sum(axis=1) == 2).any()
    assert all(c["choices"] == 40 * 6 and c["choices_zero"] == 0 for c in counts)
    assert sum(c["choices_held"] for c in counts) == 40 * 6  # each on some rank
    for c in counts:
        assert c["tokens_reaching"] <= c["choices_held"] <= 6 * c["tokens_reaching"]


def _choice_by_hand(s, share):
    """The source's form in NumPy: group maxima, the best groups, the
    scores outside them zeroed, the largest of what is left (ties to the
    lower id, as ``lax.top_k`` breaks them)."""
    T, G = s.shape[0], share.n_group
    per = share.n_experts // G
    out = []
    for t in range(T):
        best = np.argsort(-s[t].reshape(G, per).max(axis=1), kind="stable")[:share.top_groups]
        masked = np.where(np.isin(np.arange(share.n_experts) // per, best), s[t], 0.0)
        out.append(np.argsort(-masked, kind="stable")[:share.top_k])
    return np.asarray(out)


@pytest.mark.parametrize("near_ties", [False, True])
def test_the_grouped_choice_is_the_sources_reshape_max_topk_form(near_ties):
    """On random scores, and on scores rounded to three digits so that a
    third of the rows have equal group maxima or equal candidates: the
    program's choice, the reference's (which writes the source's form) and
    NumPy's by hand are the same experts in the same order."""
    s = jax.nn.softmax(2.0 * jax.random.normal(jax.random.key(8), (200, 32)), axis=-1)
    if near_ties:
        s = jnp.round(s, 2)
        tied = np.asarray([len(set(r.reshape(4, 8).max(1).tolist())) < 4 for r in np.asarray(s)])
        assert tied.sum() > 20
    share = _grouped()
    got, score = (np.asarray(a) for a in moe_ops.share_choice(s, share))
    ref, w = deepseek_ref.choose(C_GROUPS, s)
    np.testing.assert_array_equal(got, np.asarray(ref))
    np.testing.assert_array_equal(got, _choice_by_hand(np.asarray(s), share))
    np.testing.assert_array_equal(score, np.asarray(w))
    # At most two groups a row wherever the kept groups have six scores
    # above zero (a choice past them weighs nothing), and not the free
    # choice's experts.
    assert all(len(set(r[v > 0] // 8)) <= 2 for r, v in zip(got, score))
    np.testing.assert_array_equal(
        score[score > 0], np.take_along_axis(np.asarray(s), got, axis=1)[score > 0])
    free, _ = moe_ops.share_choice(s, _grouped(top_groups=0, n_group=0))
    assert (np.sort(np.asarray(free), 1) != np.sort(got, 1)).any()


def test_top_groups_zero_is_the_free_choice_bit_for_bit():
    """LongCat's path: with no groups the choice is ``top_k`` of ``s +
    bias`` and nothing else, whatever ``n_group`` says, and a router
    without a bias is chosen on ``s`` alone."""
    s = jax.nn.softmax(jax.random.normal(jax.random.key(9), (50, 48)), axis=-1)
    bias = 0.01 * jax.random.normal(jax.random.key(10), (48,))
    for share in (_share(8, 8), dataclasses.replace(_share(8, 8), n_group=4)):
        choice, score = moe_ops.share_choice(s, share, bias)
        np.testing.assert_array_equal(
            np.asarray(choice), np.asarray(jax.lax.top_k(s + bias, 6)[1]))
        np.testing.assert_array_equal(  # weighted by its score, not score + bias
            np.asarray(score), np.take_along_axis(np.asarray(s), np.asarray(choice), axis=1))
        np.testing.assert_array_equal(
            np.asarray(moe_ops.share_choice(s, share)[0]), np.asarray(jax.lax.top_k(s, 6)[1]))
    # The layer's result with a zero bias is the layer's without one.
    u = jax.random.normal(jax.random.key(5), (12, 64))
    p = _share_params(8, 8)
    p0 = dict(p, router=dict(p["router"], bias=jnp.zeros((48,))))
    bare = dict(p, router={"kernel": p["router"]["kernel"]})
    a, ca = moe_ops.apply_share(p0, u, _share(8, 8), dtype=jnp.float32)
    b, cb = moe_ops.apply_share(bare, u, _share(8, 8), dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert {k: int(v) for k, v in ca.items()} == {k: int(v) for k, v in cb.items()}


def test_a_token_whose_groups_exclude_the_held_one_gets_exactly_its_shared_part():
    """The router steered so that rows 0-5 keep groups 0 and 3 and rows 6-9
    groups 1 and 2, group 1 held: the first get no expert row and EXACTLY
    the shared experts' result, the rest reach this device; a row that is
    not live gets nothing and is not counted."""
    u = jax.random.normal(jax.random.key(6), (10, 64))
    layer = _grouped_layer(8, 8)
    # The steering direction is kept out of u: the router reads it alone.
    steer = np.zeros((64, 32), np.float32)
    steer[0, :8] = steer[0, 24:] = 5.0   # u[:, 0] > 0: groups 0 and 3
    steer[0, 8:24] = -5.0                # u[:, 0] < 0: groups 1 and 2
    u = u.at[:6, 0].set(1.0).at[6:, 0].set(-1.0)
    p = dict(layer["moe"], router={"kernel": layer["moe"]["router"]["kernel"] + steer})
    live = np.array([True] * 5 + [False] + [True] * 3 + [False])
    m, counts = moe_ops.apply_share(p, u, _grouped(8, 8), jnp.asarray(live), dtype=jnp.float32)
    m = np.asarray(m)
    assert not m[:6].any() and not m[9].any() and np.abs(m[6:9]).max(axis=1).min() > 0
    shared = np.asarray(layers.gated_mlp(layer["shared"], u, dtype=jnp.float32))
    np.testing.assert_array_equal((m + shared)[:6], shared[:6])
    assert int(counts["choices"]) == 6 * 8 and int(counts["tokens_reaching"]) == 3
    assert 3 <= int(counts["choices_held"]) <= 18 and int(counts["choices_zero"]) == 0


def test_a_held_range_that_is_not_whole_groups_raises():
    for first, held in ((4, 8), (8, 4), (0, 12)):
        with pytest.raises(ValueError, match="not whole groups"):
            _grouped(first, held)
    with pytest.raises(ValueError, match="equal groups"):
        _grouped(n_group=5)
    with pytest.raises(ValueError, match="equal groups"):
        moe_ops.ShareConfig(n_experts=32, n_zero=8, top_k=6, scale=1.0, first=0, held=8,
                            n_group=4, top_groups=2)
    with pytest.raises(ValueError, match="cannot give"):
        _grouped(top_groups=1, top_k=9)
    with pytest.raises(ValueError, match="on the scores alone"):
        moe_ops.share_choice(jnp.ones((2, 32)) / 32, _grouped(), bias=jnp.zeros((32,)))
    assert _grouped(8, 16).held == 16  # two whole groups are a share


def test_a_counted_call_adds_to_the_entries_a_model_keeps_and_to_no_other():
    """``apply_share_counted`` is ``apply_share`` with its counts added to
    the counters a model keeps (models/longcat.py and models/deepseek.py
    keep different ones); a chunk's call adds to its own entries too, a
    step's leaves them as they were."""
    u = jax.random.normal(jax.random.key(8), (12, 64))
    p, share = _grouped_layer(8, 8)["moe"], _grouped(8, 8)
    live = jnp.arange(12) < 9
    kept, chunk = ("choices", "choices_held", "tokens_reaching"), ("choices_held",)
    zero = moe_ops.share_counters(kept, chunk)
    assert sorted(zero) == ["moe_choices", "moe_choices_held", "moe_chunk_choices_held",
                            "moe_tokens_reaching"]
    assert all(v.dtype == jnp.int32 and int(v) == 0 for v in zero.values())
    m, counts = moe_ops.apply_share(p, u, share, live, dtype=jnp.float32)
    m1, step = moe_ops.apply_share_counted(p, u, share, live, zero, dtype=jnp.float32)
    m2, both = moe_ops.apply_share_counted(
        p, u, share, live, step, chunk_counts=chunk, dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(m1), np.asarray(m))
    np.testing.assert_array_equal(np.asarray(m2), np.asarray(m))
    assert {k: int(v) for k, v in step.items()} == {
        "moe_choices": 6 * 9, "moe_choices_held": int(counts["choices_held"]),
        "moe_tokens_reaching": int(counts["tokens_reaching"]), "moe_chunk_choices_held": 0}
    assert {k: int(v) for k, v in both.items()} == {
        "moe_choices": 2 * 6 * 9, "moe_choices_held": 2 * int(counts["choices_held"]),
        "moe_tokens_reaching": 2 * int(counts["tokens_reaching"]),
        "moe_chunk_choices_held": int(counts["choices_held"])}
    assert int(counts["choices_held"]) > 0


# ----------------------------------------------------------------------------
# Sigmoid scores, normalised weights, a bias in the choice (AFMoE's router)
# ----------------------------------------------------------------------------

from benchmarks.reference import afmoe_ref  # noqa: E402

#: 32 experts, 4 a token, one shared expert; the reference's layer 2 (the
#: first expert layer of a model with two leading dense ones).
C_SIGMOID = dict(
    hidden_size=64, intermediate_size=128, moe_intermediate_size=32, num_hidden_layers=4,
    num_dense_layers=2, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    layer_types=("sliding_attention",) * 4, sliding_window=16, num_experts=32,
    num_experts_per_tok=4, num_shared_experts=1, route_scale=2.826, vocab_size=100,
    rms_norm_eps=1e-5, rope_theta=1e4, init_std=0.125, router_std_factor=0.25,
    expert_bias_std=0.05,
)


def _sigmoid(first=0, held=32, **over):
    return moe_ops.ShareConfig(**dict(dict(
        n_experts=32, n_zero=0, top_k=4, scale=2.826, first=first, held=held,
        scoring="sigmoid", normalise=True), **over))


def _sigmoid_layer(first, held, seed=17):
    """Layer 2's ``moe`` leaves for ``held`` experts from ``first`` on and
    its ``shared`` leaves, as the reference seeds them, in float32."""
    key = ref_weights.base_key(seed)
    p = afmoe_ref.build(afmoe_ref.layer_spec(C_SIGMOID, False), key, layer=2)
    p["moe"].update(jax.vmap(lambda e: afmoe_ref.expert(C_SIGMOID, key, 2, e))(
        first + jnp.arange(held)))
    return jax.tree.map(lambda a: a.astype(jnp.float32), p)


def _expert_of(moe):
    """``expert_fn`` of the reference's ``routed`` over stacked leaves."""
    return lambda e: {k: moe[k][e] for k in ("gate", "up", "down")}


def _sigmoid_by_hand(p, u, share, leak=False):
    """The layer in NumPy, a token and a choice at a time: sigmoid scores,
    the ``top_k`` largest of ``s + bias``, weights ``scale x s / (sum of the
    chosen s + 1e-20)``.  ``leak``: the fault, the bias in the weights."""
    u = np.asarray(u, np.float64)
    s = 1.0 / (1.0 + np.exp(-(u @ np.asarray(p["router"]["kernel"], np.float64))))
    bias = np.asarray(p["router"]["bias"], np.float64)
    silu = lambda x: x / (1 + np.exp(-x))
    out, chosen, weights = np.zeros_like(u), [], []
    for t in range(u.shape[0]):
        pick = np.argsort(-(s[t] + bias), kind="stable")[:share.top_k]
        score = s[t, pick] + (bias[pick] if leak else 0.0)
        w = share.scale * score / (score.sum() + 1e-20)
        chosen.append(pick.tolist())
        weights.append(w)
        for e, w_e in zip(pick, w):
            if share.first <= e < share.first + share.held:
                g, up, down = (np.asarray(p[k][e - share.first], np.float64)
                               for k in ("gate", "up", "down"))
                out[t] += w_e * ((silu(u[t] @ g) * (u[t] @ up)) @ down)
    return out, chosen, np.asarray(weights)


def test_sigmoid_normalised_biased_choice_is_the_layer_written_out():
    """The new fields against the sum by hand AND against the plain
    reference's routed part; a token's weights add up to ``scale``; and THE
    BIAS CHANGES THE CHOICE BUT NOT A WEIGHT - with the bias in the weights
    (the fault) the sum by hand is another layer, which the program is not."""
    u = jax.random.normal(jax.random.key(5), (24, 64))
    p, share = _sigmoid_layer(0, 32), _sigmoid()
    want, chosen, w = _sigmoid_by_hand(p["moe"], u, share)
    np.testing.assert_allclose(w.sum(axis=1), share.scale, rtol=1e-12)
    m, counts = moe_ops.apply_share(p["moe"], u, share, dtype=jnp.float32)
    assert np.abs(want).max() > 0.5
    assert np.abs(np.asarray(m) - want).max() < SHARE_TOL
    assert int(counts["choices_held"]) == int(counts["choices"]) == 24 * 4
    ref = afmoe_ref.routed(C_SIGMOID, p["moe"], _expert_of(p["moe"]), u, "float32")
    assert np.abs(np.asarray(m) - np.asarray(ref)).max() < SHARE_TOL
    # The seeded bias moves some token's choice ...
    no_bias = dict(p["moe"], router=dict(p["moe"]["router"], bias=jnp.zeros((32,))))
    _, chosen_plain, _ = _sigmoid_by_hand(no_bias, u, share)
    assert any(sorted(a) != sorted(b) for a, b in zip(chosen, chosen_plain))
    # ... and a bias that outweighs every score picks the experts and still
    # weighs each by its own score: the four weights are the four scores'.
    favoured = [3, 9, 20, 31]
    steered = dict(p["moe"], router=dict(
        p["moe"]["router"], bias=jnp.zeros((32,)).at[jnp.asarray(favoured)].set(10.0)))
    want_steered, chosen, _ = _sigmoid_by_hand(steered, u, share)
    assert all(sorted(c) == favoured for c in chosen)
    m, _ = moe_ops.apply_share(steered, u, share, dtype=jnp.float32)
    assert np.abs(np.asarray(m) - want_steered).max() < SHARE_TOL
    leaked, _, _ = _sigmoid_by_hand(p["moe"], u, share, leak=True)
    assert np.abs(leaked - want).max() > 100 * SHARE_TOL


def test_the_sigmoid_shares_of_four_ranks_and_the_shared_expert_once_are_the_uncut_layer():
    """THE SHARE TIES TO THE MODEL under the new scoring too: with ``held``
    < ``n_experts`` the routed parts of ranks 0-3, 8 of the 32 experts each
    from one seed, plus the shared expert counted once, are the reference's
    whole expert layer - the weights are normalised over a token's CHOSEN
    experts wherever they live, not over those a rank holds."""
    u = jax.random.normal(jax.random.key(3), (40, 64))
    whole = _sigmoid_layer(0, 32)
    want = np.asarray(
        afmoe_ref.routed(C_SIGMOID, whole["moe"], _expert_of(whole["moe"]), u, "float32")
        + afmoe_ref._gated(u, afmoe_ref._kernels(whole["shared"]), "float32"))
    total = np.array(layers.gated_mlp(whole["shared"], u, dtype=jnp.float32))
    held = 0
    for rank in range(4):
        p = _sigmoid_layer(8 * rank, 8)
        m, c = moe_ops.apply_share(p["moe"], u, _sigmoid(8 * rank, 8), dtype=jnp.float32)
        assert int(c["choices"]) == 40 * 4 and 0 < int(c["experts_touched"]) <= 8
        total += np.asarray(m)
        held += int(c["choices_held"])
    assert held == 40 * 4  # every choice is on some rank's expert
    assert np.abs(want).max() > 0.5
    assert np.abs(total - want).max() < 4 * SHARE_TOL


@pytest.mark.parametrize("model", ["longcat", "deepseek"])
def test_the_softmax_defaults_give_the_two_expert_models_their_layers_bit_for_bit(model):
    """The two fields default to what ``apply_share`` was before it had
    them: a share that states ``softmax`` and no normalising IS the models'
    own share, its result and its lowered program are theirs to the bit
    (the models' whole steps and chunks were compared as lowered text
    before and after the change; PERF.md section 6, PR 39), and an unknown
    scoring is refused."""
    from distributed_tensorflow_examples_tpu.models import deepseek, longcat

    if model == "longcat":
        share, p = longcat.Config(**{
            k: v for k, v in C_SHARE.items() if k != "init_std"},
            experts_held=8, expert_first=8).share, _share_params(8, 8)
    else:
        share = deepseek.Config(**{
            k: v for k, v in C_GROUPS.items() if k != "init_std"},
            experts_held=8, expert_first=8).share
        p = _grouped_layer(8, 8)["moe"]
    assert (share.scoring, share.normalise) == ("softmax", False)
    stated = dataclasses.replace(share, scoring="softmax", normalise=False)
    assert stated == share
    u = jax.random.normal(jax.random.key(9), (20, 64))
    run = lambda s: jax.jit(lambda p, u: moe_ops.apply_share(p, u, s, dtype=jnp.float32)[0])
    assert run(share).lower(p, u).as_text() == run(stated).lower(p, u).as_text()
    assert np.array_equal(np.asarray(run(share)(p, u)), np.asarray(run(stated)(p, u)))
    # The other scoring is another program and another layer.
    other = dataclasses.replace(share, scoring="sigmoid")
    assert run(other).lower(p, u).as_text() != run(share).lower(p, u).as_text()
    assert np.abs(np.asarray(run(other)(p, u)) - np.asarray(run(share)(p, u))).max() > 1e-3
    with pytest.raises(ValueError, match="scoring"):
        dataclasses.replace(share, scoring="tanh")


# ----------------------------------------------------------------------------
# The plan apart from the product (a router that reads another tensor)
# ----------------------------------------------------------------------------

from benchmarks.reference import smallthinker_ref  # noqa: E402

#: 32 ReLU-gated experts, 4 a token, the softmax over the chosen.
C_AHEAD = dict(
    hidden_size=64, moe_ffn_hidden_size=32, num_hidden_layers=2, num_attention_heads=14,
    num_key_value_heads=2, head_dim=16, sliding_window_layout=(0, 1), rope_layout=(0, 1),
    sliding_window_size=16, moe_num_primary_experts=32, moe_num_active_primary_experts=4,
    vocab_size=100, rms_norm_eps=1e-6, rope_theta=1e4, init_std=0.125,
    router_spread=0.125, out_std_factor=1.0,
)


def _ahead(first=0, held=32):
    return moe_ops.ShareConfig(
        n_experts=32, n_zero=0, top_k=4, scale=1.0, first=first, held=held,
        scoring="softmax", normalise=True, activation="relu")


def _ahead_layer(first, held, seed=19):
    key = ref_weights.base_key(seed)
    p = smallthinker_ref.build(smallthinker_ref.layer_spec(C_AHEAD, 1), key, layer=1)["moe"]
    p.update(jax.vmap(lambda e: smallthinker_ref.expert(C_AHEAD, key, 1, e))(
        first + jnp.arange(held)))
    return jax.tree.map(lambda a: a.astype(jnp.float32), p)


@pytest.mark.parametrize("model", ["longcat", "deepseek", "trinity"])
def test_the_plan_and_the_product_apart_are_apply_share_bit_for_bit(model):
    """``share_plan`` on ``u`` then ``apply_share_plan`` on the same ``u``
    and ``share_counts`` of the plan ARE ``apply_share`` - result, counts and
    lowered program - on the settings of the three models that call it whole
    (zero-compute experts and a scale; a choice limited to groups; sigmoid
    scores normalised under a bias), with some rows not live."""
    share, p = {
        "longcat": lambda: (_share(8, 8), _share_params(8, 8)),
        "deepseek": lambda: (_grouped(8, 8), _grouped_layer(8, 8)["moe"]),
        "trinity": lambda: (_sigmoid(), _sigmoid_layer(0, 32)["moe"]),
    }[model]()
    assert share.activation == "silu"
    u = jax.random.normal(jax.random.key(21), (20, 64))
    live = jnp.arange(20) % 5 != 3

    def apart(p, u, live):
        plan = moe_ops.share_plan(p["router"], u, share, live)
        m = moe_ops.apply_share_plan(p, u, plan, share, dtype=jnp.float32)
        return m, moe_ops.share_counts(plan, share)

    whole = jax.jit(lambda p, u, live: moe_ops.apply_share(p, u, share, live, dtype=jnp.float32))
    assert jax.jit(apart).lower(p, u, live).as_text().replace("apart", "_lambda") == \
        whole.lower(p, u, live).as_text().replace("<lambda>", "_lambda")
    (m, counts), (m_whole, counts_whole) = jax.jit(apart)(p, u, live), whole(p, u, live)
    assert np.abs(np.asarray(m)).max() > 0.1
    assert np.array_equal(np.asarray(m), np.asarray(m_whole))
    assert {k: int(v) for k, v in counts.items()} == {
        k: int(v) for k, v in counts_whole.items()}


def test_the_shares_of_four_ranks_under_a_plan_from_another_tensor_are_the_uncut_layer():
    """THE SHARE TIES TO THE MODEL for the split too: the router reads ``x``
    and the experts ``u``; with ``held`` < ``n_experts`` the parts of ranks
    0-3, 8 of the 32 ReLU-gated experts each from one seed, every rank
    planning from the same ``x``, add up to the reference's whole layer (the
    weights are the softmax over a token's CHOSEN experts wherever they
    live) - and a plan from ``u`` itself is another layer."""
    x = jax.random.normal(jax.random.key(3), (40, 64))
    u = jax.random.normal(jax.random.key(4), (40, 64))
    whole = _ahead_layer(0, 32)
    choice, w = smallthinker_ref.route(C_AHEAD, whole, x)
    want = np.asarray(smallthinker_ref.routed(
        C_AHEAD, choice, w, _expert_of(whole), u, "float32"))
    total, held = np.zeros_like(want), 0
    for rank in range(4):
        p, share = _ahead_layer(8 * rank, 8), _ahead(8 * rank, 8)
        plan = moe_ops.share_plan(p["router"], x, share)
        total += np.asarray(moe_ops.apply_share_plan(p, u, plan, share, dtype=jnp.float32))
        counts = moe_ops.share_counts(plan, share)
        assert int(counts["choices"]) == 40 * 4 and 0 < int(counts["experts_touched"]) <= 8
        held += int(counts["choices_held"])
    assert held == 40 * 4  # every choice is on some rank's expert
    assert np.abs(want).max() > 0.5
    assert np.abs(total - want).max() < 4 * SHARE_TOL
    late, _ = moe_ops.apply_share(whole, u, _ahead(), dtype=jnp.float32)
    assert np.abs(np.asarray(late) - want).max() > 0.1
    with pytest.raises(ValueError, match="activation"):
        dataclasses.replace(_ahead(), activation="gelu")


# -- ungated experts read in another WIDTH than the router (Nemotron-H) ---------

from benchmarks.reference import nemotron_h_ref  # noqa: E402

#: 32 ungated squared-ReLU experts in a latent of 32 under a hidden of 64,
#: 6 a token under a sigmoid router with a bias in the choice.
C_LATENT = dict(
    hidden_size=64, n_routed_experts=32, num_experts_per_tok=6, moe_latent_size=32,
    moe_intermediate_size=48, moe_shared_expert_intermediate_size=96,
    routed_scaling_factor=5.0, hybrid_override_pattern="E", expert_bias_std=0.05,
    expert_down_factor=0.5)


def _latent(first=0, held=32, **changes):
    return moe_ops.ShareConfig(**{**dict(
        n_experts=32, n_zero=0, top_k=6, scale=5.0, first=first, held=held,
        scoring="sigmoid", normalise=True, activation="relu2"), **changes})


def _latent_layer(first, held):
    key = ref_weights.base_key(17)
    p = nemotron_h_ref.build(nemotron_h_ref.layer_spec(C_LATENT, 0), key, layer=0)["moe"]
    p.update(jax.vmap(lambda e: nemotron_h_ref.expert(C_LATENT, key, 0, e))(
        first + jnp.arange(held)))
    return jax.tree.map(lambda a: a.astype(jnp.float32), p)


@pytest.mark.parametrize("ranks", [1, 4])
def test_the_ungated_shares_under_a_plan_of_another_width_are_the_uncut_routed_sum(ranks):
    """THE SHARE TIES TO THE MODEL where the router reads 64 values a token
    and the experts 32: every rank plans from the same hidden ``u`` with the
    whole router and multiplies the LATENT ``l``; the parts of ``ranks``
    ranks, 32 / ``ranks`` ungated experts each and NO ``gate`` leaf among
    them (which is all that says so), add up to the reference's routed
    latent sum."""
    u = jax.random.normal(jax.random.key(3), (40, 64))
    whole = _latent_layer(0, 32)
    latent = u @ whole["latent_in"]["kernel"]
    choice, w = nemotron_h_ref.route(C_LATENT, whole, u)
    want = np.zeros((40, 32), np.float32)
    for e in range(32):
        w_e = np.where(np.asarray(choice) == e, np.asarray(w), 0.0).sum(-1, keepdims=True)
        want += w_e * np.asarray(
            jnp.square(jax.nn.relu(latent @ whole["up"][e])) @ whole["down"][e])
    per = 32 // ranks
    total, held = np.zeros_like(want), 0
    for rank in range(ranks):
        p, share = _latent_layer(per * rank, per), _latent(per * rank, per)
        assert "gate" not in p
        plan = moe_ops.share_plan(p["router"], u, share)
        total += np.asarray(moe_ops.apply_share_plan(p, latent, plan, share, dtype=jnp.float32))
        held += int(moe_ops.share_counts(plan, share)["choices_held"])
    assert held == 40 * 6 and np.abs(want).max() > 0.5
    assert np.abs(total - want).max() < 4 * SHARE_TOL
