"""Transformer LM (growth-path flagship): trains under dp x tp x sp, and the
parallel placement does not change numerics vs a single-device run."""

import numpy as np
import jax
import jax.numpy as jnp
import optax
import pytest

from distributed_tensorflow_examples_tpu import models, train
from distributed_tensorflow_examples_tpu.data.pipeline import as_global
from distributed_tensorflow_examples_tpu.parallel import local_mesh_for_testing

CFG = models.transformer.Config(
    vocab_size=128, dim=32, n_layers=2, n_heads=4, max_seq_len=64,
    compute_dtype="float32",
)


def _batches(n, b=4, t=16, seed=0):
    # Markov-structured stream (learnable bigrams) — random tokens would
    # leave nothing for the loss to descend on in a short test.
    from distributed_tensorflow_examples_tpu.data import datasets

    ids = datasets._synthetic_token_stream(8192, 128, seed)
    it = datasets.lm_batches(ids, batch_size=b, seq_len=t)
    return [next(it) for _ in range(n)]


def _run(mesh, raw, rules, spec=None):
    from jax.sharding import PartitionSpec as P

    spec = spec if spec is not None else P("data")
    opt = optax.adam(1e-3)
    state, shardings = train.create_sharded_state(
        lambda r: models.transformer.init(CFG, r),
        opt,
        jax.random.key(0),
        mesh=mesh,
        rules=rules,
    )
    step = train.build_train_step(
        models.transformer.loss_fn(CFG, mesh=mesh),
        opt,
        mesh=mesh,
        state_shardings=shardings,
        batch_spec=spec,
    )
    losses = []
    for b in raw:
        state, m = step(state, as_global(b, mesh, spec=spec))
        losses.append(float(m["loss"]))
    return losses


def test_transformer_trains_dp_tp_sp():
    from jax.sharding import PartitionSpec as P

    mesh = local_mesh_for_testing({"data": 2, "seq": 2, "model": 2})
    raw = _batches(20)
    losses = _run(mesh, raw, models.transformer.SHARDING_RULES, spec=P("data", "seq"))
    assert losses[-1] < losses[0] * 0.98, losses
    assert all(np.isfinite(losses))


def test_transformer_parallel_matches_single_device():
    from jax.sharding import PartitionSpec as P

    raw = _batches(4)
    mesh1 = local_mesh_for_testing({"data": 1})
    mesh8 = local_mesh_for_testing({"data": 2, "seq": 2, "model": 2})
    l1 = _run(mesh1, raw, ())
    l8 = _run(mesh8, raw, models.transformer.SHARDING_RULES, spec=P("data", "seq"))
    np.testing.assert_allclose(l1, l8, rtol=5e-4)


def test_transformer_flash_under_mesh():
    """attention='flash' on a dp x tp mesh (seq unsharded) routes through
    the shard_map-wrapped Pallas kernel and matches the xla path."""
    from jax.sharding import PartitionSpec as P

    cfg_flash = models.transformer.Config(
        vocab_size=128, dim=32, n_layers=1, n_heads=4, max_seq_len=64,
        compute_dtype="float32", attention="flash",
    )
    cfg_xla = models.transformer.Config(
        vocab_size=128, dim=32, n_layers=1, n_heads=4, max_seq_len=64,
        compute_dtype="float32", attention="xla",
    )
    mesh = local_mesh_for_testing({"data": 2, "model": 2})
    raw = _batches(2, b=4, t=32)
    params = models.transformer.init(cfg_flash, jax.random.key(0))
    from distributed_tensorflow_examples_tpu.data.pipeline import as_global as ag

    b = ag(raw[0], mesh, spec=P("data", "seq"))
    f_flash = jax.jit(
        lambda p, x: models.transformer.apply(cfg_flash, p, x, mesh=mesh)
    )
    f_xla = jax.jit(lambda p, x: models.transformer.apply(cfg_xla, p, x, mesh=mesh))
    o1 = f_flash(params, b["x"])
    o2 = f_xla(params, b["x"])
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=2e-4, atol=2e-4)


def test_decode_step_matches_full_forward():
    """KV-cache decoding (models/transformer.py decode_step) must reproduce
    the training forward's logits position by position (teacher-forced)."""
    cfg = models.transformer.Config(
        vocab_size=97, dim=32, n_layers=2, n_heads=4, max_seq_len=16,
        attention="xla", compute_dtype="float32",
    )
    params = models.transformer.init(cfg, jax.random.key(0))
    x = jax.random.randint(jax.random.key(1), (3, 10), 0, 97)
    ref = models.transformer.apply(cfg, params, x)  # [B, T, V]

    cache = models.transformer.init_cache(cfg, 3, 10)
    step = jax.jit(
        lambda c, t, p: models.transformer.decode_step(cfg, params, c, t, p)
    )
    for pos in range(10):
        logits, cache = step(cache, x[:, pos], pos)
        np.testing.assert_allclose(
            np.asarray(logits), np.asarray(ref[:, pos]), rtol=2e-4, atol=2e-4
        )


def test_generate_greedy_continues_prompt():
    cfg = models.transformer.Config(
        vocab_size=61, dim=32, n_layers=2, n_heads=4, max_seq_len=24,
        attention="xla", compute_dtype="float32",
    )
    params = models.transformer.init(cfg, jax.random.key(0))
    prompt = jax.random.randint(jax.random.key(2), (2, 6), 0, 61)
    out = models.transformer.generate(cfg, params, prompt, max_new_tokens=8)
    assert out.shape == (2, 14)
    np.testing.assert_array_equal(np.asarray(out[:, :6]), np.asarray(prompt))
    # Greedy continuation must equal argmax of the full forward at each step
    # (the scan's own outputs are self-consistent by the parity test above;
    # here check end-to-end against apply on the generated prefix).
    full = models.transformer.apply(cfg, params, out[:, :-1])
    np.testing.assert_array_equal(
        np.asarray(jnp.argmax(full[:, 5:], axis=-1)), np.asarray(out[:, 6:])
    )


def test_remat_matches_no_remat():
    """cfg.remat changes memory scheduling, not numerics."""
    kw = dict(vocab_size=64, dim=32, n_layers=2, n_heads=2, max_seq_len=16,
              attention="xla", compute_dtype="float32")
    p = models.transformer.init(models.transformer.Config(**kw), jax.random.key(0))
    x = jax.random.randint(jax.random.key(1), (2, 16), 0, 64)

    def loss(cfg, p):
        logits = models.transformer.apply(cfg, p, x)
        return jnp.sum(logits.astype(jnp.float32) ** 2) / logits.size

    c0 = models.transformer.Config(**kw)
    c1 = models.transformer.Config(**kw, remat=True)
    l0, g0 = jax.value_and_grad(lambda p: loss(c0, p))(p)
    l1, g1 = jax.value_and_grad(lambda p: loss(c1, p))(p)
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)


def test_chunked_loss_matches_dense():
    """Config.loss_chunks must not change the loss value or the gradients —
    it only regroups the head matmul + CE into scanned chunks (f32 sums are
    reassociated, so allow float tolerance)."""
    import dataclasses

    transformer = models.transformer
    cfg_d = transformer.Config(
        vocab_size=211, dim=32, n_layers=2, n_heads=4, max_seq_len=32,
        compute_dtype="float32",
    )
    cfg_c = dataclasses.replace(cfg_d, loss_chunks=4)
    params = transformer.init(cfg_d, jax.random.key(0))
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg_d.vocab_size, size=(4, 33)).astype(np.int32)
    batch = {"x": toks[:, :-1], "y": toks[:, 1:]}

    def loss_of(cfg):
        f = transformer.loss_fn(cfg)
        def scalar(p):
            l, _ = f(p, {}, batch, jax.random.key(1))
            return l
        return scalar

    ld, gd = jax.value_and_grad(loss_of(cfg_d))(params)
    lc, gc = jax.value_and_grad(loss_of(cfg_c))(params)
    np.testing.assert_allclose(float(ld), float(lc), rtol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6),
        gd, gc,
    )


def test_generate_tp_sharded_matches_replicated(mesh_4x2):
    """TP-sharded decoding (r2 verdict missing #6): generate() on a
    data=4 x model=2 mesh — KV cache sharded over 'model', Megatron dense
    sharding — must produce the SAME greedy tokens as the replicated path,
    and decode_step's per-position logits must agree numerically."""
    import optax

    cfg = models.transformer.Config(
        vocab_size=211, dim=64, n_layers=2, n_heads=4, max_seq_len=48,
        compute_dtype="float32", attention="xla",
    )
    state, _ = train.create_sharded_state(
        lambda r: models.transformer.init(cfg, r),
        optax.sgd(0.1),
        jax.random.key(0),
        mesh=mesh_4x2,
        rules=models.transformer.SHARDING_RULES,
    )
    params_sharded = state.params
    params_local = jax.device_get(params_sharded)

    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, size=(4, 8)).astype(np.int32)

    out_rep = models.transformer.generate(
        cfg, params_local, prompt, max_new_tokens=12
    )
    out_tp = models.transformer.generate(
        cfg, params_sharded, prompt, max_new_tokens=12, mesh=mesh_4x2
    )
    np.testing.assert_array_equal(np.asarray(out_rep), np.asarray(out_tp))

    # Logit-level agreement at one position (summation-order tolerance).
    cache_r = models.transformer.init_cache(cfg, 4, 16)
    cache_s = models.transformer.init_cache(cfg, 4, 16, mesh=mesh_4x2)
    tok = jnp.asarray(prompt[:, 0])
    lr, _ = models.transformer.decode_step(cfg, params_local, cache_r, tok, 0)
    ls, _ = jax.jit(
        lambda p, c, t: models.transformer.decode_step(
            cfg, p, c, t, 0, mesh=mesh_4x2
        )
    )(params_sharded, cache_s, tok)
    np.testing.assert_allclose(np.asarray(lr), np.asarray(ls), atol=2e-4)


def test_decode_step_batch_matches_scalar_pos_bitwise():
    """r19 sequence-slot decode: with every row at the SAME position the
    per-row-pos batched step is byte-identical to decode_step, which is
    that step with its one position given to every row."""
    import numpy as np

    cfg = models.transformer.Config(
        vocab_size=97, dim=32, n_layers=2, n_heads=4, max_seq_len=32,
        compute_dtype="float32",
    )
    params = models.transformer.init(cfg, jax.random.key(1))
    S, T = 3, 16
    cache_a = models.transformer.init_cache(cfg, S, T)
    cache_b = models.transformer.init_cache(cfg, S, T)
    tok = jnp.asarray(np.array([5, 9, 11], np.int32))
    for p in range(4):
        la, cache_a = models.transformer.decode_step(
            cfg, params, cache_a, tok, p
        )
        lb, cache_b = models.transformer.decode_step_batch(
            cfg, params, cache_b, tok, jnp.full((S,), p, jnp.int32)
        )
        assert np.array_equal(np.asarray(la), np.asarray(lb)), p
        tok = jnp.argmax(la, axis=-1).astype(jnp.int32)


def test_decode_step_batch_rows_are_independent_sessions():
    """Per-row positions: row i advanced in a shared slot batch follows
    exactly the trajectory it follows running ALONE — the property that
    lets decode sessions share slots with no cache resets and makes
    served batched decode byte-identical to the unbatched reference."""
    import numpy as np

    cfg = models.transformer.Config(
        vocab_size=97, dim=32, n_layers=2, n_heads=4, max_seq_len=32,
        compute_dtype="float32",
    )
    params = models.transformer.init(cfg, jax.random.key(1))
    S, T = 3, 16
    cache = models.transformer.init_cache(cfg, S, T)
    toks = jnp.asarray(np.array([1, 2, 3], np.int32))
    pos = jnp.zeros((S,), jnp.int32)
    hist = [[1], [2], [3]]
    for _ in range(5):
        logits, cache = models.transformer.decode_step_batch(
            cfg, params, cache, toks, pos
        )
        nxt = np.argmax(np.asarray(logits), axis=-1).astype(np.int32)
        for i in range(S):
            hist[i].append(int(nxt[i]))
        toks = jnp.asarray(nxt)
        pos = pos + 1
    for i in range(S):
        cache1 = models.transformer.init_cache(cfg, 1, T)
        t = jnp.asarray(np.array([hist[i][0]], np.int32))
        for p in range(5):
            l1, cache1 = models.transformer.decode_step(
                cfg, params, cache1, t, p
            )
            n1 = int(np.argmax(np.asarray(l1)[0]))
            assert n1 == hist[i][p + 1], (i, p)
            t = jnp.asarray(np.array([n1], np.int32))


def _junk_cache(tf, cfg, S, T, seed):
    """What earlier sessions left behind: nothing may depend on it, and
    whatever a step does not own must keep it to the bit."""
    rng = np.random.default_rng(seed)
    return jax.tree.map(
        lambda a: jnp.asarray(rng.normal(size=a.shape), a.dtype),
        tf.init_cache(cfg, S, T),
    )


_BLOCKED = models.transformer.Config(
    vocab_size=97, dim=32, n_layers=2, n_heads=4, max_seq_len=32,
    compute_dtype="float32",
)


@pytest.mark.parametrize("other", [0, 7, 8, 19])
def test_a_decode_row_is_bit_equal_wherever_another_row_stands(monkeypatch, other):
    """The step's attention reads whole blocks up to its DEEPEST row: a
    row's logits and the key and value it wrote are the same to the bit
    whether the other row stands before it, at a block's last position,
    at a block's first or at the cache's end (more trips of the loop, each
    an exact no-op for this row) - whatever the other row's slot holds."""
    tf = models.transformer
    monkeypatch.setattr(tf, "DECODE_BLOCK", 8)
    params = tf.init(_BLOCKED, jax.random.key(1))
    T, mine = 20, 9
    step = jax.jit(lambda c, t, p: tf.decode_step_batch(_BLOCKED, params, c, t, p))
    tok = jnp.asarray(np.array([5, 11], np.int32))
    want_l, want_c = step(
        _junk_cache(tf, _BLOCKED, 2, T, 0), tok, jnp.asarray([mine, mine], jnp.int32)
    )
    junk = _junk_cache(tf, _BLOCKED, 2, T, 0)
    junk = jax.tree.map(lambda a: a.at[1].set(a[1] * 3 + 1), junk)
    got_l, got_c = step(junk, tok, jnp.asarray([mine, other], jnp.int32))
    assert np.array_equal(np.asarray(got_l)[0], np.asarray(want_l)[0])
    for name in got_c:
        for kv in ("k", "v"):
            assert np.array_equal(
                np.asarray(got_c[name][kv])[0], np.asarray(want_c[name][kv])[0]
            ), (name, kv)


@pytest.mark.parametrize(
    "T,pos",
    [
        (20, (0, 7, 8, 19)),   # block edges; the cache's end, T no multiple of 8
        (20, (19, 16, 15, 3)),  # the shifted last block's first and shared rows
        (16, (15, 0, 8, 7)),   # T a multiple of the block
        (5, (4, 0, 2, 1)),     # a cache shorter than a block
    ],
)
def test_a_decode_step_writes_one_cache_row_a_slot_and_no_other(monkeypatch, T, pos):
    """A step over a cache of junk changes ``cache[b, :, pos[b], :]`` and
    no other element (what ``prefill_chunk`` is held to for its rows)."""
    tf = models.transformer
    monkeypatch.setattr(tf, "DECODE_BLOCK", 8)
    params = tf.init(_BLOCKED, jax.random.key(1))
    junk = _junk_cache(tf, _BLOCKED, 4, T, T)
    tok = jnp.asarray(np.array([5, 11, 2, 90], np.int32))
    logits, got = jax.jit(
        lambda c, t, p: tf.decode_step_batch(_BLOCKED, params, c, t, p)
    )(junk, tok, jnp.asarray(pos, jnp.int32))
    assert np.all(np.isfinite(np.asarray(logits)))
    for name in got:
        for kv in ("k", "v"):
            g, j = np.asarray(got[name][kv]), np.asarray(junk[name][kv])
            keep = np.ones(g.shape, bool)
            for b, p in enumerate(pos):
                keep[b, :, p] = False
                assert not np.array_equal(g[b, :, p], j[b, :, p]), (name, kv, b)
            assert np.array_equal(g[keep], j[keep]), (name, kv)


@pytest.mark.parametrize("T", [20, 16])
def test_blocked_decode_matches_full_forward_at_every_depth(monkeypatch, T):
    """Rows three positions apart walk the whole cache, every block edge
    and position ``T - 1`` among them (``T`` a multiple of the block and
    not): each step's logits are the training forward's at that row's
    position."""
    tf = models.transformer
    monkeypatch.setattr(tf, "DECODE_BLOCK", 8)
    params = tf.init(_BLOCKED, jax.random.key(0))
    S = 3
    x = np.asarray(jax.random.randint(jax.random.key(1), (S, T), 0, 97))
    ref = np.asarray(tf.apply(_BLOCKED, params, jnp.asarray(x)))  # [S, T, V]
    cache = _junk_cache(tf, _BLOCKED, S, T, 1)
    step = jax.jit(lambda c, t, p: tf.decode_step_batch(_BLOCKED, params, c, t, p))
    rows_read = lambda *pos: tf.decode_rows_read(np.array(pos), np.ones(len(pos), bool), T)
    assert rows_read(7, 0) == 8 and rows_read(3, 8) == 16 and rows_read(T - 1) == T
    for s in range(T + 3 * (S - 1)):
        pos = np.clip(s - 3 * np.arange(S), 0, T - 1).astype(np.int32)
        logits, cache = step(cache, jnp.asarray(x[np.arange(S), pos]), jnp.asarray(pos))
        np.testing.assert_allclose(
            np.asarray(logits), ref[np.arange(S), pos], rtol=2e-4, atol=2e-4
        )


def test_blocked_decode_on_a_mesh_matches_one_device(monkeypatch, mesh_4x2):
    """Rows at their own depths on a data=4 x model=2 mesh (the cache's
    batch and heads sharded, its positions not: a block is a slice on
    every device, a row's write lands on the device that holds the row):
    logits and cache agree with the one-device step."""
    import optax

    tf = models.transformer
    monkeypatch.setattr(tf, "DECODE_BLOCK", 8)
    state, _ = train.create_sharded_state(
        lambda r: tf.init(_BLOCKED, r), optax.sgd(0.1), jax.random.key(0),
        mesh=mesh_4x2, rules=tf.SHARDING_RULES,
    )
    local = jax.device_get(state.params)
    T = 20
    junk = jax.device_get(_junk_cache(tf, _BLOCKED, 4, T, 3))
    tok = jnp.asarray(np.array([5, 11, 2, 90], np.int32))
    pos = jnp.asarray(np.array([0, 7, 8, 19], np.int32))
    want_l, want_c = jax.jit(
        lambda p, c: tf.decode_step_batch(_BLOCKED, p, c, tok, pos)
    )(local, junk)
    sharded = jax.tree.map(
        lambda a, like: jax.device_put(a, like.sharding),
        junk, tf.init_cache(_BLOCKED, 4, T, mesh=mesh_4x2),
    )
    got_l, got_c = jax.jit(
        lambda p, c: tf.decode_step_batch(_BLOCKED, p, c, tok, pos, mesh=mesh_4x2)
    )(state.params, sharded)
    np.testing.assert_allclose(np.asarray(got_l), np.asarray(want_l), atol=2e-4)
    for name in want_c:
        for kv in ("k", "v"):
            assert got_c[name][kv].sharding.is_equivalent_to(
                sharded[name][kv].sharding, 4
            )
            np.testing.assert_allclose(
                np.asarray(got_c[name][kv]), np.asarray(want_c[name][kv]), atol=1e-5
            )


@pytest.mark.parametrize(
    "C,T,n,slot,floor",
    [
        # One width, ``C``: the floor is over half of it.
        (8, 40, 21, 2, 128),   # several chunks, a ragged last one, a slot other than 0
        (8, 29, 28, 1, 128),   # max_len no multiple of C; the prompt ends at max_len - 1
        (8, 16, 8, 0, 128),    # exactly one full chunk
        (8, 32, 3, 3, 128),    # one chunk, mostly padding
        (16, 16, 15, 2, 128),  # the chunk as long as the cache
        (8, 12, 11, 0, 128),   # every chunk but the first shifted back inside its window
        # Widths ``floor`` .. ``C``: the last chunk as wide as what is left needs.
        (16, 40, 21, 2, 4),    # 16, then 5 in a chunk of 8
        (16, 40, 20, 1, 4),    # 16, then 4 in a chunk of 4: none padded
        (16, 40, 3, 3, 4),     # 3 in a chunk of 4
        (8, 29, 28, 1, 2),     # 8, 8, 8, then 4 up to the cache's last row but one
        (8, 12, 10, 0, 2),     # 8, then a chunk of 2
        (8, 10, 9, 0, 4),      # 8, then 1 in a chunk of 4 shifted back inside the cache
    ],
)
def test_prefill_chunks_match_token_by_token_decode(engine_chunks, C, T, n, slot, floor):
    """``n`` prompt tokens prefilled ``C`` at a time - the last chunk at the
    narrowest of the engine's widths that holds it - leave the slot's cache
    rows ``[0, n)`` as ``n`` calls of ``decode_step_batch`` leave them and
    the following decode steps' logits equal (summation order apart),
    and no other row of the cache is touched at all."""
    cfg = models.transformer.Config(
        vocab_size=97, dim=32, n_layers=2, n_heads=4, max_seq_len=64,
        compute_dtype="float32",
    )
    tf = models.transformer
    params = tf.init(cfg, jax.random.key(1))
    S = 4
    rng = np.random.default_rng(C * 1000 + T)
    toks = rng.integers(0, cfg.vocab_size, size=T).astype(np.int32)
    junk = _junk_cache(tf, cfg, S, T, C * 1000 + T + 1)
    step = jax.jit(lambda c, t, p: tf.decode_step_batch(cfg, params, c, t, p))
    chunk = jax.jit(
        lambda c, t, o, nv: tf.prefill_chunk(cfg, params, c, t, slot, o, nv)
    )
    row = lambda v: jnp.zeros((S,), jnp.int32).at[slot].set(v)
    ref = junk
    for p in range(n):
        _logits, ref = step(ref, row(toks[p]), row(p))
    got = junk
    for o, nv, width in engine_chunks(n, C, floor):
        buf = np.zeros((width,), np.int32)
        buf[:nv] = toks[o:o + nv]
        got = chunk(got, buf, o, nv)
    for name in got:
        for kv in ("k", "v"):
            g, r, j = (np.asarray(c[name][kv]) for c in (got, ref, junk))
            np.testing.assert_allclose(
                g[slot, :, :n], r[slot, :, :n], rtol=1e-5, atol=1e-5
            )
            keep = np.ones(g.shape, bool)
            keep[slot, :, :n] = False
            assert np.array_equal(g[keep], j[keep]), (name, kv)
    for p in range(n, min(n + 3, T)):
        lr, ref = step(ref, row(toks[p]), row(p))
        lg, got = step(got, row(toks[p]), row(p))
        np.testing.assert_allclose(
            np.asarray(lg)[slot], np.asarray(lr)[slot], rtol=1e-4, atol=1e-5
        )


@pytest.mark.parametrize("program", ["decode_step_batch", "prefill_chunk"])
def test_the_step_and_the_chunk_never_materialise_a_cast_embedding_table(program):
    """Structural guard: with float32 parameters and bfloat16 compute, the
    compiled step and chunk gather their ids' rows out of the float32 table
    and cast those.  XLA does not move a convert through a gather, so a
    lookup that casts first converts the whole ``[vocab, dim]`` table on
    every launch (PR 43: 0.92 ms of a 9.2 ms launch at 50,304 x 2,048); the
    optimised HLO then holds an instruction whose result is ``[vocab, dim]``
    and which is no parameter."""
    import re

    tf = models.transformer
    cfg = tf.Config(
        vocab_size=97, dim=32, n_layers=2, n_heads=4, max_seq_len=48,
        compute_dtype="bfloat16",
    )
    params = tf.init(cfg, jax.random.key(1))
    assert params["emb"]["table"].dtype == jnp.float32
    S, T, C = 8, 40, 16
    cache = tf.init_cache(cfg, S, T)
    if program == "decode_step_batch":
        fn = lambda p, c, t, pos: tf.decode_step_batch(cfg, p, c, t, pos)
        args = (params, cache, jnp.zeros((S,), jnp.int32), jnp.zeros((S,), jnp.int32))
    else:
        fn = lambda p, c, t, o, nv: tf.prefill_chunk(cfg, p, c, t, 1, o, nv)
        args = (params, cache, jnp.zeros((C,), jnp.int32), jnp.int32(0), jnp.int32(C))
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    table = rf"\[{cfg.vocab_size},{cfg.dim}\]"
    assert re.search(rf"f32{table}\S* parameter\(", hlo), "the table is an argument"
    made = [
        line.strip()
        for line in hlo.splitlines()
        if re.search(rf"= \w+{table}", line) and " parameter(" not in line
    ]
    assert not made, made


def test_serve_decode_fns_gives_prefill_for_dense_blocks_only():
    """The engine adapts to what it is handed: a dense model hands it the
    chunk function, an MoE model (capacity is per call) does not."""
    tf = models.transformer
    fns = tf.serve_decode_fns(CFG)
    assert [f.__name__ for f in fns[:3]] == ["init_cache_fn", "step_fn", "prefill_fn"]
    moe = tf.Config(
        vocab_size=128, dim=32, n_layers=1, n_heads=4, max_seq_len=64,
        moe_experts=4,
    )
    fns = tf.serve_decode_fns(moe)
    assert [f.__name__ for f in fns[:2]] == ["init_cache_fn", "step_fn"]
    assert fns.prefill is None and not fns.wants_live
    with pytest.raises(NotImplementedError):
        tf.prefill_chunk(moe, None, None, np.zeros(4, np.int32), 0, 0, 4)


def test_transformer_served_decode_byte_identical_to_reference(tmp_path):
    """transformer_lm as a SERVED workload (r19 acceptance): stepped
    KV-cache decode through the sequence-slot batcher returns tokens
    byte-identical to the unbatched reference decode (generate()), solo
    AND coalesced with concurrent sessions."""
    import threading

    import numpy as np

    from distributed_tensorflow_examples_tpu import serve
    from distributed_tensorflow_examples_tpu.parallel import ps_shard
    from distributed_tensorflow_examples_tpu.serve.registry import (
        ModelRegistry,
    )

    cfg = models.transformer.Config(
        vocab_size=211, dim=32, n_layers=2, n_heads=4, max_seq_len=48,
        compute_dtype="bfloat16",
    )
    params = models.transformer.init(cfg, jax.random.key(3))
    total, unflatten = ps_shard.flat_param_spec(params)
    flat = np.concatenate(
        [np.asarray(l, np.float32).reshape(-1) for l in jax.tree.leaves(params)]
    )
    reg = ModelRegistry(str(tmp_path))
    v = reg.publish("transformer_lm", flat, step=11)
    srv = serve.ModelReplicaServer(
        lambda r: models.transformer.init(cfg, r),
        lambda p, b: models.transformer.apply(cfg, p, b["x"]),
        [], registry_dir=str(tmp_path), model_name="transformer_lm",
        model_version=v, decode_fns=models.transformer.serve_decode_fns(cfg),
        decode_slots=4, decode_max_len=48, role="tsrv0",
    )
    try:
        c = serve.ServeClient("127.0.0.1", srv.port, role="ts_sv")
        prompt = np.array([3, 17, 155, 42], np.int32)
        served = c.generate(prompt, 10)
        # The unbatched reference: the model's own greedy KV-cache decode
        # over the SAME registry snapshot.
        ref_params = unflatten(flat)
        ref = np.asarray(
            models.transformer.generate(
                cfg, ref_params, prompt[None], max_new_tokens=10
            )
        )[0, len(prompt):]
        assert np.array_equal(served, ref.astype(np.int32)), (
            served.tolist(), ref.tolist(),
        )
        # Coalesced with concurrent variable-length sessions: still
        # byte-identical (row independence + per-row masks).
        prompts = [prompt, np.array([9], np.int32),
                   np.array([100, 200, 7], np.int32)]
        outs: list = [None] * 3

        def body(i):
            ci = serve.ServeClient("127.0.0.1", srv.port, role=f"tg{i}_sv")
            outs[i] = ci.generate(prompts[i], 10)
            ci.close()

        ts = [threading.Thread(target=body, args=(i,)) for i in range(3)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
        assert np.array_equal(outs[0], served)
        st = c.stats()
        assert st["model_version"] == v and st["decode_sessions"] >= 4
        c.close()
    finally:
        srv.stop()


@pytest.mark.parametrize("floor,width", [(128, 9 * 8), (2, 28 + 12 + 20)])
def test_transformer_served_with_chunked_prefill_matches_generate(
    tmp_path, monkeypatch, floor, width,
):
    """Prompts longer than a chunk, seated together on a live replica: the
    engine's chunks (several a prompt, one an iteration, while the other
    rows decode; all 8 wide, or a prompt's last one 2 or 4 wide) leave
    every session the tokens ``generate`` gives it — and ``generate`` those
    of the token-by-token feed."""
    import threading

    from distributed_tensorflow_examples_tpu import serve
    from distributed_tensorflow_examples_tpu.serve import model_server
    from distributed_tensorflow_examples_tpu.serve.registry import (
        ModelRegistry,
    )
    from distributed_tensorflow_examples_tpu.train.checkpoint import (
        flat_params_of,
    )

    monkeypatch.setattr(model_server, "PREFILL_CHUNK", 8)
    monkeypatch.setattr(model_server, "PREFILL_FLOOR", floor)
    cfg = models.transformer.Config(
        vocab_size=211, dim=32, n_layers=2, n_heads=4, max_seq_len=48,
        compute_dtype="float32",
    )
    tf = models.transformer
    params = tf.init(cfg, jax.random.key(5))
    v = ModelRegistry(str(tmp_path)).publish(
        "transformer_lm", flat_params_of(params), step=3
    )
    srv = serve.ModelReplicaServer(
        lambda r: tf.init(cfg, r), lambda p, b: tf.apply(cfg, p, b["x"]),
        [], registry_dir=str(tmp_path), model_name="transformer_lm",
        model_version=v, decode_fns=tf.serve_decode_fns(cfg),
        decode_slots=3, decode_max_len=41, role="tsrv1",
    )
    rng = np.random.default_rng(11)
    # 29 + 12 = 41: the longest session ends on the cache's last row, and
    # its last chunk would overrun it.
    prompts = [
        rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
        for n in (29, 1, 12, 20)
    ]
    outs: list = [None] * len(prompts)

    def body(i):
        c = serve.ServeClient("127.0.0.1", srv.port, role=f"tc{i}_sv")
        outs[i] = c.generate(prompts[i], 12)
        c.close()

    try:
        ts = [threading.Thread(target=body, args=(i,)) for i in range(len(prompts))]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=120)
        st = srv.stats()
    finally:
        srv.stop()
    assert st["decode_prefill_tokens"] == sum(len(p) - 1 for p in prompts)
    assert st["decode_prefill_chunks"] == 4 + 0 + 2 + 3
    # 28 = 8 + 8 + 8 + 4, 11 = 8 + 3 in 4, 19 = 8 + 8 + 3 in 4.
    assert st["decode_prefill_width"] == width
    step = jax.jit(lambda c, t, p: tf.decode_step(cfg, params, c, t, p))
    for p, o in zip(prompts, outs):
        ref = np.asarray(tf.generate(cfg, params, p[None], max_new_tokens=12))
        assert np.array_equal(o, ref[0, len(p):]), len(p)
        # The feed that was: every position through the decode step.
        cache, fed = tf.init_cache(cfg, 1, len(p) + 12), list(p)
        for pos in range(len(p) + 11):
            logits, cache = step(cache, jnp.asarray(fed[pos:pos + 1]), pos)
            if pos + 1 >= len(p):
                fed.append(int(jnp.argmax(logits[0])))
        assert fed[len(p):] == o.tolist(), len(p)
