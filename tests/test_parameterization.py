"""Parameterization tests: hashing, QR, baseline correction, feature towers,
EM baselines, sparse-row optimizer, recovery properties."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (Compression, DeepCrossParameterConfig,
                        EmbeddingParameter, EmbeddingParameterConfig,
                        LinearParameterConfig, MLPParameterConfig,
                        PositionBasedModel, build_parameter, em)
from repro.core.parameterization import hash_ids, relayout_scalar_tables
from repro.optim.sparse import (init_sparse_table_state, sparse_adamw_update,
                                sparse_row_grads)


def test_hash_ids_deterministic_and_in_range():
    ids = jnp.arange(1000)
    h1, h2 = hash_ids(ids, 128), hash_ids(ids, 128)
    np.testing.assert_array_equal(np.asarray(h1), np.asarray(h2))
    assert int(h1.min()) >= 0 and int(h1.max()) < 128


def test_hash_distribution_roughly_uniform():
    h = np.asarray(hash_ids(jnp.arange(100_000), 64))
    counts = np.bincount(h, minlength=64)
    assert counts.min() > 100_000 / 64 * 0.8
    assert counts.max() < 100_000 / 64 * 1.2


def test_hash_compression_reduces_rows():
    cfg = EmbeddingParameterConfig(parameters=1_000_000,
                                   compression=Compression.HASH,
                                   compression_ratio=100.0)
    mod = EmbeddingParameter(cfg)
    params = mod.init(jax.random.PRNGKey(0))
    assert params["table"].shape[0] <= 1_000_000 / 50  # rounded to 512
    batch = {"query_doc_ids": jnp.asarray([[0, 999_999]])}
    out = mod(params, batch)
    assert out.shape == (1, 2)


def test_qr_distinct_ids_mostly_distinct_embeddings():
    cfg = EmbeddingParameterConfig(parameters=100_000,
                                   compression=Compression.QR,
                                   compression_ratio=10.0, features=4)
    mod = EmbeddingParameter(cfg)
    params = mod.init(jax.random.PRNGKey(0))
    params = jax.tree_util.tree_map(
        lambda x: jax.random.normal(jax.random.PRNGKey(1), x.shape), params)
    ids = jnp.arange(2000)[None]
    out = np.asarray(mod(params, {"query_doc_ids": ids}))[0]
    uniq = len(np.unique(out.round(5), axis=0))
    assert uniq > 1900  # QR: collisions ~ |ids|/(q*r), essentially none here


def test_baseline_correction_gradient_flows_to_baseline():
    cfg = EmbeddingParameterConfig(parameters=100, baseline_correction=True,
                                   init_logit=-1.5)
    mod = EmbeddingParameter(cfg)
    params = mod.init(jax.random.PRNGKey(0))
    np.testing.assert_array_equal(np.asarray(params["table"]), 0.0)
    np.testing.assert_allclose(np.asarray(params["baseline"]), -1.5)
    batch = {"query_doc_ids": jnp.asarray([[1, 2, 3]])}

    def loss(p):
        return jnp.sum(mod(p, batch) ** 2)

    g = jax.grad(loss)(params)
    assert float(jnp.abs(g["baseline"]).sum()) > 0


@pytest.mark.parametrize("config", [
    LinearParameterConfig(features=8),
    MLPParameterConfig(features=8, hidden=(16,)),
    DeepCrossParameterConfig(features=8, cross_layers=2, deep_layers=1),
])
def test_feature_towers_shape(config):
    mod = build_parameter(config)
    params = mod.init(jax.random.PRNGKey(0))
    batch = {"query_doc_features": jnp.ones((3, 5, 8))}
    out = mod(params, batch)
    assert out.shape == (3, 5)


# ---------------------------------------------------------------------------
# Scalar tables: (rows, 1) in the model's shapes, (rows,) while training
# ---------------------------------------------------------------------------

TABLE_CONFIGS = [
    dict(parameters=64, compression=Compression.NONE),
    dict(parameters=5000, compression=Compression.HASH, compression_ratio=10.0),
    dict(parameters=5000, compression=Compression.QR, compression_ratio=10.0),
]
TABLE_IDS = ["none", "hash", "qr"]
ROW_LEAVES = ("table", "quotient", "remainder")


def _table_config(spec, baseline, features=1):
    return EmbeddingParameterConfig(**spec, baseline_correction=baseline,
                                    features=features, init_logit=-1.25)


def _ids_batch(n_ids, b=6, k=5, seed=0):
    r = np.random.default_rng(seed)
    return {"positions": np.tile(np.arange(1, k + 1, dtype=np.int32), (b, 1)),
            "query_doc_ids": r.integers(0, n_ids, size=(b, k)).astype(np.int32),
            "clicks": (r.random((b, k)) < 0.4).astype(np.float32),
            "mask": r.random((b, k)) < 0.9}


def _dbn(cfg):
    from repro.core import DynamicBayesianNetwork

    return DynamicBayesianNetwork(positions=5, attraction=cfg,
                                  satisfaction=cfg)


def _shapes(tree):
    return jax.tree_util.tree_map(np.shape, tree)


@pytest.mark.parametrize("baseline", [False, True], ids=["plain", "baseline"])
@pytest.mark.parametrize("spec", TABLE_CONFIGS, ids=TABLE_IDS)
def test_scalar_table_is_stored_as_1d_rows(spec, baseline):
    """The model gives (rows, 1); the training form is (rows,), for the
    tables and their AdamW moments alike, and goes back exactly."""
    from repro import optim

    model = _dbn(_table_config(spec, baseline))
    params = model.init(jax.random.PRNGKey(0))
    state = (params, optim.adamw(0.1).init(params))
    flat = relayout_scalar_tables(model, state, flat=True)
    for path, leaf in jax.tree_util.tree_flatten_with_path(flat)[0]:
        names = [getattr(k, "key", getattr(k, "name", None)) for k in path]
        if names[-1] in ROW_LEAVES:
            assert leaf.ndim == 1, (names, leaf.shape)
        elif names[-1] == "baseline":
            assert leaf.shape == (1,)
    # idempotent both ways, and the way back is the model's own shapes
    assert _shapes(relayout_scalar_tables(model, flat, flat=True)) \
        == _shapes(flat)
    back = relayout_scalar_tables(model, flat, flat=False)
    assert _shapes(back) == _shapes(state)
    assert _shapes(relayout_scalar_tables(model, back, flat=False)) \
        == _shapes(state)
    part = model.parts["attraction"]
    batch = _ids_batch(spec["parameters"])
    out = part(flat[0]["attraction"], batch)
    assert out.shape == (6, 5)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(part(params["attraction"], batch)))


@pytest.mark.parametrize("baseline", [False, True], ids=["plain", "baseline"])
@pytest.mark.parametrize("spec", TABLE_CONFIGS, ids=TABLE_IDS)
def test_wide_table_keeps_its_features_axis(spec, baseline):
    mod = EmbeddingParameter(_table_config(spec, baseline, features=3))
    params = mod.init(jax.random.PRNGKey(0))
    names = set(ROW_LEAVES) & set(params)
    assert names
    for name in names:
        assert params[name].ndim == 2 and params[name].shape[1] == 3
    assert mod.scalar_tables() == {}
    out = mod(params, _ids_batch(spec["parameters"]))
    assert out.shape == (6, 5, 3)


@pytest.mark.parametrize("baseline", [False, True], ids=["plain", "baseline"])
@pytest.mark.parametrize("spec", TABLE_CONFIGS, ids=TABLE_IDS)
def test_1d_rows_match_the_column_table_bit_for_bit(spec, baseline):
    """Logits, loss, gradients and three AdamW steps of a DBN over flat
    tables equal those over the model's (rows, 1) tables, reshaped."""
    from repro import optim

    model = _dbn(_table_config(spec, baseline))
    p2 = model.init(jax.random.PRNGKey(3))
    # rows away from their start, so the gather reads distinct values
    p2 = {name: {k: (v + jax.random.normal(jax.random.PRNGKey(i), v.shape)
                     if k in ROW_LEAVES else v) for k, v in leaf.items()}
          for i, (name, leaf) in enumerate(p2.items())}
    p1 = relayout_scalar_tables(model, p2, flat=True)
    assert p1["attraction"]["table" if "table" in p1["attraction"]
                            else "quotient"].ndim == 1
    batch = {k: jnp.asarray(v) for k, v in _ids_batch(spec["parameters"]).items()}

    logits = jax.jit(lambda p: model.parts["attraction"](p["attraction"],
                                                         batch))
    np.testing.assert_array_equal(np.asarray(logits(p1)),
                                  np.asarray(logits(p2)))

    tx = optim.adamw(0.05, weight_decay=1e-3)

    @jax.jit
    def run(p, o):
        loss, g = jax.value_and_grad(model.compute_loss)(p, batch)
        u, o = tx.update(g, o, p)
        return loss, g, optim.apply_updates(p, u), o

    o1, o2 = tx.init(p1), tx.init(p2)
    for _ in range(3):
        l1, g1, p1, o1 = run(p1, o1)
        l2, g2, p2, o2 = run(p2, o2)
        assert float(l1) == float(l2)
        for got, want in ((g1, g2), (p1, p2), (o1, o2)):
            got = jax.tree_util.tree_map(lambda a, b: a.reshape(b.shape),
                                         got, want)
            for a, b in zip(jax.tree_util.tree_leaves(got),
                            jax.tree_util.tree_leaves(want)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("saved_flat", [False, True],
                         ids=["column-into-flat", "flat-into-column"])
def test_column_checkpoint_restores_into_1d_model(tmp_path, saved_flat):
    """A checkpoint holding (rows, 1) tables restores into the flat
    training state, and one written from that state into the model's
    (rows, 1) template, parameters and AdamW moments alike."""
    from repro import optim
    from repro.train.checkpoints import CheckpointManager

    model = _dbn(_table_config(TABLE_CONFIGS[1], True))
    tx = optim.adamw(0.05)
    params = model.init(jax.random.PRNGKey(0))
    params = jax.tree_util.tree_map(
        lambda v: v + jax.random.normal(jax.random.PRNGKey(1), v.shape), params)
    _, opt_state = tx.update(params, tx.init(params), params)
    column = {"params": params, "opt_state": opt_state}
    flat = relayout_scalar_tables(model, column, flat=True)
    assert flat["params"]["attraction"]["table"].ndim == 1
    saved, want = (flat, column) if saved_flat else (column, flat)

    ckpt = CheckpointManager(str(tmp_path), log_fn=lambda *_: None)
    ckpt.save(7, saved)
    like = jax.tree_util.tree_map(jnp.zeros_like, want)
    tree, _, step = ckpt.restore(like=like)
    assert step == 7
    for a, b in zip(jax.tree_util.tree_leaves(tree),
                    jax.tree_util.tree_leaves(want)):
        assert a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("saved,template", [((8, 2), (8,)), ((3, 5), (15,))],
                         ids=["other-size", "same-size"])
def test_checkpoint_restore_refuses_a_leaf_of_another_shape(tmp_path, saved,
                                                            template):
    """Only axes of length 1 may differ: anything else would reorder or
    drop values."""
    from repro.train.checkpoints import CheckpointManager

    ckpt = CheckpointManager(str(tmp_path), log_fn=lambda *_: None)
    ckpt.save(1, {"table": np.zeros(saved, np.float32)})
    with pytest.raises(ValueError, match="'table'"):
        ckpt.restore(like={"table": np.zeros(template, np.float32)})


@pytest.mark.parametrize("sparse", [False, True], ids=["dense", "sparse"])
def test_trainer_trains_flat_tables_and_hands_back_the_models_shapes(
        tmp_path, sparse):
    """Trainer checkpoints hold flat tables and moments; its final state is
    in the model's shapes; and a second call handed that state continues
    exactly where one longer call goes."""
    from repro import optim
    from repro.data import ClickLogLoader, SyntheticConfig, generate_click_log
    from repro.train import Trainer
    from repro.train.checkpoints import CheckpointManager

    cfg = _table_config(TABLE_CONFIGS[1], True)
    data, _ = generate_click_log(SyntheticConfig(
        n_sessions=768, n_queries=50, positions=5, behavior="dbn", seed=2))

    def trainer(epochs, ckpt=None):
        kw = (dict(sparse_tables=True,
                   sparse_table_kwargs={"lr": 0.05, "weight_decay": 1e-4})
              if sparse else {})
        return Trainer(optim.adamw(0.05), epochs=epochs, patience=100,
                       log_fn=lambda *_: None, chunk_batches=2,
                       checkpoint_dir=ckpt, **kw)

    def loader():
        return ClickLogLoader(data, batch_size=128, seed=5)

    model = _dbn(cfg)
    whole = trainer(2, str(tmp_path / "ckpt"))
    whole.train(model, loader())
    init = model.init(jax.random.PRNGKey(0))
    assert _shapes(whole.final_state.params) == _shapes(init)

    # what the trainer wrote, as written (restore would reshape it)
    ckpt = CheckpointManager(str(tmp_path / "ckpt"))
    _, saved = ckpt._load_validated(ckpt.latest_step())
    tables = {k: v.shape for k, v in saved.items() if "table" in k and v.ndim}
    rows = init["attraction"]["table"].shape[0]
    assert any("params" in k for k in tables) and len(tables) >= 6, tables
    assert all(shape == (rows,) for shape in tables.values()), tables

    split, once = trainer(1), loader()
    split.train(model, once)
    split.epochs += 1
    split.train(model, once, state=split.final_state)
    ends = [(t.final_state.params, t.final_state.opt_state)
            for t in (whole, split)]
    assert _shapes(ends[0]) == _shapes(ends[1])
    for a, b in zip(*map(jax.tree_util.tree_leaves, ends)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_registry_serves_from_flat_tables():
    """``ModelRegistry`` holds scalar tables flat and answers as the model
    does on its own (rows, 1) tables."""
    from repro.serve import ModelRegistry

    model = _dbn(_table_config(TABLE_CONFIGS[1], True))
    params = model.init(jax.random.PRNGKey(0))
    params = jax.tree_util.tree_map(
        lambda v: v + jax.random.normal(jax.random.PRNGKey(1), v.shape), params)
    entry = ModelRegistry(buckets=(8,)).add("dbn", model, params, n_pairs=5000)
    assert entry.params["attraction"]["table"].ndim == 1
    assert params["attraction"]["table"].ndim == 2
    batch = {k: v for k, v in _ids_batch(5000, b=8).items()
             if k in ("positions", "query_doc_ids", "mask")}
    np.testing.assert_array_equal(
        entry.run("primary", batch),
        np.asarray(jax.jit(model.predict_clicks)(params, batch)))


# ---------------------------------------------------------------------------
# EM correctness properties
# ---------------------------------------------------------------------------

def _pbm_loglik(theta, gamma, pos, docs, clicks, mask):
    p = np.clip(theta[pos] * gamma[docs], 1e-9, 1 - 1e-9)
    ll = clicks * np.log(p) + (1 - clicks) * np.log(1 - p)
    return float((ll * mask).sum())


def test_pbm_em_monotonically_improves_loglik(small_log):
    cfg, data, meta = small_log
    batch = {k: jnp.asarray(v) for k, v in data.items()
             if k in ("positions", "query_doc_ids", "clicks", "mask")}
    pos = np.asarray(batch["positions"]).reshape(-1) - 1
    docs = np.asarray(batch["query_doc_ids"]).reshape(-1)
    clicks = np.asarray(batch["clicks"]).reshape(-1)
    mask = np.asarray(batch["mask"]).reshape(-1)
    lls = []
    for iters in (1, 3, 10, 30):
        theta, gamma = em.fit_pbm_em(batch, cfg.positions,
                                     cfg.n_query_doc_pairs, n_iters=iters)
        lls.append(_pbm_loglik(np.asarray(theta), np.asarray(gamma),
                               pos, docs, clicks, mask))
    assert all(b >= a - 1e-6 for a, b in zip(lls, lls[1:])), lls


def test_mle_counting_matches_numpy(small_log):
    cfg, data, meta = small_log
    batch = {k: jnp.asarray(v) for k, v in data.items()
             if k in ("positions", "query_doc_ids", "clicks", "mask")}
    np.testing.assert_allclose(float(em.fit_gctr(batch)),
                               data["clicks"].mean(), rtol=1e-6)
    rctr = np.asarray(em.fit_rctr(batch, cfg.positions))
    np.testing.assert_allclose(rctr, data["clicks"].mean(axis=0), rtol=1e-5)


# ---------------------------------------------------------------------------
# Sparse-row optimizer == dense AdamW on touched rows
# ---------------------------------------------------------------------------

def test_sparse_adamw_matches_dense_on_touched_rows():
    from repro import optim

    R, D = 64, 4
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.normal(size=(R, D)).astype(np.float32))
    ids = jnp.asarray([[1, 5, 5], [9, 1, 2]], jnp.int32)
    row_grads = jnp.asarray(rng.normal(size=(2, 3, D)).astype(np.float32))

    # dense reference: scatter-add grads then dense adamw
    dense_g = np.zeros((R, D), np.float32)
    for b in range(2):
        for k in range(3):
            dense_g[int(ids[b, k])] += np.asarray(row_grads[b, k])
    tx = optim.adamw(0.01, weight_decay=0.0)
    state = tx.init(table)
    updates, _ = tx.update(jnp.asarray(dense_g), state, table)
    dense_next = optim.apply_updates(table, updates)

    sstate = init_sparse_table_state(table)
    uids, ugrads = sparse_row_grads(row_grads, ids, R)
    sparse_next, _ = sparse_adamw_update(table, sstate, uids, ugrads, lr=0.01)

    touched = sorted({int(i) for i in np.asarray(ids).reshape(-1)})
    np.testing.assert_allclose(np.asarray(sparse_next)[touched],
                               np.asarray(dense_next)[touched], rtol=1e-5)
    untouched = [r for r in range(R) if r not in touched]
    np.testing.assert_array_equal(np.asarray(sparse_next)[untouched],
                                  np.asarray(table)[untouched])


def test_sparse_adamw_on_a_1d_table_matches_dense_on_touched_rows():
    """The lazy path keeps a scalar table's 1-D rows: per-lookup grads of
    the ids' shape dedupe to (U,), not (U, 1)."""
    from repro import optim

    R = 64
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.normal(size=(R,)).astype(np.float32))
    ids = jnp.asarray([[1, 5, 5], [9, 1, 2]], jnp.int32)
    row_grads = jnp.asarray(rng.normal(size=(2, 3)).astype(np.float32))
    dense_g = jnp.zeros((R,), jnp.float32).at[ids].add(row_grads)
    tx = optim.adamw(0.01, weight_decay=0.0)
    updates, _ = tx.update(dense_g, tx.init(table), table)
    dense_next = optim.apply_updates(table, updates)

    uids, ugrads = sparse_row_grads(row_grads, ids, R)
    assert ugrads.shape == uids.shape
    sparse_next, st = sparse_adamw_update(
        table, init_sparse_table_state(table), uids, ugrads, lr=0.01)
    assert sparse_next.shape == st.mu.shape == (R,)
    touched = sorted({int(i) for i in np.asarray(ids).reshape(-1)})
    np.testing.assert_allclose(np.asarray(sparse_next)[touched],
                               np.asarray(dense_next)[touched], rtol=1e-5)


# ---------------------------------------------------------------------------
# End-to-end recovery: training on a model's own samples recovers the fit
# ---------------------------------------------------------------------------

def test_pbm_gradient_training_matches_em_fit(small_log):
    from benchmarks.common import evaluate_clicks, train_gradient

    cfg, data, meta = small_log
    full = {k: jnp.asarray(v) for k, v in data.items()
            if k in ("positions", "query_doc_ids", "clicks", "mask")}
    theta, gamma = em.fit_pbm_em(full, cfg.positions, cfg.n_query_doc_pairs,
                                 n_iters=40, init=1 / 9)
    pbm = PositionBasedModel(query_doc_pairs=cfg.n_query_doc_pairs,
                             positions=cfg.positions)
    em_m = evaluate_clicks(pbm, em.pbm_params_from_em(theta, gamma), data,
                           positions=cfg.positions, batch_size=256)
    model = PositionBasedModel(query_doc_pairs=cfg.n_query_doc_pairs,
                               positions=cfg.positions, init_prob=1 / 9)
    params, _ = train_gradient(model, data, None, epochs=20, batch_size=128,
                               lr=0.05)
    grad_m = evaluate_clicks(model, params, data, positions=cfg.positions,
                             batch_size=256)
    assert abs(grad_m["ppl"] - em_m["ppl"]) < 0.02  # the paper's Fig-1 claim


def test_sdbn_mle_counting(small_log):
    """SDBN MLE on SDBN-like data: gamma estimates correlate with truth."""
    import jax.numpy as jnp

    from repro.data import SyntheticConfig, generate_click_log

    cfg = SyntheticConfig(n_sessions=20_000, n_queries=20, docs_per_query=10,
                          positions=8, behavior="dbn", continuation=1.0,
                          seed=13)  # lambda=1 == SDBN behavior
    data, meta = generate_click_log(cfg)
    batch = {k: jnp.asarray(v) for k, v in data.items()
             if k in ("positions", "query_doc_ids", "clicks", "mask")}
    gamma, sigma = em.fit_sdbn_mle(batch, cfg.n_query_doc_pairs)
    g, t = np.asarray(gamma), meta["gamma"]
    seen = g > 0
    assert seen.sum() > 50
    corr = np.corrcoef(g[seen], t[seen])[0, 1]
    assert corr > 0.8, corr
