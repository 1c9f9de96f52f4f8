"""Training-engine tests: scan-jitted chunk steps vs the per-batch loop
(bit-exact), chunked prefetch, checkpoint/resume at chunk granularity,
sparse-table lazy AdamW, data-parallel execution, and eval-step caching."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim
from repro.core import PositionBasedModel
from repro.data import (ClickLogLoader, DevicePrefetcher, SyntheticConfig,
                        generate_click_log, split_sessions)
from repro.train import Trainer, TrainEngine


@pytest.fixture(scope="module")
def pbm_log():
    cfg = SyntheticConfig(n_sessions=2200, n_queries=25, docs_per_query=12,
                          positions=6, behavior="pbm", seed=13)
    data, _ = generate_click_log(cfg)
    train, val, _ = split_sessions(data, (0.8, 0.1, 0.1), seed=0)
    return cfg, train, val


def _model(cfg):
    return PositionBasedModel(query_doc_pairs=cfg.n_query_doc_pairs,
                              positions=cfg.positions, init_prob=0.2)


def _copy(tree):
    return jax.tree_util.tree_map(lambda x: jnp.array(np.asarray(x)), tree)


def _assert_trees_equal(a, b, msg=""):
    la = jax.tree_util.tree_leaves_with_path(a)
    lb = jax.tree_util.tree_leaves_with_path(b)
    assert len(la) == len(lb)
    for (ka, va), (_, vb) in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(va), np.asarray(vb),
                                      err_msg=f"{msg}{ka}")


def _loop_reference(cfg, data, epochs, batch_size=256, lr=0.05):
    """The historical trainer loop: one jit dispatch + one blocking
    ``float(loss)`` per batch. The engine must reproduce it bit-for-bit."""
    model = _model(cfg)
    tx = optim.adamw(lr)
    params = model.init(jax.random.PRNGKey(0))
    opt_state = tx.init(params)

    @jax.jit
    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(model.compute_loss)(params, batch)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optim.apply_updates(params, updates), opt_state, loss

    loader = ClickLogLoader(data, batch_size=batch_size, seed=5)
    losses = []
    for _ in range(epochs):
        for batch in iter(loader):
            batch = {k: jax.device_put(v) for k, v in batch.items()}
            params, opt_state, loss = step(params, opt_state, batch)
            losses.append(float(loss))
    return params, opt_state, losses


def _engine_run(cfg, data, epochs, chunk, batch_size=256, lr=0.05):
    model = _model(cfg)
    engine = TrainEngine(model, optim.adamw(lr), chunk_batches=chunk)
    params = model.init(jax.random.PRNGKey(0))
    opt_state = engine.init_opt_state(params)
    loader = ClickLogLoader(data, batch_size=batch_size, seed=5)
    losses = []
    for _ in range(epochs):
        for chunk_arr, _, n in DevicePrefetcher(loader, chunk_batches=chunk):
            params, opt_state, step_losses = engine.step(params, opt_state,
                                                         chunk_arr)
            assert step_losses.shape == (n,)
            losses.extend(float(x) for x in np.asarray(step_losses))
    return params, opt_state, losses


@pytest.mark.parametrize("chunk", [1, 4, 5])
def test_engine_bitexact_vs_per_batch_loop(pbm_log, chunk):
    """Params, opt_state, and the full per-step loss history must be
    identical for chunk 1, a dividing chunk, and a non-dividing chunk
    (6 batches/epoch at B=256: chunk 5 leaves a partial trailing chunk)."""
    cfg, train, _ = pbm_log
    p_ref, o_ref, l_ref = _loop_reference(cfg, train, epochs=2)
    p, o, losses = _engine_run(cfg, train, epochs=2, chunk=chunk)
    assert losses == l_ref
    _assert_trees_equal(p_ref, p, msg=f"chunk={chunk} params ")
    _assert_trees_equal(o_ref, o, msg=f"chunk={chunk} opt_state ")


def test_trainer_chunked_matches_loop_history(pbm_log):
    cfg, train, _ = pbm_log

    def run(chunk):
        model = _model(cfg)
        trainer = Trainer(optim.adamw(0.05), epochs=3, patience=100,
                          log_fn=lambda *_: None, chunk_batches=chunk)
        loader = ClickLogLoader(train, batch_size=256, seed=5)
        history = trainer.train(model, loader, None)
        return history, trainer._final_state

    h1, s1 = run(1)
    h4, s4 = run(4)
    assert [r["train_loss"] for r in h1] == [r["train_loss"] for r in h4]
    assert s1.global_step == s4.global_step
    _assert_trees_equal(s1.params, s4.params)


def test_chunked_prefetcher_stacks_and_flushes_partial_shapes():
    n, k = 103, 4  # batch 10, drop_last=False: 10 full batches + one of 3
    data = {"positions": np.tile(np.arange(1, k + 1, dtype=np.int32), (n, 1)),
            "query_doc_ids": np.arange(n * k, dtype=np.int64).reshape(n, k),
            "clicks": np.zeros((n, k), np.float32),
            "mask": np.ones((n, k), bool)}
    loader = ClickLogLoader(data, batch_size=10, seed=2, drop_last=False)
    chunks = list(DevicePrefetcher(loader, chunk_batches=4))
    # 10 same-shape batches chunk as 4+4+2; the odd-shaped tail flushes into
    # its own chunk of 1 instead of breaking the stack
    assert [(c[2],) + tuple(c[0]["clicks"].shape) for c in chunks] == [
        (4, 4, 10, 4), (4, 4, 10, 4), (2, 2, 10, 4), (1, 1, 3, 4)]
    # every session appears exactly once across the stacked chunks
    seen = np.concatenate([np.asarray(c[0]["query_doc_ids"]).reshape(-1, k)[:, 0]
                           for c in chunks])
    assert len(set(seen.tolist())) == n
    # the recorded loader_state is the resume point after the chunk's last
    # batch: replaying from chunk 0's state yields batches 5.. onward
    state = chunks[0][1]
    resumed = ClickLogLoader(data, batch_size=10, seed=2, drop_last=False)
    resumed.load_state_dict(state)
    rest = list(iter(resumed))
    assert len(rest) == 7
    first_after = np.asarray(chunks[1][0]["query_doc_ids"])[0]
    np.testing.assert_array_equal(np.asarray(rest[0]["query_doc_ids"]),
                                  first_after)


def test_chunked_resume_is_bit_exact(tmp_path, pbm_log):
    """Interrupt + resume with checkpoint_every_steps not aligned to the
    chunk size: checkpoints land at chunk boundaries with the chunk's last
    loader_state, and the resumed run must match the uninterrupted one."""
    cfg, train, _ = pbm_log
    model = _model(cfg)

    def run(epochs, ckpt_dir, resume=False):
        loader = ClickLogLoader(train, batch_size=256, seed=5)
        trainer = Trainer(optim.adamw(0.01), epochs=epochs, patience=100,
                          checkpoint_dir=ckpt_dir, checkpoint_every_steps=5,
                          log_fn=lambda *_: None, chunk_batches=4)
        trainer.train(model, loader, None, resume=resume)
        return trainer._final_state.params

    p_full = run(4, str(tmp_path / "full"))
    run(2, str(tmp_path / "resume"))
    p_resumed = run(4, str(tmp_path / "resume"), resume=True)
    _assert_trees_equal(p_full, p_resumed)


def test_chunked_resume_through_prefetcher_mid_epoch(pbm_log, tmp_path):
    """Kill the run mid-epoch at a chunk boundary (checkpoint written from
    the chunk's loader_state while the prefetcher has run ahead), resume,
    and compare against an uninterrupted run."""
    cfg, train, _ = pbm_log
    model = _model(cfg)

    # uninterrupted: 2 epochs
    loader = ClickLogLoader(train, batch_size=256, seed=5)
    t_full = Trainer(optim.adamw(0.01), epochs=2, patience=100,
                     log_fn=lambda *_: None, chunk_batches=4)
    t_full.train(model, loader, None)

    # interrupted: stop after the first checkpoint (step 4 of 6 per epoch)
    class Stop(Exception):
        pass

    ckpt_dir = str(tmp_path / "mid")
    loader = ClickLogLoader(train, batch_size=256, seed=5)
    t_int = Trainer(optim.adamw(0.01), epochs=2, patience=100,
                    checkpoint_dir=ckpt_dir, checkpoint_every_steps=3,
                    log_fn=lambda *_: None, chunk_batches=4)
    saved = t_int._save
    calls = []

    def save_once(*args, **kwargs):
        saved(*args, **kwargs)
        calls.append(1)
        if len(calls) == 1:
            raise Stop

    t_int._save = save_once
    with pytest.raises(Stop):
        t_int.train(model, loader, None)

    # resume from the mid-epoch checkpoint with a FRESH loader
    loader = ClickLogLoader(train, batch_size=256, seed=5)
    t_res = Trainer(optim.adamw(0.01), epochs=2, patience=100,
                    checkpoint_dir=ckpt_dir, checkpoint_every_steps=10_000,
                    log_fn=lambda *_: None, chunk_batches=4)
    t_res.train(model, loader, None, resume=True)
    assert t_res._final_state.global_step == t_full._final_state.global_step
    _assert_trees_equal(t_full._final_state.params, t_res._final_state.params)


# ---------------------------------------------------------------------------
# Sparse embedding tables (optim/sparse.py lazy AdamW through the engine).
# ---------------------------------------------------------------------------

def _all_rows_batch(n_rows, b, k, seed):
    r = np.random.default_rng(seed)
    ids = r.permutation(n_rows).reshape(b, k)
    return {"positions": np.tile(np.arange(1, k + 1, dtype=np.int32), (b, 1)),
            "query_doc_ids": ids.astype(np.int64),
            "clicks": (r.random((b, k)) < 0.3).astype(np.float32),
            "mask": np.ones((b, k), bool)}


def test_sparse_tables_match_dense_adamw_when_all_rows_touched():
    """On a table whose every row appears in every batch, lazy AdamW must be
    bit-identical to the dense optimizer in params and losses, and agree
    with its moments to 1 ulp."""
    R, B, K = 24, 6, 4
    model = PositionBasedModel(query_doc_pairs=R, positions=K, init_prob=0.2)
    lr, wd = 0.05, 1e-3
    dense = TrainEngine(model, optim.adamw(lr, weight_decay=wd))
    sparse = TrainEngine(model, optim.adamw(lr, weight_decay=wd),
                         sparse_tables=True,
                         sparse_table_kwargs=dict(lr=lr, weight_decay=wd))
    p0 = model.init(jax.random.PRNGKey(1))
    p_d, p_s = _copy(p0), _copy(p0)
    o_d, o_s = dense.init_opt_state(_copy(p0)), sparse.init_opt_state(_copy(p0))
    for step in range(5):
        chunk = {k: v[None] for k, v in _all_rows_batch(R, B, K, step).items()}
        p_d, o_d, l_d = dense.step(p_d, o_d, chunk)
        p_s, o_s, l_s = sparse.step(p_s, o_s, chunk)
        assert float(l_d[0]) == float(l_s[0])
    _assert_trees_equal(p_d, p_s)
    st = o_s["sparse"]["attraction/table"]
    # The moments may differ by 1 ulp: the installed XLA orders the dense
    # and the scattered second-moment reductions differently.
    np.testing.assert_allclose(np.asarray(o_d[0].mu["attraction"]["table"]),
                               np.asarray(st.mu), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(o_d[0].nu["attraction"]["table"]),
                               np.asarray(st.nu), rtol=1e-6)


def test_sparse_tables_leave_untouched_rows_undecayed():
    """Rows absent from every batch keep their params AND moments untouched
    (lazy-Adam semantics); rows present get updated."""
    R, B, K = 24, 6, 4
    model = PositionBasedModel(query_doc_pairs=R, positions=K, init_prob=0.2)
    engine = TrainEngine(model, optim.adamw(0.05, weight_decay=0.0),
                         sparse_tables=True,
                         sparse_table_kwargs=dict(lr=0.05, weight_decay=0.0))
    params = _copy(model.init(jax.random.PRNGKey(2)))
    opt_state = engine.init_opt_state(_copy(model.init(jax.random.PRNGKey(2))))
    table0 = np.asarray(params["attraction"]["table"]).copy()
    r = np.random.default_rng(9)
    batch = {"positions": np.tile(np.arange(1, K + 1, dtype=np.int32), (B, 1)),
             "query_doc_ids": r.integers(0, 8, size=(B, K)).astype(np.int64),
             "clicks": (r.random((B, K)) < 0.5).astype(np.float32),
             "mask": np.ones((B, K), bool)}
    # warm the moments on rows 0..7, then keep stepping: moments of rows
    # 8.. must stay exactly zero (no decay, no weight-decay drift)
    for _ in range(4):
        chunk = {k: v[None] for k, v in batch.items()}
        params, opt_state, _ = engine.step(params, opt_state, chunk)
    table1 = np.asarray(params["attraction"]["table"])
    st = opt_state["sparse"]["attraction/table"]
    np.testing.assert_array_equal(table1[8:], table0[8:])
    np.testing.assert_array_equal(np.asarray(st.mu)[8:], 0.0)
    np.testing.assert_array_equal(np.asarray(st.nu)[8:], 0.0)
    assert not np.array_equal(table1[:8], table0[:8])
    assert np.any(np.asarray(st.mu)[:8] != 0.0)
    assert int(st.count) == 4


def test_sparse_row_grads_sentinel_padding_is_noop():
    """Fixed-size dedupe pads with the out-of-range sentinel: padding slots
    must not alias row 0 (the old fill_value=0 decayed its moments)."""
    from repro.optim.sparse import (init_sparse_table_state, sparse_adamw_update,
                                    sparse_row_grads)

    table = jnp.ones((8, 3))
    state = init_sparse_table_state(table)
    # lookups touch only rows 5 and 6; 4 lookup slots -> 2 padding slots
    ids = jnp.array([5, 6, 5, 6])
    row_grads = jnp.ones((4, 3))
    uids, grads = sparse_row_grads(row_grads, ids, n_rows=8)
    assert sorted(np.asarray(uids).tolist())[:2] == [5, 6]
    assert (np.asarray(uids) == 8).sum() == 2  # sentinel, not row 0
    new_table, new_state = sparse_adamw_update(table, state, uids, grads,
                                               lr=0.1)
    np.testing.assert_array_equal(np.asarray(new_table)[:5], 1.0)
    np.testing.assert_array_equal(np.asarray(new_state.mu)[0], 0.0)
    assert np.all(np.asarray(new_table)[5:7] != 1.0)


def test_sparse_tables_refuse_qr_compression():
    from repro.core import Compression, EmbeddingParameterConfig

    model = PositionBasedModel(
        query_doc_pairs=1024, positions=4,
        attraction=EmbeddingParameterConfig(
            parameters=1024, compression=Compression.QR, compression_ratio=4))
    with pytest.raises(NotImplementedError):
        TrainEngine(model, optim.adamw(0.05), sparse_tables=True,
                    sparse_table_kwargs=dict(lr=0.05, weight_decay=0.0))


def test_sparse_tables_require_explicit_hyperparams():
    """optim.adamw defaults weight_decay=1e-4 while the sparse update
    defaults to 0.0 — forgetting to mirror it must be an error, not a
    silent divergence from the dense optimizer."""
    model = PositionBasedModel(query_doc_pairs=64, positions=4)
    with pytest.raises(ValueError, match="weight_decay"):
        TrainEngine(model, optim.adamw(0.05), sparse_tables=True,
                    sparse_table_kwargs=dict(lr=0.05))


# ---------------------------------------------------------------------------
# Eval-step caching + single-transfer evaluation.
# ---------------------------------------------------------------------------

def test_eval_step_compiled_once_across_epochs(pbm_log):
    cfg, train, val = pbm_log
    model = _model(cfg)
    trainer = Trainer(optim.adamw(0.05), epochs=1, log_fn=lambda *_: None)
    makes = []
    original = trainer._make_eval_step

    def counting(model_, metrics_, replicas=None):
        makes.append(1)
        return original(model_, metrics_, replicas)

    trainer._make_eval_step = counting
    params = model.init(jax.random.PRNGKey(0))
    loader = ClickLogLoader(val, batch_size=128, shuffle=False,
                            drop_last=False)
    out1 = trainer.evaluate(model, params, loader)
    out2 = trainer.evaluate(model, params, loader)
    assert len(makes) == 1  # epochs 2..n reuse the compiled step
    assert out1 == out2
    assert set(out1) == {"ll", "ppl", "cond_ppl"}


# ---------------------------------------------------------------------------
# Data-parallel execution (8 fake host devices, subprocess — the main test
# process stays single-device, see tests/test_distrib.py).
# ---------------------------------------------------------------------------

DATA_PARALLEL_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax
from repro import optim
from repro.core import PositionBasedModel
from repro.data import ClickLogLoader, SyntheticConfig, generate_click_log, split_sessions
from repro.train import Trainer
from repro.launch.mesh import make_data_parallel_mesh

cfg = SyntheticConfig(n_sessions=2200, n_queries=25, docs_per_query=12,
                      positions=6, behavior="pbm", seed=13)
data, _ = generate_click_log(cfg)
train, val, _ = split_sessions(data, (0.8, 0.1, 0.1), seed=0)

def run(mesh):
    model = PositionBasedModel(query_doc_pairs=cfg.n_query_doc_pairs,
                               positions=cfg.positions, init_prob=0.2)
    trainer = Trainer(optim.adamw(0.05), epochs=2, patience=100,
                      log_fn=lambda *_: None, chunk_batches=4, mesh=mesh)
    loader = ClickLogLoader(train, batch_size=256, seed=5)
    vloader = ClickLogLoader(val, batch_size=128, shuffle=False,
                             drop_last=False)
    history = trainer.train(model, loader, vloader)
    return history, trainer._final_state.params

mesh = make_data_parallel_mesh()
assert dict(mesh.shape) == {"data": 8, "model": 1}, mesh.shape
h_dp, p_dp = run(mesh)
h_1, p_1 = run(None)
for (ka, a), (_, b) in zip(jax.tree_util.tree_leaves_with_path(p_1),
                           jax.tree_util.tree_leaves_with_path(p_dp)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5,
                               err_msg=str(ka))
for r1, r8 in zip(h_1, h_dp):
    assert abs(r1["train_loss"] - r8["train_loss"]) < 1e-5
    assert abs(r1["val_ll"] - r8["val_ll"]) < 1e-5

# params landed sharded on the mesh (replicated over data via model axis)
sharded = [x.sharding for x in jax.tree_util.tree_leaves(p_dp)]
assert all(len(s.device_set) == 8 for s in sharded), sharded

# indivisible batch size raises a clear error
model = PositionBasedModel(query_doc_pairs=cfg.n_query_doc_pairs,
                           positions=cfg.positions)
trainer = Trainer(optim.adamw(0.05), epochs=1, log_fn=lambda *_: None,
                  chunk_batches=4, mesh=mesh)
try:
    trainer.train(model, ClickLogLoader(train, batch_size=250, seed=5), None)
except ValueError as e:
    assert "divisible" in str(e), e
else:
    raise AssertionError("indivisible batch accepted")

# drop_last=False would leave an unsplittable tail batch: clear error upfront
try:
    trainer.train(model, ClickLogLoader(train, batch_size=256, seed=5,
                                        drop_last=False), None)
except ValueError as e:
    assert "drop_last" in str(e), e
else:
    raise AssertionError("drop_last=False accepted for data-parallel")
print("ENGINE_DP_OK")
"""


def test_data_parallel_engine_on_8_fake_devices():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    env["JAX_PLATFORMS"] = "cpu"  # see test_distrib.py: avoid TPU probing
    proc = subprocess.run([sys.executable, "-c", DATA_PARALLEL_SCRIPT],
                          capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "ENGINE_DP_OK" in proc.stdout


DATA_PARALLEL_KERNEL_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np
from repro import optim
from repro.core import DynamicBayesianNetwork
from repro.data import ClickLogLoader, SyntheticConfig, generate_click_log
from repro.kernels import override_impl
from repro.launch.mesh import make_data_parallel_mesh
from repro.obs import MemorySink, Recorder
from repro.train import Trainer

cfg = SyntheticConfig(n_sessions=2048, n_queries=25, docs_per_query=12,
                      positions=6, behavior="dbn", seed=13)
data, _ = generate_click_log(cfg)

def losses(mesh):
    sink = MemorySink()
    model = DynamicBayesianNetwork(query_doc_pairs=cfg.n_query_doc_pairs,
                                   positions=cfg.positions)
    trainer = Trainer(optim.adamw(0.05), epochs=1, log_fn=lambda *_: None,
                      chunk_batches=2, mesh=mesh,
                      recorder=Recorder(sinks=[sink]))
    trainer.train(model, ClickLogLoader(data, batch_size=256, seed=5))
    return np.array([e["value"] for e in sink.by_name("train_step")])

# The Pallas loss kernel cannot be partitioned by XLA: under the mesh it
# must run per data shard and still give the single-device losses.
with override_impl(examination_nll="pallas"):
    one, dp = losses(None), losses(make_data_parallel_mesh())
assert len(one) == 8, one
np.testing.assert_allclose(dp, one, rtol=1e-5)
print("ENGINE_DP_KERNEL_OK")
"""


def test_data_parallel_pallas_loss_matches_single_device():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c",
                           DATA_PARALLEL_KERNEL_SCRIPT],
                          capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "ENGINE_DP_KERNEL_OK" in proc.stdout
