"""Chaos suite: deterministic fault injection against every hardened layer.

Each test injects one failure class (disk corruption, NaN batches, flaky IO,
a dead read-ahead producer, torn checkpoints, process death) and asserts the
system's declared guarantee: deterministic skip, retry-then-recover,
fall-back-to-valid, or crash-exact resume.
"""
import json
import os
import signal
import subprocess
import sys
import threading
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim
from repro.core import PositionBasedModel
from repro.data import (ClickLogLoader, SessionStore, ShardCorruptionError,
                        StreamingClickLogLoader, SyntheticConfig,
                        generate_click_log, split_sessions,
                        write_session_store)
from repro.testing import (FlakyShardReads, KillSwitch,
                           NonFiniteBatchInjector, corrupt_shard_file,
                           truncate_tail)
from repro.train import (CheckpointCorruptionError, CheckpointManager,
                         PreemptionHandler, TrainEngine, Trainer,
                         run_with_restarts)


# -- fixtures -----------------------------------------------------------------
@pytest.fixture()
def small_log():
    cfg = SyntheticConfig(n_sessions=600, n_queries=20, docs_per_query=10,
                          positions=5, behavior="pbm", seed=11)
    data, _ = generate_click_log(cfg)
    return cfg, data


@pytest.fixture()
def store_dir(tmp_path, small_log):
    cfg, data = small_log
    d = str(tmp_path / "store")
    write_session_store(data, d, shard_rows=150)  # 4 shards
    return d


@pytest.fixture()
def store_dir_auto(tmp_path, small_log):
    """Same 4-shard store written with per-column compression (format v2)."""
    cfg, data = small_log
    d = str(tmp_path / "store_auto")
    write_session_store(data, d, shard_rows=150, codec="auto")
    return d


def _model(cfg):
    return PositionBasedModel(query_doc_pairs=cfg.n_query_doc_pairs,
                              positions=cfg.positions)


# -- fault injector primitives -------------------------------------------------
def test_corrupt_shard_file_breaks_crc(store_dir):
    store = SessionStore(store_dir)
    store.verify()  # pristine store passes
    info = corrupt_shard_file(store_dir, shard=1, column="clicks", seed=3)
    assert info["column"] == "clicks" and len(info["offsets"]) == 1
    with pytest.raises(ShardCorruptionError):
        SessionStore(store_dir).verify(1)
    # other shards still verify
    SessionStore(store_dir).verify(0)


def test_corrupt_shard_file_is_replayable(store_dir):
    a = corrupt_shard_file(store_dir, shard=0, seed=7)
    b = corrupt_shard_file(store_dir, shard=0, seed=7)  # same bytes re-flipped
    assert a["offsets"] == b["offsets"]
    SessionStore(store_dir).verify(0)  # double XOR restored the bytes


def test_nonfinite_injector_counts(small_log):
    cfg, data = small_log
    loader = ClickLogLoader(data, batch_size=64, seed=5)
    inj = NonFiniteBatchInjector(loader, at_steps=[1, 3], key="clicks")
    batches = list(iter(inj))
    assert inj.injected == 2 and inj.produced == len(batches)
    assert np.isnan(batches[1]["clicks"]).all()
    assert np.isfinite(batches[0]["clicks"]).all()
    assert inj.batch_size == 64  # proxy forwards attributes


# -- non-finite guard in the engine / trainer ---------------------------------
def test_nonfinite_guard_skips_poisoned_step(small_log):
    cfg, data = small_log
    model = _model(cfg)
    loader = ClickLogLoader(data, batch_size=64, seed=5)
    engine = TrainEngine(model, optim.adamw(0.05), chunk_batches=4,
                         nonfinite_guard=True)
    params = model.init(jax.random.PRNGKey(0))
    opt_state = engine.init_opt_state(params)
    chunks = []
    batches = [b for b in iter(loader)][:4]
    poisoned = dict(batches[2])
    poisoned["clicks"] = np.full_like(poisoned["clicks"], np.nan)
    batches[2] = poisoned
    chunk = {k: np.stack([b[k] for b in batches]) for k in batches[0]}
    params2, opt2, telemetry = engine.step(params, opt_state, chunk)
    skipped = np.asarray(telemetry["skipped"])
    np.testing.assert_array_equal(skipped, [False, False, True, False])
    losses = np.asarray(telemetry["loss"])
    assert np.isnan(losses[2]) and np.isfinite(losses[[0, 1, 3]]).all()
    # params stayed finite through the poisoned step
    for leaf in jax.tree_util.tree_leaves(jax.device_get(params2)):
        assert np.isfinite(leaf).all()


def test_trainer_nonfinite_guard_counts_and_stays_finite(small_log):
    cfg, data = small_log
    model = _model(cfg)
    loader = NonFiniteBatchInjector(
        ClickLogLoader(data, batch_size=64, seed=5), at_steps=[2, 12])
    trainer = Trainer(optim.adamw(0.05), epochs=2, patience=100,
                      chunk_batches=3, nonfinite_guard=True,
                      log_fn=lambda *_: None)
    history = trainer.train(model, loader)
    assert [r["skipped_steps"] for r in history] == [1, 1]
    assert all(np.isfinite(r["train_loss"]) for r in history)
    for leaf in jax.tree_util.tree_leaves(
            jax.device_get(trainer._final_state.params)):
        assert np.isfinite(leaf).all()


def test_trainer_guard_off_poisoned_params_diverge(small_log):
    # control: without the guard a NaN batch destroys the run
    cfg, data = small_log
    model = _model(cfg)
    loader = NonFiniteBatchInjector(
        ClickLogLoader(data, batch_size=64, seed=5), at_steps=[2])
    trainer = Trainer(optim.adamw(0.05), epochs=1, patience=100,
                      chunk_batches=3, log_fn=lambda *_: None)
    history = trainer.train(model, loader)
    assert "skipped_steps" not in history[0]
    assert not np.isfinite(history[0]["train_loss"])


def test_nonfinite_guard_replicas(small_log):
    cfg, data = small_log
    model = _model(cfg)
    loader = NonFiniteBatchInjector(
        ClickLogLoader(data, batch_size=64, seed=5), at_steps=[1])
    trainer = Trainer(optim.adamw(0.05), epochs=1, patience=100, replicas=2,
                      chunk_batches=3, nonfinite_guard=True,
                      log_fn=lambda *_: None)
    history = trainer.train(model, loader)
    # a broadcast poisoned batch skips on every replica
    assert history[0]["skipped_steps"] == [1, 1]
    assert all(np.isfinite(v) for v in history[0]["train_loss"])


# -- self-healing streaming data plane ----------------------------------------
def test_streaming_verify_checksums_raises(store_dir):
    corrupt_shard_file(store_dir, shard=2, column="clicks", seed=1)
    loader = StreamingClickLogLoader(store_dir, batch_size=50,
                                     verify_checksums=True)
    with pytest.raises(ShardCorruptionError):
        list(iter(loader))
    # without verification the corrupt bytes stream through silently
    loader2 = StreamingClickLogLoader(store_dir, batch_size=50)
    assert len(list(iter(loader2))) == loader2.batches_per_epoch


def test_streaming_skip_policy_is_deterministic(store_dir):
    clean = [b["clicks"].copy() for b in iter(
        StreamingClickLogLoader(store_dir, batch_size=50, seed=3))]
    corrupt_shard_file(store_dir, shard=1, column="clicks", seed=1)
    logs = []

    def run():
        ld = StreamingClickLogLoader(store_dir, batch_size=50, seed=3,
                                     verify_checksums=True,
                                     corrupt_policy="skip",
                                     log_fn=logs.append)
        return ld, [b["clicks"].copy() for b in iter(ld)]

    ld_a, run_a = run()
    ld_b, run_b = run()
    assert ld_a.quarantined == {1}
    assert len(run_a) == len(run_b) < len(clean)
    for x, y in zip(run_a, run_b):
        np.testing.assert_array_equal(x, y)
    assert any("QUARANTINED shard 1" in m for m in logs)
    # epoch 2 pre-excludes the quarantined shard and agrees with the cap
    run_a2 = [b["clicks"] for b in iter(ld_a)]
    assert len(run_a2) == ld_a.batches_per_epoch


def test_streaming_quarantine_rides_state_dict(store_dir):
    # corrupt the shard that epoch 0 (seed=3) opens FIRST, so the quarantine
    # deterministically precedes the mid-epoch save below
    first = int(np.random.default_rng((3, 0, 0)).permutation(4)[0])
    corrupt_shard_file(store_dir, shard=first, column="clicks", seed=1)
    mk = lambda: StreamingClickLogLoader(store_dir, batch_size=50, seed=3,
                                         verify_checksums=True,
                                         corrupt_policy="skip",
                                         log_fn=lambda *_: None)
    full_ld = mk()
    full = [b["clicks"].copy() for b in iter(full_ld)]
    part = mk()
    it = iter(part)
    head = [next(it)["clicks"].copy() for _ in range(2)]
    sd = part.state_dict()
    it.close()
    assert sd["quarantined"] == [first]
    resumed = mk()
    resumed.load_state_dict(sd)
    tail = [b["clicks"].copy() for b in iter(resumed)]
    assert len(head) + len(tail) == len(full)
    for x, y in zip(head + tail, full):
        np.testing.assert_array_equal(x, y)


def test_streaming_skip_policy_rejected_multihost(store_dir):
    with pytest.raises(ValueError, match="per-host"):
        StreamingClickLogLoader(store_dir, batch_size=50, host_id=0,
                                host_count=2, corrupt_policy="skip")
    with pytest.raises(ValueError, match="corrupt_policy"):
        StreamingClickLogLoader(store_dir, batch_size=50,
                                corrupt_policy="quarantine")


def test_compressed_store_corruption_fails_closed(store_dir_auto):
    """Corrupting a *compressed* column (bitpacked clicks) trips the same
    crc-over-stored-bytes path as a raw one under verify_checksums=True."""
    store = SessionStore(store_dir_auto)
    assert store.shard_codec(2, "clicks") == "bitpack"
    corrupt_shard_file(store_dir_auto, shard=2, column="clicks", seed=1)
    loader = StreamingClickLogLoader(store_dir_auto, batch_size=50,
                                     verify_checksums=True)
    with pytest.raises(ShardCorruptionError):
        list(iter(loader))


def test_compressed_store_quarantine_is_deterministic(store_dir_auto):
    """skip-policy quarantine works unchanged on a compressed store: the
    corrupt shard contributes zero rows, replayably."""
    clean = [b["clicks"].copy() for b in iter(
        StreamingClickLogLoader(store_dir_auto, batch_size=50, seed=3))]
    corrupt_shard_file(store_dir_auto, shard=1, column="clicks", seed=1)
    logs = []

    def run():
        ld = StreamingClickLogLoader(store_dir_auto, batch_size=50, seed=3,
                                     verify_checksums=True,
                                     corrupt_policy="skip",
                                     log_fn=logs.append)
        return ld, [b["clicks"].copy() for b in iter(ld)]

    ld_a, run_a = run()
    ld_b, run_b = run()
    assert ld_a.quarantined == {1}
    assert len(run_a) == len(run_b) < len(clean)
    for x, y in zip(run_a, run_b):
        np.testing.assert_array_equal(x, y)
    assert any("QUARANTINED shard 1" in m for m in logs)


def test_streaming_io_retry_recovers(store_dir):
    clean = [b["clicks"].copy() for b in iter(
        StreamingClickLogLoader(store_dir, batch_size=50, seed=3))]
    flaky = FlakyShardReads(SessionStore(store_dir), fail_times=2)
    loader = StreamingClickLogLoader(flaky, batch_size=50, seed=3,
                                     io_retries=3, io_retry_backoff=0.001,
                                     log_fn=lambda *_: None)
    got = [b["clicks"].copy() for b in iter(loader)]
    assert flaky.failures == 2 and len(got) == len(clean)
    for x, y in zip(got, clean):
        np.testing.assert_array_equal(x, y)


def test_streaming_io_retries_exhausted_raises(store_dir):
    flaky = FlakyShardReads(SessionStore(store_dir), fail_times=100)
    loader = StreamingClickLogLoader(flaky, batch_size=50, io_retries=1,
                                     io_retry_backoff=0.001,
                                     watchdog_restarts=1,
                                     log_fn=lambda *_: None)
    with pytest.raises(OSError, match="injected transient"):
        list(iter(loader))


def test_producer_watchdog_restarts_once(store_dir):
    clean = [b["clicks"].copy() for b in iter(
        StreamingClickLogLoader(store_dir, batch_size=50, seed=3))]
    logs = []
    # two failures, no per-read retries: only the watchdog's restarted
    # producer (third open_shard call) survives
    flaky = FlakyShardReads(SessionStore(store_dir), fail_times=2)
    loader = StreamingClickLogLoader(flaky, batch_size=50, seed=3,
                                     io_retries=0, watchdog_restarts=2,
                                     log_fn=logs.append)
    got = [b["clicks"].copy() for b in iter(loader)]
    assert len(got) == len(clean)
    for x, y in zip(got, clean):
        np.testing.assert_array_equal(x, y)
    assert sum("producer died" in m for m in logs) == 2


def test_producer_error_preserves_traceback(store_dir):
    flaky = FlakyShardReads(SessionStore(store_dir), fail_times=100)
    loader = StreamingClickLogLoader(flaky, batch_size=50, io_retries=0,
                                     watchdog_restarts=0,
                                     log_fn=lambda *_: None)
    try:
        list(iter(loader))
        raise AssertionError("expected OSError")
    except OSError as e:
        frames = [f.name for f in traceback.extract_tb(e.__traceback__)]
        # the worker thread's frames survive the cross-thread re-raise
        assert "_read_plan" in frames and "open_shard" in frames


def test_abandoned_iterator_joins_reader_thread(store_dir):
    loader = StreamingClickLogLoader(store_dir, batch_size=50, seed=3,
                                     window_rows=25, read_ahead=1)
    it = iter(loader)
    next(it)
    it.close()  # abandon mid-epoch; generator finally must stop + join
    deadline = time.time() + 5.0
    while time.time() < deadline:
        alive = [t for t in threading.enumerate()
                 if t.name == "store-read-ahead" and t.is_alive()]
        if not alive:
            break
        time.sleep(0.01)
    assert not alive, "read-ahead thread leaked after iterator abandonment"


def test_close_beats_watchdog_no_restart_after_shutdown(store_dir):
    """Regression: a producer dying around a cross-thread close() must
    surface shutdown (or the original error) immediately — the watchdog
    never restarts a producer after close(), even with restarts budgeted."""
    store = SessionStore(store_dir)
    real = store.open_shard

    def open_shard(i, **kw):
        if i != 0:
            raise OSError(f"injected: shard {i} unreachable")
        return real(i, **kw)

    store.open_shard = open_shard
    logs = []
    loader = StreamingClickLogLoader(store, batch_size=50, shuffle=False,
                                     read_ahead=2, watchdog_restarts=5,
                                     log_fn=logs.append)
    it = iter(loader)
    next(it)  # shard 0 delivered; the producer dies on shard 1
    loader.close()
    with pytest.raises((RuntimeError, OSError)):
        for _ in it:
            pass
    assert not any("restarting" in m for m in logs)
    # closed is permanent: a fresh epoch refuses to start
    with pytest.raises(RuntimeError, match="closed"):
        next(iter(loader))


def test_close_stops_inline_stream_too(store_dir):
    """The read_ahead=0 path honors close() between windows as well."""
    loader = StreamingClickLogLoader(store_dir, batch_size=50, shuffle=False,
                                     read_ahead=0)
    it = iter(loader)
    next(it)
    loader.close()
    with pytest.raises(RuntimeError, match="close"):
        for _ in it:
            pass


# -- hardened checkpoints ------------------------------------------------------
@pytest.fixture()
def ckpt_tree():
    return {"w": jnp.arange(12.0).reshape(3, 4), "b": jnp.ones(4)}


def test_checkpoint_writes_leaf_checksums(tmp_path, ckpt_tree):
    m = CheckpointManager(str(tmp_path), log_fn=lambda *_: None)
    m.save(1, ckpt_tree)
    meta = json.load(open(tmp_path / "step_0000000001" / "structure.json"))
    assert set(meta["checksums"]) == {"w", "b"}


def test_restore_falls_back_to_newest_valid(tmp_path, ckpt_tree):
    logs = []
    m = CheckpointManager(str(tmp_path), keep=5, log_fn=logs.append)
    for s in (1, 2, 3):
        m.save(s, ckpt_tree, aux={"s": s})
    truncate_tail(str(tmp_path / "step_0000000003" / "arrays.npz"), 64)
    tree, aux, step = m.restore(like=ckpt_tree)
    assert step == 2 and aux["s"] == 2
    np.testing.assert_array_equal(np.asarray(tree["w"]),
                                  np.asarray(ckpt_tree["w"]))
    # the torn checkpoint was deleted, not just skipped
    assert not (tmp_path / "step_0000000003").exists()
    assert any("corrupt" in m_ for m_ in logs)


def test_restore_detects_bit_rot_via_crc(tmp_path, ckpt_tree):
    m = CheckpointManager(str(tmp_path), keep=5, log_fn=lambda *_: None)
    m.save(1, ckpt_tree, aux={"s": 1})
    m.save(2, ckpt_tree, aux={"s": 2})
    path = tmp_path / "step_0000000002" / "arrays.npz"
    data = bytearray(path.read_bytes())
    data[len(data) // 2] ^= 0xFF
    path.write_bytes(bytes(data))
    _, aux, step = m.restore(like=ckpt_tree)
    assert step == 1


def test_restore_explicit_corrupt_step_raises(tmp_path, ckpt_tree):
    m = CheckpointManager(str(tmp_path), log_fn=lambda *_: None)
    m.save(1, ckpt_tree)
    truncate_tail(str(tmp_path / "step_0000000001" / "arrays.npz"), 16)
    with pytest.raises(CheckpointCorruptionError):
        m.restore(step=1, like=ckpt_tree)


def test_all_checkpoints_invalid_raises_not_found(tmp_path, ckpt_tree):
    m = CheckpointManager(str(tmp_path), log_fn=lambda *_: None)
    m.save(1, ckpt_tree)
    truncate_tail(str(tmp_path / "step_0000000001" / "arrays.npz"), 16)
    with pytest.raises(FileNotFoundError):
        m.restore(like=ckpt_tree)


def test_partial_write_gc_and_pre_checksum_compat(tmp_path, ckpt_tree):
    m = CheckpointManager(str(tmp_path), log_fn=lambda *_: None)
    m.save(4, ckpt_tree, aux={"s": 4})
    # crash-mid-save simulants: tmp dir and COMMIT-less step dir
    (tmp_path / ".tmp_step_9_x").mkdir()
    partial = tmp_path / "step_0000000009"
    partial.mkdir()
    (partial / "arrays.npz").write_bytes(b"torn")
    # legacy checkpoint without checksums must stay restorable
    sp = tmp_path / "step_0000000004" / "structure.json"
    meta = json.loads(sp.read_text())
    del meta["checksums"]
    sp.write_text(json.dumps(meta))
    m2 = CheckpointManager(str(tmp_path), log_fn=lambda *_: None)
    assert not (tmp_path / ".tmp_step_9_x").exists()
    assert not partial.exists()
    _, aux, step = m2.restore(like=ckpt_tree)
    assert step == 4 and aux["s"] == 4


# -- preemption + restarts -----------------------------------------------------
def test_preemption_handler_context_manager_restores():
    before_term = signal.getsignal(signal.SIGTERM)
    before_int = signal.getsignal(signal.SIGINT)
    with PreemptionHandler() as h:
        assert signal.getsignal(signal.SIGTERM) is not before_term
        assert signal.getsignal(signal.SIGINT) is not before_int  # new default
        os.kill(os.getpid(), signal.SIGTERM)
        assert h.should_stop
    assert signal.getsignal(signal.SIGTERM) is before_term
    assert signal.getsignal(signal.SIGINT) is before_int


def test_preemption_handler_restores_on_exception():
    before = signal.getsignal(signal.SIGTERM)
    with pytest.raises(RuntimeError):
        with PreemptionHandler():
            raise RuntimeError("train loop blew up")
    assert signal.getsignal(signal.SIGTERM) is before


def test_run_with_restarts_recovers_from_crash(tmp_path):
    marker = tmp_path / "crashed_once"
    script = tmp_path / "flaky.py"
    script.write_text(
        "import os, sys\n"
        f"m = {str(marker)!r}\n"
        "if not os.path.exists(m):\n"
        "    open(m, 'w').close()\n"
        "    sys.exit(137)\n"
        "print('done')\n")
    logs = []
    rc = run_with_restarts([sys.executable, str(script)], max_restarts=2,
                           log_fn=logs.append)
    assert rc == 0
    assert any("relaunching" in m for m in logs)


def test_restart_parent_initializes_no_backend(tmp_path):
    """The --max-restarts parent must not hold the device its child needs:
    no JAX backend may exist by the time the child is started."""
    code = (
        "import sys, repro.train\n"
        "from jax._src import xla_bridge\n"
        "def probe(argv, max_restarts):\n"
        "    assert not xla_bridge.backends_are_initialized()\n"
        "    return 0\n"
        "repro.train.run_with_restarts = probe\n"
        "from repro.launch import train\n"
        f"sys.argv = ['train', '--sessions', '100', '--ckpt-dir', "
        f"{str(tmp_path)!r}, '--max-restarts', '1']\n"
        "train.main()\n")
    src = os.path.join(os.path.dirname(__file__), os.pardir, "src")
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [src] + [p for p in [os.environ.get("PYTHONPATH")] if p]))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_run_with_restarts_budget_exhausted(tmp_path):
    script = tmp_path / "always_dies.py"
    script.write_text("import sys; sys.exit(3)\n")
    rc = run_with_restarts([sys.executable, str(script)], max_restarts=1,
                           log_fn=lambda *_: None)
    assert rc == 3


# -- crash-exact resume (the tentpole proof obligation) ------------------------
_RUN_SCRIPT = r"""
import json, os, signal, sys
import numpy as np
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax
from repro import optim
from repro.core import PositionBasedModel
from repro.data import StreamingClickLogLoader
from repro.testing import KillSwitch
from repro.train import Trainer

store, ckpt_dir, kill_at, out, n_pairs, n_pos = (
    sys.argv[1], sys.argv[2], int(sys.argv[3]), sys.argv[4],
    int(sys.argv[5]), int(sys.argv[6]))
loader = StreamingClickLogLoader(store, batch_size=50, seed=5)
if kill_at >= 0:
    committed = os.path.isdir(ckpt_dir) and any(
        n.startswith("step_") and
        os.path.exists(os.path.join(ckpt_dir, n, "COMMIT"))
        for n in os.listdir(ckpt_dir))
    if not committed:
        loader = KillSwitch(loader, after_batches=kill_at,
                            sig=signal.SIGKILL)
model = PositionBasedModel(query_doc_pairs=n_pairs, positions=n_pos)
trainer = Trainer(optim.adamw(0.05), epochs=3, patience=100, seed=7,
                  checkpoint_dir=ckpt_dir, checkpoint_every_steps=4,
                  chunk_batches=2, nonfinite_guard=True,
                  log_fn=lambda *_: None)
hist = trainer.train(model, loader, resume=True)
leaves = jax.tree_util.tree_leaves(
    jax.device_get(trainer._final_state.params))
digest = [np.asarray(l).tobytes().hex() for l in leaves]
for r in hist:
    r.pop("seconds", None)
json.dump({"history": hist, "digest": digest}, open(out, "w"))
"""


def test_sigkill_and_resume_is_bit_exact(tmp_path, small_log):
    cfg, data = small_log
    store = str(tmp_path / "store")
    write_session_store(data, store, shard_rows=150)
    script = tmp_path / "run.py"
    script.write_text(_RUN_SCRIPT)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), os.pardir, "src"),
         env.get("PYTHONPATH", "")])
    tail = [str(cfg.n_query_doc_pairs), str(cfg.positions)]

    def run(kill_at, tag):
        ckpt = str(tmp_path / f"ckpt_{tag}")
        out = str(tmp_path / f"out_{tag}.json")
        attempts = 0
        while True:
            p = subprocess.run(
                [sys.executable, str(script), store, ckpt, str(kill_at), out]
                + tail, env=env, capture_output=True, text=True)
            attempts += 1
            if p.returncode == 0:
                return json.load(open(out)), attempts
            assert p.returncode == -signal.SIGKILL, p.stderr[-2000:]
            assert attempts < 4, "kill switch failed to disarm after resume"

    clean, clean_attempts = run(-1, "clean")
    assert clean_attempts == 1
    # kill mid-epoch 2 (12 batches/epoch at bs=50, checkpoints every 4
    # steps): epoch-1 checkpoints are committed long before batch 17
    killed, attempts = run(17, "killed")
    assert attempts == 2  # died exactly once, then completed
    assert killed["digest"] == clean["digest"]  # params bit-for-bit
    assert killed["history"] == clean["history"]  # incl. mid-epoch losses


# -- caller-armed kill gate & flaky-read latency -------------------------------
def test_killswitch_caller_armed_gate(small_log):
    """A disarmed KillSwitch is inert through any number of batches; after
    arm() it fires exactly once at the pinned batch index. SIGTERM is
    absorbed by a PreemptionHandler so the gate is testable in-process."""
    cfg, data = small_log
    ks = KillSwitch(ClickLogLoader(data, batch_size=64, seed=5),
                    after_batches=0, sig=signal.SIGTERM, armed=False)
    with PreemptionHandler() as h:
        for _ in ks:
            pass
        assert not ks.fired and not h.should_stop
        ks.arm()
        ks.produced = 0
        next(iter(ks))
        assert ks.fired and h.should_stop
        # fire-once: replaying the pinned batch does not re-signal
        h.should_stop = False
        ks.produced = 0
        next(iter(ks))
        assert not h.should_stop


def test_flaky_reads_delay_seconds(store_dir):
    """FlakyShardReads charges its configured latency on the failing calls
    (slow remote filesystem), then passes through at full speed."""
    flaky = FlakyShardReads(SessionStore(store_dir), fail_times=2,
                            delay_seconds=0.05)
    t0 = time.perf_counter()
    for _ in range(2):
        with pytest.raises(OSError):
            flaky.open_shard(0)
    elapsed = time.perf_counter() - t0
    assert elapsed >= 0.1  # two delayed failures
    shard = flaky.open_shard(0)  # third call passes through
    assert shard is not None
    assert flaky.failures == 2 and flaky.calls == 3
