"""The ``ubm-baidu`` configuration and its cell ``ubm-baidu.train``: the
plain reference against the program at the ``tinycell`` size on seeded
random weights, the least work, the control and the faults failing the
limits, the examination reducer, and the cell finding its own files."""
import numpy as np
import pytest

import tinycell
from harness import program, scopes, xtrace
from harness.cell import load_json, load_module
from test_bench_reducers import chunk_device, record_for

BENCH = tinycell.BENCH
CELL = "ubm-baidu.train"
EXAMINATION = "train.examination_ms_per_step"
CHUNK = "jit(_chunk_step)/while/body/closed_call/"


def test_least_work_is_one_tables_dense_adamw():
    cfg = load_json(BENCH / "configs" / "ubm-baidu.json")
    flops, nbytes = load_module(BENCH / "work" / "ubm-baidu.py").least_work(cfg)
    assert nbytes == pytest.approx(5.15e9, rel=5e-3)
    assert nbytes >= 24 * 214_748_672
    dctr = load_module(BENCH / "work" / "dctr-baidu.py").least_work(
        load_json(BENCH / "configs" / "dctr-baidu.json"))
    assert nbytes == dctr[1]            # the same table, batch and AdamW
    assert nbytes / 819e9 > 100 * flops / 197e12


def _tiny_inputs(cell, seed):
    """Random leaves in the program's shapes, and a batch of the mix with
    sessions of 0 to K clicks and tails masked off."""
    import jax

    from harness.sessions import SessionSource

    cfg = cell.config
    net = program.build_model(cfg)
    rng = np.random.default_rng(seed)
    leaves = {k: np.asarray(v) for k, v in
              program.leaves(net.init(jax.random.PRNGKey(0))).items()}
    leaves = {k: rng.normal(0.5, 1.5, v.shape).astype(np.float32)
              for k, v in leaves.items()}
    batch = SessionSource(cell.traffic["sessions"], seed).chunk(0, cfg["batch"])
    K = batch["clicks"].shape[1]
    for b in range(0, len(batch["clicks"]), 3):
        batch["clicks"][b] = rng.permutation(K) < (b // 3) % (K + 1)
    lengths = rng.integers(1, K + 1, size=len(batch["mask"]))
    batch["mask"] = np.arange(K)[None, :] < lengths[:, None]
    batch["clicks"] = batch["clicks"] * batch["mask"]
    return net, leaves, batch


def test_reference_matches_the_program_on_random_weights(tmp_path):
    """Loss, every leaf's gradient and one AdamW step of the program's UBM
    against ``configs/ubm-baidu.py`` at the tinycell size."""
    import jax
    import jax.numpy as jnp

    from harness import plain

    cell = tinycell.tiny_cell(CELL, tmp_path)
    cfg, ref = cell.config, cell.reference()
    net, leaves, batch = _tiny_inputs(cell, 5)
    assert set(leaves) == set(ref.LEAVES)
    params = program.nest({k: jnp.asarray(v) for k, v in leaves.items()})
    got, grads = jax.value_and_grad(net.compute_loss)(params, batch)
    with jax.default_matmul_precision("highest"):
        want, want_grads = jax.value_and_grad(ref.loss)(
            {k: jnp.asarray(v) for k, v in leaves.items()}, batch, cfg)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    grads = program.leaves(grads)
    for k in ref.LEAVES:
        np.testing.assert_allclose(grads[k], want_grads[k],
                                   rtol=1e-4, atol=1e-9)
    # one AdamW step from zero moments, the program's optimizer and plain's
    tx = program.optimizer(cfg)
    updates, _ = tx.update(program.nest(grads), tx.init(params), params)
    stepped = program.leaves(jax.tree_util.tree_map(jnp.add, params, updates))
    zeros = {k: jnp.zeros_like(v) for k, v in want_grads.items()}
    plain_step, _, _ = plain.adamw(leaves, want_grads, zeros, zeros, 1,
                                   cfg["optimizer"])
    for k in ref.LEAVES:
        np.testing.assert_allclose(stepped[k], plain_step[k],
                                   rtol=1e-6, atol=1e-7)


def test_reference_marginal_matches_the_program(tmp_path):
    """eq. 26: the reference's served click probability against the
    program's ``predict_clicks``."""
    import jax.numpy as jnp

    cell = tinycell.tiny_cell(CELL, tmp_path)
    net, leaves, batch = _tiny_inputs(cell, 6)
    params = program.nest({k: jnp.asarray(v) for k, v in leaves.items()})
    got = net.predict_clicks(params, batch)
    want = cell.reference().click_log_probs(
        {k: jnp.asarray(v) for k, v in leaves.items()}, batch, cell.config)
    np.testing.assert_allclose(np.exp(got), np.exp(want), rtol=2e-5)


def test_control_in_bfloat16_fails_the_limits(tmp_path):
    import jax.numpy as jnp

    from harness.compare import counted_leaves, judge, leaf_gap, max_rel_gap
    from harness.follow import follow
    from harness.sessions import SessionSource

    cell = tinycell.tiny_cell(CELL, tmp_path)
    ref = cell.reference()
    source = SessionSource(cell.traffic["sessions"], cell.seed)
    batches = [source.chunk(i, cell.config["batch"]) for i in range(2)]
    want = follow(ref, cell.config, batches)
    control = follow(ref, cell.config, batches, dtype=jnp.bfloat16)
    values = {"loss": max_rel_gap(control["losses"], want["losses"]),
              "grad": leaf_gap(control["mu"], want["mu"]),
              "step": leaf_gap(control["step"], want["step"],
                               counted_leaves(want["grad1"]))}
    assert not all(c.ok for c in judge(values, cell.limits)), values


def test_sound_run_is_correct(tmp_path):
    record = tinycell.run(tinycell.tiny_cell(CELL, tmp_path))
    assert record.attempted > 0 and record.failed == 0
    assert all(c.ok for c in record.checks), record.checks
    assert record.end_to_end["train_sessions_per_s"] > 0


def _unchanged(monkeypatch):
    from repro.train.engine import TrainEngine

    monkeypatch.setattr(TrainEngine, "_apply_update",
                        lambda self, params, opt_state, grads, batch:
                        (params, opt_state))


def _examination_frozen(monkeypatch):
    """The examination table takes no gradient (a lookup read as a
    constant): only the attraction table learns."""
    import jax

    from repro.core.parameterization import UBMExaminationParameter

    whole = UBMExaminationParameter.logit
    monkeypatch.setattr(UBMExaminationParameter, "logit",
                        lambda self, params, k, kp: whole(
                            self, jax.lax.stop_gradient(params), k, kp))


def _window_afresh(monkeypatch):
    from repro.train import Trainer

    whole = Trainer.train

    def afresh(self, model, loader, *args, state=None, **kwargs):
        return whole(self, model, loader, *args, **kwargs)

    monkeypatch.setattr(Trainer, "train", afresh)


@pytest.mark.parametrize("fault,caught_by", [
    (_unchanged, "step"), (_examination_frozen, "grad"),
    (_window_afresh, "loss")])
def test_fault_is_caught(fault, caught_by, tmp_path, monkeypatch):
    fault(monkeypatch)
    record = tinycell.run(tinycell.tiny_cell(CELL, tmp_path))
    bad = {c.name for c in record.checks if not c.ok}
    assert caught_by in bad, record.checks


def test_lost_moments_at_the_hand_over_reach_only_loss(tmp_path, monkeypatch):
    """A known blind spot: where the second ``train`` call starts AdamW's
    moments afresh, ``grad`` and ``step`` (read before the hand-over) are
    exactly those of a sound run, and only ``loss`` moves. At the cell's
    size it moves less than its limit, so ``correct`` cannot see this
    fault."""
    import dataclasses

    from repro.train import Trainer

    sound = {c.name: c.value for c in
             tinycell.run(tinycell.tiny_cell(CELL, tmp_path / "a")).checks}
    whole = Trainer.train

    def afresh(self, model, loader, *args, state=None, **kwargs):
        if state is not None:
            state = dataclasses.replace(
                state, opt_state=self.optimizer.init(state.params))
        return whole(self, model, loader, *args, state=state, **kwargs)

    monkeypatch.setattr(Trainer, "train", afresh)
    lost = {c.name: c.value for c in
            tinycell.run(tinycell.tiny_cell(CELL, tmp_path / "b")).checks}
    assert lost["grad"] == sound["grad"] and lost["step"] == sound["step"]
    assert lost["loss"] != sound["loss"]


@pytest.mark.parametrize("op_name,inside", [
    (CHUNK + "jvp(loss)/examination/max:", True),
    (CHUNK + "transpose(jvp(loss))/examination/dot_general:", True),
    (CHUNK + "transpose(jvp(examination))/scatter-add:", True),
    (CHUNK + "jvp(loss)/log1p:", False),
    (CHUNK + "jvp(loss)/examination_nll/pallas_call:", False),
    (CHUNK + "transpose(jvp(embed))/jit(_take)/scatter-add:", False),
    ("", False),
])
def test_examination_scope_is_read_from_the_op_name(op_name, inside):
    metric = load_module(BENCH / "metrics" / f"{EXAMINATION}.py")
    assert metric.in_scope(op_name) is inside


def test_an_unnamed_op_counts_where_its_readers_do():
    """As ``scopes.inherit`` gives ``loss``: an operation with no op_name
    read only by the examination is in it; one read by the examination and
    by another scope, or carrying an op_name of its own, is not."""
    metric = load_module(BENCH / "metrics" / f"{EXAMINATION}.py")
    cummax = "%reduce-window.3 = s32[8,10]{1,0} reduce-window(s32[8,10] %copy.1)"
    lookup = ("%fusion.7 = f32[8,10]{1,0} fusion(s32[8,10]{1,0} "
              "%reduce-window.3, f32[10,10]{1,0} %param.4)")
    copy = "%copy.1 = s32[8,10]{1,0} copy(s32[8,10]{1,0} %param.2)"
    nll = "%fusion.9 = f32[8,10]{1,0} fusion(s32[8,10]{1,0} %copy.1)"
    ops = {cummax: "", lookup: CHUNK + "jvp(loss)/examination/dot_general:",
           copy: "", nll: CHUNK + "jvp(loss)/log1p:"}
    assert metric.examination_ops(ops) == {cummax, lookup}
    ops[copy] = CHUNK + "jvp(loss)/examination/copy:"
    assert metric.examination_ops(ops) == {cummax, lookup, copy}
    assert metric.examination_ops({cummax: ""}) == set()


def test_examination_reducer_reads_its_ops(monkeypatch):
    """fusion.2 (2 ms an execution) is the examination's backward; the
    update and the collective are not."""
    dev = chunk_device()
    ops = {"fusion.1": CHUNK + "update/add:",
           "all-reduce.7": CHUNK + "transpose(jvp(embed))/psum:",
           "fusion.2": CHUNK + "transpose(jvp(loss))/examination/mul:"}
    monkeypatch.setattr(scopes, "op_names", lambda path: {
        dev.name: {(0, name): op for name, op in ops.items()}})
    run = record_for(CELL, [xtrace.figures(dev, by_program=True)])
    run.trace = xtrace.Trace(devices=[dev], host=[], path="synthetic")
    metric = run.cell.metric(EXAMINATION)
    # two executions x 8 steps
    assert metric.reduce(run) == pytest.approx(2 * 2 / 16)
    # a program without the scope (the parent commit) reads nothing
    ops["fusion.2"] = CHUNK + "jvp(loss)/mul:"
    assert metric.reduce(run) is None
    run.trace = None
    assert metric.reduce(run) is None


def test_new_cells_find_their_own_files():
    dp4 = tinycell.cell_named("dbn-baidu.train-dp4", chips=4)
    assert dp4.chips == 4 and dp4.traffic["data_parallel"]
    assert dp4.limits == load_json(BENCH / "limits" /
                                   "dbn-baidu.train-dp4.json")
    names = {m["name"] for m in dp4.per_layer}
    assert "train.collective_ms_per_step" in names
    assert EXAMINATION not in names
    ubm = tinycell.cell_named(CELL)
    assert ubm.limits == load_json(BENCH / "limits" / f"{CELL}.json")
    names = {m["name"] for m in ubm.per_layer}
    assert EXAMINATION in names and "train.loss_ms_per_step" in names
    assert "train.collective_ms_per_step" not in names
    assert ubm.reference().LEAVES[-1] == "examination/table"
    assert [m["name"] for m in ubm.end_to_end] == [
        "setup_s", "train_sessions_per_s"]
