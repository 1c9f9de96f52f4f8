"""The trace reduction and each per-layer reducer on a small synthetic
trace whose busy, idle, span and collective figures are known."""
import pytest

import tinycell
from tinycell import BENCH
from harness import xtrace
from harness.record import Record

MS = 1_000_000  # ns


def chunk_device(name="/device:TPU:0"):
    """Four executions of a 10 ms chunk program, 2 ms apart. Inside each:
    an op over [0, 4) ms, a collective over [3, 6) ms (alone over [4, 6)),
    an op over [7, 9) ms; the chip is idle over [6, 7) and [9, 12)."""
    ops, modules = [], []
    for i in range(4):
        t = i * 12 * MS
        modules.append((t, t + 10 * MS, "jit__chunk_step"))
        ops += [(t, t + 4 * MS, "fusion.1"),
                (t + 3 * MS, t + 6 * MS, "all-reduce.7"),
                (t + 7 * MS, t + 9 * MS, "fusion.2")]
    modules.append((48 * MS, 49 * MS, "jit_small"))
    ops.append((48 * MS, 49 * MS, "copy.3"))
    return xtrace.Device(name, sorted(ops), sorted(modules))


def test_window_drops_the_cut_executions_and_bounds_by_the_device():
    f = xtrace.figures(chunk_device(), by_program=True)
    # executions 2 and 3 of 4: [12, 34) ms
    assert f.executions == 2
    assert f.window_s == pytest.approx(22e-3)
    # busy: [0, 6) + [7, 9) in each of the two, and nothing in the gap
    assert f.busy_s == pytest.approx(2 * 8e-3)
    assert f.collective_s == pytest.approx(2 * 3e-3)
    assert f.collective_exposed_s == pytest.approx(2 * 2e-3)
    assert f.op_seconds["fusion.1"] == pytest.approx(8e-3)
    lengths = sorted(round((e - s) / MS, 6) for s, e in f.gaps)
    assert lengths == [1.0, 1.0, 1.0, 3.0]


def test_window_without_a_step_program_spans_every_op():
    f = xtrace.figures(chunk_device(), by_program=False)
    assert f.window_s == pytest.approx(49e-3)
    assert f.busy_s == pytest.approx(4 * 8e-3 + 1e-3)


def test_breakdown_names_gaps_by_the_host():
    dev = chunk_device()
    host = [(21 * MS, 24 * MS, "TransferToDevice"),
            (0, 60 * MS, "ThreadMain")]
    trace = xtrace.Trace(devices=[dev], host=host)
    f = xtrace.figures(dev, by_program=True)
    b = xtrace.breakdown(trace, [f])
    assert b["device_ops"][0][0] == "fusion.1"
    assert b["idle_gaps"][0] == ["TransferToDevice", pytest.approx(3e-3)]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def record_for(cell_name, devices, steps_per_execution=8, chips=1):
    cell = tinycell.cell_named(cell_name, chips)
    return Record(cell=cell, end_to_end={}, attempted=0, failed=0, checks=[],
                  memory_peak_bytes=0, device_kind="TPU v5 lite",
                  steps=100, devices=devices,
                  steps_per_execution=steps_per_execution)


def test_training_reducers_read_the_known_figures():
    f = xtrace.figures(chunk_device(), by_program=True)
    run = record_for("dbn-baidu.train-dp4", [f, f, f, f], chips=4)
    cell = run.cell
    assert cell.metric("train.device_idle_share").reduce(run) == \
        pytest.approx(100 * (1 - 16 / 22))
    # 2 executions x 8 steps
    assert cell.metric("train.device_ms_per_step").reduce(run) == \
        pytest.approx(16 / 16)
    assert cell.metric("train.collective_ms_per_step").reduce(run) == \
        pytest.approx(6 / 16)
    assert any("collectives" in n for n in run.notes)
    flops, nbytes = cell.work().least_work(cell.config)
    least = nbytes / (4 * 819e9)
    assert cell.metric("train.step_mfu").reduce(run) == \
        pytest.approx(100 * least / (22e-3 / 16))


def test_spans_reducers():
    run = record_for("dbn-baidu.train", [])
    run.spans = [{"name": "shard_read", "start": 0.0, "end": 0.02, "tags": {}},
                 {"name": "shard_read", "start": 1.0, "end": 1.03, "tags": {}},
                 {"name": "other", "start": 0.0, "end": 5.0, "tags": {}}]
    assert run.cell.metric("train.shard_read_ms_per_step").reduce(run) == \
        pytest.approx(50 / 100)
    serve = record_for("dbn-baidu.serve-poisson", [], 0)
    serve.spans = [{"name": "serve_batch", "start": 0.0, "end": 0.010,
                    "tags": {"n": 3, "bucket": 4}},
                   {"name": "serve_batch", "start": 1.0, "end": 1.020,
                    "tags": {"n": 200, "bucket": 256}}]
    cell = serve.cell
    assert cell.metric("serve.batch_ms").reduce(serve) == pytest.approx(15.0)
    assert cell.metric("serve.batch_occupancy").reduce(serve) == \
        pytest.approx(100 * 203 / 260)


def test_reducers_with_nothing_to_read_return_nothing():
    # every reducer of the driver, entered in BENCHMARK.json or prepared
    for name, driver in (("dbn-baidu.train-dp4", "train"),
                         ("dbn-baidu.serve-poisson", "serve")):
        run = record_for(name, [], chips=4 if name.endswith("dp4") else 1)
        run.steps = 0
        reducers = sorted((BENCH / "metrics").glob(f"{driver}.*.py"))
        assert reducers
        for path in reducers:
            assert run.cell.metric(path.stem).reduce(run) is None, path.stem


def test_a_loop_that_spans_its_operations_is_not_counted():
    ops = [(0, 10 * MS, "%while.3 = (s32[]) while"),
           (0, 4 * MS, "%fusion.1 = f32[8]"),
           (6 * MS, 9 * MS, "%fusion.2 = f32[8]"),
           (20 * MS, 22 * MS, "%copy.1 = f32[8]")]
    kept = xtrace.leaf_ops(ops)
    assert [n for _, _, n in kept] == ["%fusion.1 = f32[8]",
                                       "%fusion.2 = f32[8]",
                                       "%copy.1 = f32[8]"]
    assert xtrace.short_name("%fusion.3 = f32[2560]{0:T(1024)} fusion(x), "
                             "kind=kLoop") == "%fusion.3 = f32[2560]{0:T(1024)}"
