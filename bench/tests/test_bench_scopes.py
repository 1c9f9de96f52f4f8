"""The scope and span reducers (``harness/scopes.py`` and the five training
metrics that read it) on synthetic input whose figures are known, and on a
small scoped trace recorded on one v5e chip (``traces/``)."""
import gzip
import json

import pytest

from test_bench_reducers import MS, chunk_device, record_for
from tinycell import BENCH
from harness import scopes, xtrace

CHUNK = "jit(_chunk_step)/while/body/closed_call/"


@pytest.mark.parametrize("op_name,scope", [
    (CHUNK + "update/add:", "update"),
    (CHUNK + "jvp(embed)/jit(_take)/gather:", "embed"),
    (CHUNK + "transpose(jvp(embed))/jit(_take)/scatter-add:", "embed"),
    (CHUNK + "jvp(loss)/examination_nll/pallas_call:", "loss"),
    (CHUNK + "transpose(jvp(loss))/loss/jvp()/div:", "loss"),
    (CHUNK + "jvp()/mul:", None),
    ("jit(_chunk_step)/while/body/dynamic_slice:", None),
    ("gather:", None),
    (CHUNK + "embedding_bag/pallas_call:", None),
])
def test_scope_of_reads_the_outermost_named_scope(op_name, scope):
    assert scopes.scope_of(op_name) == scope


def test_an_op_without_op_name_takes_its_readers_scope():
    relayout = "%reduce.9 = f32[64]{0} reduce(f32[64,1]{0,1} %param.1)"
    gather = ("%fusion.35 = f32[8]{0} fusion(f32[64]{0} %reduce.9, "
              "s32[8]{0} %copy.2)")
    copy = "%copy.2 = s32[8]{0} copy(s32[8]{0} %param.2)"
    scale = "%multiply.4 = f32[8]{0} multiply(f32[8]{0} %fusion.35)"
    update = "%add.5 = f32[64]{0} add(f32[64]{0} %reduce.9)"
    got = scopes.inherit({relayout: "", gather: CHUNK + "jvp(embed)/gather:",
                          copy: "", scale: CHUNK + "jvp()/mul:"})
    assert got == {relayout: "embed", gather: "embed", copy: "embed",
                   scale: None}
    # read under two scopes, or carrying an op_name of its own: no scope
    got = scopes.inherit({relayout: "", gather: CHUNK + "jvp(embed)/gather:",
                          update: CHUNK + "update/add:",
                          copy: CHUNK + "jvp()/copy:"})
    assert got[relayout] is None and got[copy] is None


def scoped_run(monkeypatch, spans=()):
    """The synthetic chunk device, its ops named into scopes: fusion.1
    (4 ms an execution) is ``update``, all-reduce.7 (3 ms, 1 ms of it under
    fusion.1) is ``embed``'s backward, fusion.2 (2 ms) has no scope."""
    dev = chunk_device()
    ops = {"fusion.1": CHUNK + "update/add:",
           "all-reduce.7": CHUNK + "transpose(jvp(embed))/psum:",
           "fusion.2": CHUNK + "jvp()/mul:"}
    monkeypatch.setattr(scopes, "op_names", lambda path: {
        dev.name: {(0, name): op for name, op in ops.items()}})
    # The train loop's host line, by start: a drain over the first
    # execution's [6, 7) ms gap; a drain over its [9, 12) ms gap with a
    # dispatch that overlaps that gap by 1 ms; a drain over the second
    # execution's [6, 7) ms gap; nothing over its [9, 10) ms gap.
    host = [(18 * MS, 19 * MS, "drain"), (20 * MS, 25 * MS, "drain"),
            (21 * MS, 22 * MS, "dispatch"), (30 * MS, 31 * MS, "drain")]
    monkeypatch.setattr(scopes, "program_spans", lambda path, names: host)
    f = xtrace.figures(dev, by_program=True)
    run = record_for("dbn-baidu.train", [f])
    run.trace = xtrace.Trace(devices=[dev], host=[], path="synthetic")
    run.spans = list(spans)
    return run


def test_scope_reducers_split_the_device_time(monkeypatch):
    compile_span = {"name": "compile", "start": 1.0, "end": 1.5,
                    "tags": {"stage": "backend", "fun": "jit(f)"}}
    run = scoped_run(monkeypatch, spans=[compile_span])
    cell = run.cell
    # two executions x 8 steps
    assert cell.metric("train.update_ms_per_step").reduce(run) == \
        pytest.approx(2 * 4 / 16)
    assert cell.metric("train.embed_ms_per_step").reduce(run) == \
        pytest.approx(2 * 3 / 16)
    assert cell.metric("train.loss_ms_per_step").reduce(run) == 0.0
    notes = "\n".join(run.notes)
    assert f"scopes: {1e3 * 2 * 2e-3 / 16!r} ms per step" in notes
    # gaps [18, 19), [21, 24), [30, 31), [33, 34) ms: the [21, 24) gap lies
    # in a drain and overlaps the dispatch by 1 ms only, so goes to drain
    assert "idle in the traced window: 6.0 ms" in notes
    assert "innermost span: drain 5.0 ms, no program span 1.0 ms" in notes
    assert "compile spans in the window: 1; backend jit(f)" in notes
    # the trace is read, and the notes written, once
    cell.metric("train.update_ms_per_step").reduce(run)
    assert len(run.notes) == 3


def test_scope_reducers_without_scopes_read_nothing(monkeypatch):
    run = scoped_run(monkeypatch)
    monkeypatch.setattr(scopes, "op_names", lambda path: {})
    for name in ("update", "embed", "loss"):
        assert run.cell.metric(f"train.{name}_ms_per_step").reduce(run) is None
    assert run.notes == []


def test_data_span_reducers():
    run = record_for("dbn-baidu.train", [])
    run.spans = [{"name": "batch_wait", "start": 0.0, "end": 0.002, "tags": {}},
                 {"name": "batch_wait", "start": 1.0, "end": 1.001, "tags": {}},
                 {"name": "stage", "start": 0.5, "end": 0.56,
                  "tags": {"n": 8}}]
    assert run.cell.metric("train.batch_wait_ms_per_step").reduce(run) == \
        pytest.approx(3 / 100)
    assert run.cell.metric("train.stage_ms_per_step").reduce(run) == \
        pytest.approx(60 / 100)
    # a program without these spans reports nothing, not zero
    run.spans = run.spans_named("shard_read")
    assert run.cell.metric("train.batch_wait_ms_per_step").reduce(run) is None
    assert run.cell.metric("train.stage_ms_per_step").reduce(run) is None


@pytest.mark.parametrize("cell_name", ["dbn-baidu.train", "dctr-baidu.train"])
def test_reducers_on_a_small_scoped_v5e_trace(cell_name, tmp_path):
    """A traced run of the training driver at tiny size on one v5e chip
    (``tinycell`` sizes, 2 steps a dispatch), kept in ``traces/``."""
    here = BENCH / "tests" / "traces"
    profile = tmp_path / "plugins" / "profile" / "run"
    profile.mkdir(parents=True)
    with gzip.open(here / f"{cell_name}.xplane.pb.gz", "rb") as f:
        (profile / "v5e.xplane.pb").write_bytes(f.read())
    with gzip.open(here / f"{cell_name}.record.json.gz", "rt") as f:
        kept = json.load(f)
    trace = xtrace.load(str(tmp_path))
    run = record_for(cell_name, [xtrace.figures(d, True)
                                 for d in trace.devices[:1]],
                     steps_per_execution=kept["steps_per_execution"])
    run.trace, run.steps, run.spans = trace, kept["steps"], kept["spans"]
    got = {m["name"]: run.cell.metric(m["name"]).reduce(run)
           for m in run.cell.per_layer}
    assert None not in got.values(), got
    scoped = sum(got[f"train.{s}_ms_per_step"]
                 for s in ("embed", "loss", "update"))
    assert 0.8 * got["train.device_ms_per_step"] < scoped <= \
        got["train.device_ms_per_step"] + 1e-9
    assert all(got[f"train.{s}_ms_per_step"] > 0
               for s in ("embed", "loss", "update"))
    assert got["train.batch_wait_ms_per_step"] > 0
    assert got["train.stage_ms_per_step"] > 0
    notes = "\n".join(run.notes)
    assert "by the train loop's innermost span: " in notes
    assert "compile spans in the window: 0" in notes
