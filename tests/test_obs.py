"""Observability suite: zero-sync telemetry, spans, sinks, profiler hooks.

The hard guarantees pinned here:

* enabling engine telemetry adds ZERO extra host syncs per step (the
  telemetry rides the loss drain — exactly one ``jax.device_get`` per
  chunk either way), never retraces the compiled chunk, and leaves params
  bit-identical to a telemetry-off run;
* the trainer's per-epoch loss/skip bookkeeping is a derived view over the
  telemetry stream (``TelemetryDrain``), with the historical bit-exact
  python-float accumulation semantics (crash-exact resume stays green);
* replica-tagged events reproduce the per-replica history, and a poisoned
  replica is the only one that emits ``skipped_step`` events;
* the data plane's spans/counters/events flow through the same recorder,
  including from the read-ahead producer thread.
"""
import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim
from repro.core import PositionBasedModel
from repro.data import (ClickLogLoader, DevicePrefetcher, SessionStore,
                        StreamingClickLogLoader, SyntheticConfig,
                        generate_click_log, write_session_store)
from repro.obs import (EVENT_KINDS, ConsoleReporter, JsonlSink, MemorySink,
                       ProfileWindow, Recorder, SpanTracer, TelemetryDrain,
                       make_event, parse_profile_steps, read_jsonl,
                       validate_event)
from repro.testing import (FlakyShardReads, NonFiniteBatchInjector,
                           corrupt_shard_file)
from repro.train import StepWatchdog, Trainer, TrainEngine


# -- fixtures -----------------------------------------------------------------
@pytest.fixture(scope="module")
def small_log():
    cfg = SyntheticConfig(n_sessions=600, n_queries=20, docs_per_query=10,
                          positions=5, behavior="pbm", seed=11)
    data, _ = generate_click_log(cfg)
    return cfg, data


def _model(cfg):
    return PositionBasedModel(query_doc_pairs=cfg.n_query_doc_pairs,
                              positions=cfg.positions)


def _chunk(data, batch_size=64, n=4, seed=5, poison_step=None):
    batches = [b for b in iter(ClickLogLoader(data, batch_size=batch_size,
                                              seed=seed))][:n]
    if poison_step is not None:
        poisoned = dict(batches[poison_step])
        poisoned["clicks"] = np.full_like(poisoned["clicks"], np.nan)
        batches[poison_step] = poisoned
    return {k: np.stack([b[k] for b in batches]) for k in batches[0]}


# -- events and sinks ---------------------------------------------------------
def test_make_event_schema_roundtrip():
    e = make_event("metric", "train_step", np.float32(0.5), step=np.int64(3),
                   epoch=1, replica=0, data={"grad_norm": 0.1}, shard=2)
    validate_event(e)
    assert e["value"] == 0.5 and isinstance(e["value"], float)
    assert e["step"] == 3 and isinstance(e["step"], int)
    assert e["tags"] == {"shard": 2}
    json.dumps(e)  # JSON-able end to end


def test_validate_event_rejects_malformed():
    with pytest.raises(ValueError, match="missing required"):
        validate_event({"kind": "metric", "name": "x"})
    with pytest.raises(ValueError, match="unknown event kind"):
        validate_event(make_event("metric", "x") | {"kind": "nope"})
    with pytest.raises(ValueError, match="must be a dict"):
        validate_event(make_event("metric", "x") | {"data": [1]})
    with pytest.raises(ValueError, match="must be an int"):
        validate_event(make_event("metric", "x") | {"step": 1.5})
    assert "metric" in EVENT_KINDS and "span" in EVENT_KINDS


def test_memory_sink_queries():
    s = MemorySink()
    s.emit(make_event("metric", "loss", 1.0, step=0, replica=0))
    s.emit(make_event("metric", "loss", 2.0, step=1, replica=1))
    s.emit(make_event("event", "quarantine"))
    assert len(s) == 3
    assert s.series("loss") == [1.0, 2.0]
    assert s.series("loss", replica=1) == [2.0]
    assert [e["name"] for e in s.by_kind("event")] == ["quarantine"]


def test_jsonl_sink_roundtrip(tmp_path):
    path = str(tmp_path / "m.jsonl")
    sink = JsonlSink(path, flush_every=2)
    for i in range(5):
        sink.emit(make_event("metric", "loss", float(i), step=i))
    sink.close()
    events = read_jsonl(path)  # validates every line
    assert [e["value"] for e in events] == [0.0, 1.0, 2.0, 3.0, 4.0]
    # late emit after close (daemon reader thread) must not raise
    sink.emit(make_event("metric", "loss", 9.0))
    assert len(read_jsonl(path)) == 5


def test_console_reporter_rate_limits_metrics():
    lines = []
    rep = ConsoleReporter(log_fn=lines.append, every=10)
    for i in range(25):
        rep.emit(make_event("metric", "loss", float(i), step=i))
    rep.emit(make_event("event", "quarantine", data={"shard": 1}))
    metric_lines = [l for l in lines if "metric/loss" in l]
    assert len(metric_lines) == 3  # samples 0, 10, 20
    assert any("event/quarantine" in l for l in lines)


# -- spans --------------------------------------------------------------------
def test_span_tracer_nesting_and_ring_buffer():
    tr = SpanTracer(capacity=4)
    with tr.span("outer", epoch=0):
        with tr.span("inner"):
            pass
    assert [s.name for s in tr.spans] == ["inner", "outer"]  # exit order
    assert tr.spans[-1].tags == {"epoch": 0}
    for i in range(10):
        with tr.span(f"s{i}"):
            pass
    assert len(tr.spans) == 4  # bounded: old spans fell off


def test_span_recorded_even_on_error():
    tr = SpanTracer()
    with pytest.raises(RuntimeError):
        with tr.span("doomed"):
            raise RuntimeError("boom")
    assert [s.name for s in tr.spans] == ["doomed"]


def test_chrome_trace_export(tmp_path):
    rec = Recorder()
    with rec.span("epoch", epoch=0):
        with rec.span("chunk"):
            time.sleep(0.002)
    path = str(tmp_path / "trace.json")
    n = rec.export_chrome_trace(path)
    assert n == 2
    trace = json.load(open(path))
    assert trace["displayTimeUnit"] == "ms"
    by_name = {e["name"]: e for e in trace["traceEvents"]}
    assert set(by_name) == {"epoch", "chunk"}
    for e in trace["traceEvents"]:
        assert e["ph"] == "X" and e["dur"] >= 0 and e["ts"] > 0
    assert by_name["chunk"]["dur"] >= 2000  # microseconds
    assert by_name["epoch"]["args"] == {"epoch": 0}


def test_recorder_disabled_is_noop_but_spans_still_trace():
    rec = Recorder()  # no sinks
    assert not rec.enabled
    rec.metric("loss", 1.0)  # must not raise, must not store
    with rec.span("epoch"):
        pass
    assert len(rec.tracer.spans) == 1


def test_recorder_counters_gauges_and_flush():
    sink = MemorySink()
    rec = Recorder(sinks=[sink])
    rec.add("io_retries")
    rec.add("bytes_read", 100)
    rec.add("bytes_read", 28)
    rec.gauge("queue_depth", 3)
    snap = rec.counters_snapshot()
    assert snap == {"io_retries": 1, "bytes_read": 128, "queue_depth:gauge": 3}
    rec.flush_counters(epoch=0)
    (e,) = sink.by_kind("counters")
    assert e["data"]["bytes_read"] == 128 and e["epoch"] == 0


def test_recorder_span_forwarded_to_sinks():
    sink = MemorySink()
    rec = Recorder(sinks=[sink])
    with rec.span("shard_read", shard=2):
        pass
    (e,) = sink.by_kind("span")
    assert e["name"] == "shard_read" and e["tags"] == {"shard": 2}
    assert e["value"] >= 0  # seconds


def test_process_stats_reports_host_rss():
    rec = Recorder(sinks=[MemorySink()])
    stats = rec.process_stats(epoch=1)
    assert stats["rss_bytes"] > 0
    (e,) = rec.sinks[0].by_kind("process")
    assert e["data"]["rss_bytes"] == stats["rss_bytes"]


def test_recorder_thread_safety_under_producer_emits():
    sink = MemorySink()
    rec = Recorder(sinks=[sink])

    def worker(tid):
        for i in range(200):
            rec.add("n")
            rec.metric("m", float(i), step=i, replica=tid)

    threads = [threading.Thread(target=worker, args=(t,)) for t in range(4)]
    [t.start() for t in threads]
    [t.join() for t in threads]
    assert rec.counters_snapshot()["n"] == 800
    assert len(sink.by_name("m")) == 800


# -- engine telemetry: the zero-sync / no-retrace / bit-exact pins ------------
def test_engine_telemetry_params_bit_exact_and_payload(small_log):
    cfg, data = small_log
    model = _model(cfg)
    chunk = _chunk(data)

    def run(telemetry):
        eng = TrainEngine(model, optim.adamw(0.05), chunk_batches=4,
                          telemetry=telemetry)
        params = model.init(jax.random.PRNGKey(0))
        p, _, out = eng.step(params, eng.init_opt_state(params), chunk)
        return jax.device_get(p), jax.device_get(out)

    p_off, losses = run(False)
    p_on, out = run(True)
    assert set(out) == {"loss", "grad_norm", "param_norm"}
    np.testing.assert_array_equal(np.asarray(losses), out["loss"])
    for (ka, a), (_, b) in zip(jax.tree_util.tree_leaves_with_path(p_off),
                               jax.tree_util.tree_leaves_with_path(p_on)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"telemetry changed {ka}")


def test_engine_telemetry_values_match_manual_computation(small_log):
    cfg, data = small_log
    model = _model(cfg)
    chunk = _chunk(data, n=1)
    eng = TrainEngine(model, optim.adamw(0.05), chunk_batches=1,
                      telemetry=True)
    params = model.init(jax.random.PRNGKey(0))
    opt_state = eng.init_opt_state(params)
    batch = {k: v[0] for k, v in chunk.items()}
    loss, grads = jax.value_and_grad(model.compute_loss)(params, batch)
    p2, _, out = eng.step(params, opt_state, chunk)
    out = jax.device_get(out)
    np.testing.assert_allclose(out["grad_norm"][0],
                               float(optim.global_norm(grads)), rtol=1e-6)
    np.testing.assert_allclose(out["param_norm"][0],
                               float(optim.global_norm(p2)), rtol=1e-6)
    np.testing.assert_allclose(out["loss"][0], float(loss), rtol=1e-6)


def test_engine_telemetry_lr_series_with_injected_lr(small_log):
    cfg, data = small_log
    model = _model(cfg)
    eng = TrainEngine(model, optim.adamw(0.05, inject_lr=True),
                      chunk_batches=4, telemetry=True)
    params = model.init(jax.random.PRNGKey(0))
    _, _, out = eng.step(params, eng.init_opt_state(params), _chunk(data))
    np.testing.assert_allclose(np.asarray(out["lr"]), 0.05, rtol=1e-6)


def test_engine_telemetry_never_retraces_across_chunks(small_log):
    """The trace-counter pin (same pattern as test_dispatch): a Python-side
    counter in the loss closure counts traces — jit cache hits never
    re-enter Python, so telemetry must cost exactly as many traces as the
    bare engine (one per chunk shape)."""
    cfg, data = small_log
    model = _model(cfg)
    traces = []

    def loss_fn(params, batch):
        traces.append(1)
        return model.compute_loss(params, batch)

    eng = TrainEngine(model, optim.adamw(0.05), chunk_batches=4,
                      telemetry=True, nonfinite_guard=True, loss_fn=loss_fn)
    params = model.init(jax.random.PRNGKey(0))
    opt_state = eng.init_opt_state(params)
    chunk = _chunk(data)
    params, opt_state, _ = eng.step(params, opt_state, chunk)
    n_traces = len(traces)
    assert n_traces > 0
    for _ in range(3):
        params, opt_state, out = eng.step(params, opt_state, chunk)
    assert len(traces) == n_traces  # compiled chunk never re-entered Python
    assert np.isfinite(np.asarray(out["loss"])).all()


def test_trainer_telemetry_zero_extra_host_syncs(small_log, monkeypatch):
    """Telemetry-on and telemetry-off trainer runs perform EXACTLY the same
    number of jax.device_get calls: one per chunk (the loss drain telemetry
    rides along with). Counted by wrapping jax.device_get itself."""
    cfg, data = small_log

    def run(telemetry):
        model = _model(cfg)
        loader = ClickLogLoader(data, batch_size=64, seed=5)
        trainer = Trainer(optim.adamw(0.05), epochs=2, patience=100,
                          chunk_batches=4, telemetry=telemetry,
                          recorder=Recorder(sinks=[MemorySink()]),
                          log_fn=lambda *_: None)
        calls = []
        real = jax.device_get
        monkeypatch.setattr(jax, "device_get",
                            lambda x: (calls.append(1), real(x))[1])
        try:
            trainer.train(model, loader)
        finally:
            monkeypatch.setattr(jax, "device_get", real)
        return len(calls)

    chunks_per_epoch = -(-(len(list(iter(ClickLogLoader(
        data, batch_size=64, seed=5)))) ) // 4)
    n_off, n_on = run(False), run(True)
    assert n_on == n_off == 2 * chunks_per_epoch


# -- TelemetryDrain: the single source of truth -------------------------------
def test_drain_scalar_accumulation_is_bit_exact_python_floats():
    rng = np.random.default_rng(0)
    losses = rng.normal(size=13).astype(np.float32)
    acc = TelemetryDrain()
    acc.drain(losses[:4], first_step=0)
    acc.drain(losses[4:], first_step=4)
    expected = 0.0
    for x in losses:
        expected += float(x)
    assert acc.train_loss == expected  # bitwise, not allclose
    assert acc.n_batches == 13
    assert acc.mean_loss() == expected / 13


def test_drain_aux_json_roundtrip_exact():
    acc = TelemetryDrain()
    acc.drain(np.asarray([0.1, 0.2, 0.3], np.float32))
    aux = json.loads(json.dumps(acc.aux()))
    acc2 = TelemetryDrain()
    acc2.load(aux)
    assert acc2.train_loss == acc.train_loss  # python floats round-trip json
    assert acc2.n_batches == 3 and acc2.skipped_steps == 0


def test_drain_skipped_steps_excluded_from_mean():
    acc = TelemetryDrain()
    acc.drain({"loss": np.asarray([1.0, np.nan, 3.0], np.float32),
               "skipped": np.asarray([False, True, False])})
    assert acc.skipped_steps == 1 and acc.n_batches == 3
    assert acc.mean_loss() == (1.0 + 3.0) / 2


def test_drain_replica_accumulation_and_events():
    sink = MemorySink()
    rec = Recorder(sinks=[sink])
    acc = TelemetryDrain(replicas=2, recorder=rec, epoch=0)
    loss = np.asarray([[1.0, 10.0], [2.0, np.nan]], np.float32)
    skipped = np.asarray([[False, False], [False, True]])
    acc.drain({"loss": loss, "skipped": skipped,
               "grad_norm": np.ones((2, 2), np.float32)}, first_step=0)
    np.testing.assert_array_equal(acc.train_loss, [3.0, 10.0])
    np.testing.assert_array_equal(acc.skipped_steps, [0, 1])
    np.testing.assert_array_equal(acc.mean_loss(), [1.5, 10.0])
    assert sink.series("train_step", replica=0) == [1.0, 2.0]
    skips = sink.by_name("skipped_step")
    assert [(e["step"], e["replica"]) for e in skips] == [(1, 1)]
    # extras ride in data, per replica
    assert sink.by_name("train_step")[0]["data"] == {"grad_norm": 1.0}


def test_drain_every_rate_limits_metrics_not_skips():
    sink = MemorySink()
    acc = TelemetryDrain(recorder=Recorder(sinks=[sink]), every=4)
    acc.drain({"loss": np.arange(8, dtype=np.float32),
               "skipped": np.asarray([0, 0, 1, 0, 0, 0, 0, 1], bool)},
              first_step=0)
    assert [e["step"] for e in sink.by_name("train_step")] == [0, 4]
    assert [e["step"] for e in sink.by_name("skipped_step")] == [2, 7]


# -- trainer integration ------------------------------------------------------
def test_trainer_history_is_derived_view_of_event_stream(small_log):
    """Satellite: per-epoch train_loss is exactly the mean of the per-step
    telemetry events — one source of truth, no double bookkeeping."""
    cfg, data = small_log
    model = _model(cfg)
    sink = MemorySink()
    trainer = Trainer(optim.adamw(0.05), epochs=2, patience=100,
                      chunk_batches=4, telemetry=True,
                      recorder=Recorder(sinks=[sink]),
                      log_fn=lambda *_: None)
    history = trainer.train(model, ClickLogLoader(data, batch_size=64, seed=5))
    for epoch, record in enumerate(history):
        vals = [e["value"] for e in sink.by_name("train_step")
                if e["epoch"] == epoch]
        assert len(vals) == 9  # 600 sessions * 64 batch
        assert abs(np.mean(vals) - record["train_loss"]) < 1e-9
        # per-step events carry the on-device norm series
        datas = [e["data"] for e in sink.by_name("train_step")
                 if e["epoch"] == epoch]
        assert all(d["grad_norm"] > 0 and d["param_norm"] > 0 for d in datas)
    epochs = sink.by_kind("epoch")
    assert [e["data"]["train_loss"] for e in epochs] == \
        [r["train_loss"] for r in history]
    assert len(sink.by_kind("process")) == 2  # one per epoch


def test_trainer_replica_events_match_history(small_log):
    """Satellite: replica-tagged events from a vmapped 4-way sweep reproduce
    each replica's loss history to <= 1e-5."""
    cfg, data = small_log
    model = _model(cfg)
    sink = MemorySink()
    lrs = [0.01, 0.02, 0.05, 0.1]
    trainer = Trainer(optim.adamw(0.05, inject_lr=True), epochs=1,
                      patience=100, replicas=4, replica_lrs=lrs,
                      chunk_batches=3, telemetry=True,
                      recorder=Recorder(sinks=[sink]),
                      log_fn=lambda *_: None)
    history = trainer.train(model, ClickLogLoader(data, batch_size=64, seed=5))
    for r in range(4):
        series = sink.series("train_step", replica=r)
        assert len(series) == 9
        assert abs(np.mean(series) - history[0]["train_loss"][r]) <= 1e-5
        # each replica's events carry its own injected lr
        lr_seen = {e["data"]["lr"] for e in sink.by_name("train_step")
                   if e["replica"] == r}
        assert len(lr_seen) == 1
        assert abs(lr_seen.pop() - lrs[r]) < 1e-6
    # distinct lrs -> distinct trajectories in the event stream too
    assert sink.series("train_step", replica=0) != \
        sink.series("train_step", replica=1)


def test_trainer_broadcast_poison_tags_every_replica(small_log):
    """A NonFiniteBatchInjector batch is broadcast to all replicas: each one
    skips it and each emits its own replica-tagged skipped event."""
    cfg, data = small_log
    model = _model(cfg)
    sink = MemorySink()
    loader = NonFiniteBatchInjector(
        ClickLogLoader(data, batch_size=64, seed=5), at_steps=[2])
    trainer = Trainer(optim.adamw(0.05), epochs=1, patience=100, replicas=2,
                      chunk_batches=3, nonfinite_guard=True,
                      recorder=Recorder(sinks=[sink]),
                      log_fn=lambda *_: None)
    history = trainer.train(model, loader)
    assert history[0]["skipped_steps"] == [1, 1]
    skips = sink.by_name("skipped_step")
    assert sorted((e["step"], e["replica"]) for e in skips) == \
        [(2, 0), (2, 1)]


def test_only_poisoned_replica_emits_skipped_events(small_log):
    """One replica's params poisoned with NaN: its every step skips (its own
    loss is non-finite), the healthy replica's never do — the in-memory sink
    sees skipped events only from the poisoned replica."""
    cfg, data = small_log
    model = _model(cfg)
    eng = TrainEngine(model, optim.adamw(0.05), chunk_batches=4, replicas=2,
                      nonfinite_guard=True)
    params = eng.init_replica_params([0, 1])
    # poison replica 1's params wholesale
    params = jax.tree_util.tree_map(
        lambda x: np.asarray(x).copy(), params)
    for leaf in jax.tree_util.tree_leaves(params):
        leaf[1] = np.nan
    params = jax.tree_util.tree_map(jnp.asarray, params)
    opt_state = eng.init_opt_state(params)
    sink = MemorySink()
    acc = TelemetryDrain(replicas=2, recorder=Recorder(sinks=[sink]))
    _, _, out = eng.step(params, opt_state, _chunk(data))
    acc.drain(out, first_step=0)
    np.testing.assert_array_equal(acc.skipped_steps, [0, 4])
    assert {e["replica"] for e in sink.by_name("skipped_step")} == {1}
    assert len(sink.by_name("skipped_step")) == 4


def test_trainer_scalar_skip_events_at_poisoned_steps(small_log):
    cfg, data = small_log
    model = _model(cfg)
    sink = MemorySink()
    loader = NonFiniteBatchInjector(
        ClickLogLoader(data, batch_size=64, seed=5), at_steps=[2, 7])
    trainer = Trainer(optim.adamw(0.05), epochs=1, patience=100,
                      chunk_batches=3, nonfinite_guard=True,
                      recorder=Recorder(sinks=[sink]),
                      log_fn=lambda *_: None)
    history = trainer.train(model, loader)
    assert history[0]["skipped_steps"] == 2
    assert [e["step"] for e in sink.by_name("skipped_step")] == [2, 7]


def test_trainer_emits_spans_and_roofline(small_log):
    # The roofline event (a second compile for an HLO cost estimate) is
    # gone; the trainer's spans remain.
    cfg, data = small_log
    model = _model(cfg)
    sink = MemorySink()
    rec = Recorder(sinks=[sink])
    trainer = Trainer(optim.adamw(0.05), epochs=1, patience=100,
                      chunk_batches=4, recorder=rec,
                      log_fn=lambda *_: None)
    trainer.train(model, ClickLogLoader(data, batch_size=64, seed=5),
                  ClickLogLoader(data, batch_size=256, shuffle=False,
                                 drop_last=False))
    span_names = {e["name"] for e in sink.by_kind("span")}
    assert {"epoch", "eval", "dispatch", "drain", "batch_wait",
            "stage"} <= span_names
    assert "roofline" not in EVENT_KINDS
    assert not any(e["kind"] == "roofline" for e in sink.events)


def test_trainer_spans_once_per_chunk(small_log):
    cfg, data = small_log
    sink = MemorySink()
    trainer = Trainer(optim.adamw(0.05), epochs=1, patience=100,
                      chunk_batches=3, recorder=Recorder(sinks=[sink]),
                      log_fn=lambda *_: None)
    trainer.train(_model(cfg), ClickLogLoader(data, batch_size=64, seed=5))
    chunks = 3   # 600 sessions: 9 batches of 64

    def spans(name):
        return [e for e in sink.by_kind("span") if e["name"] == name]

    dispatch = spans("dispatch")
    assert len(dispatch) == chunks
    # a step annotation: tagged with each chunk's first global step
    assert [e["tags"]["step_num"] for e in dispatch] == [0, 3, 6]
    assert [e["step"] for e in spans("drain")] == [0, 3, 6]
    assert [e["tags"]["n"] for e in spans("stage")] == [3] * chunks
    # one wait per chunk, and the last for the end of the epoch
    assert len(spans("batch_wait")) == chunks + 1


@pytest.mark.parametrize("model_name", ["dbn", "dctr"])
def test_chunk_step_ops_carry_their_scopes(model_name):
    import re

    from repro.core import (MODEL_REGISTRY, Compression,
                            EmbeddingParameterConfig)

    table = EmbeddingParameterConfig(
        parameters=1 << 16, compression=Compression.HASH,
        compression_ratio=10.0, baseline_correction=True, init_logit=-2.0)
    tables = ({"attraction": table, "satisfaction": table}
              if model_name == "dbn" else {"attraction": table})
    model = MODEL_REGISTRY[model_name](positions=10, **tables)
    engine = TrainEngine(model, optim.adamw(0.003), chunk_batches=2)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    shape = (2, 64, 10)
    chunk = {"positions": np.broadcast_to(np.arange(1, 11, dtype=np.int32),
                                          shape).copy(),
             "query_doc_ids": rng.integers(0, 1 << 20, shape,
                                           dtype=np.int32),
             "clicks": rng.integers(0, 2, shape).astype(np.int32),
             "mask": np.ones(shape, bool)}
    text = engine.compiled(params, engine.init_opt_state(params),
                           chunk).as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))

    def has(pattern):
        return any(re.search(pattern, n) for n in names)

    assert has(r"/jvp\(embed\)/")                  # hash, gather
    assert has(r"/transpose\(jvp\(embed\)\)/")     # gradient scatter
    assert has(r"/jvp\(loss\)/")                   # fused loss forward
    assert has(r"/transpose\(jvp\(loss\)\)/loss/")  # its custom backward
    assert has(r"/update/")                         # AdamW


def test_recorder_span_lands_on_the_profiler_host_plane(tmp_path):
    rec = Recorder()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with rec.span("shard_read", shard=7):
            jnp.ones(4).block_until_ready()
        with rec.span("dispatch", step_num=12):
            pass
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(str(path))
    found = {}
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in ("shard_read", "dispatch"):
                        found[e.name] = (e.duration_ns, dict(e.stats))
    assert set(found) == {"shard_read", "dispatch"}
    assert str(found["shard_read"][1]["shard"]) == "7"
    assert str(found["dispatch"][1]["step_num"]) == "12"
    (span,) = [s for s in rec.tracer.spans if s.name == "shard_read"]
    # the annotation encloses the span's own timing
    assert found["shard_read"][0] >= span.duration * 1e9


def test_importing_obs_leaves_jax_unimported():
    import os
    import subprocess
    import sys

    code = ("import sys, repro.obs, repro.data\n"
            "assert 'jax' not in sys.modules, 'jax imported'\n"
            "with repro.obs.span('shard_read', shard=1):\n"
            "    pass\n"
            "assert 'jax' not in sys.modules, 'span imported jax'\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr


def test_fresh_jit_emits_compile_span_and_counters():
    from repro.obs import get_recorder, set_recorder, watch_compiles

    def triple_plus_one(x):
        return x * 3.0 + 1.0

    x = jnp.arange(7.0).block_until_ready()
    sink = MemorySink()
    rec = Recorder(sinks=[sink])
    previous = get_recorder()
    set_recorder(rec)
    try:
        watch_compiles()
        watch_compiles()   # idempotent: one listener per process
        t0 = time.time()
        jax.jit(triple_plus_one)(x).block_until_ready()
    finally:
        set_recorder(previous)
    compiles = sink.by_name("compile", kind="span")
    ours = [e["tags"]["stage"] for e in compiles
            if "triple_plus_one" in e["tags"]["fun"]]
    assert sorted(ours) == ["backend", "lower", "trace"]
    backends = [e for e in compiles if e["tags"]["stage"] == "backend"]
    assert rec.counters["compile.count"] == len(backends) >= 1
    assert rec.counters["compile.seconds"] == pytest.approx(
        sum(e["value"] for e in compiles))
    assert all(e["t"] >= t0 - 1e-3 for e in compiles)


# -- watchdog + profiler hooks ------------------------------------------------
def test_watchdog_violation_emits_event():
    sink = MemorySink()
    wd = StepWatchdog(0.01, recorder=Recorder(sinks=[sink]))
    wd.check(0.005, step=4)   # within budget
    wd.check(0.5, step=8)     # violation
    assert wd.violations == 1
    (e,) = sink.by_name("watchdog_violation")
    assert e["step"] == 8 and e["value"] == 0.5
    assert e["data"]["budget_seconds"] == 0.01


def test_parse_profile_steps():
    assert parse_profile_steps("10:20") == (10, 20)
    for bad in ("10", "20:10", "a:b", "-1:5"):
        with pytest.raises(ValueError):
            parse_profile_steps(bad)


def test_profile_window_opens_and_closes_on_chunk_boundaries(monkeypatch):
    calls = []
    import jax.profiler

    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: calls.append(("start", d)))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append(("stop",)))
    sink = MemorySink()
    pw = ProfileWindow(8, 16, log_dir="prof",
                       recorder=Recorder(sinks=[sink]))
    pw.before_chunk(0)
    pw.after_chunk(4)
    assert calls == []          # window not reached
    pw.before_chunk(8)
    assert calls == [("start", "prof")]
    pw.after_chunk(12)          # inside the window: stays open
    pw.before_chunk(12)         # idempotent while active
    pw.after_chunk(16)
    assert calls == [("start", "prof"), ("stop",)]
    pw.before_chunk(20)         # window done: never reopens
    assert calls == [("start", "prof"), ("stop",)]
    names = [e["name"] for e in sink.by_kind("event")]
    assert names == ["profile_start", "profile_stop"]
    assert sink.by_name("profile_start")[0]["step"] == 8


def test_profile_window_close_flushes_open_trace(monkeypatch):
    calls = []
    import jax.profiler

    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: calls.append("start"))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append("stop"))
    pw = ProfileWindow(0, 100, log_dir="prof", recorder=Recorder())
    pw.before_chunk(0)
    pw.close(8)  # training ended inside the window
    assert calls == ["start", "stop"]
    pw.close(8)  # idempotent
    assert calls == ["start", "stop"]


def test_profile_window_raises_when_trace_cannot_start(monkeypatch):
    import jax.profiler

    def refuse(_):
        raise RuntimeError("no profiler")

    monkeypatch.setattr(jax.profiler, "start_trace", refuse)
    sink = MemorySink()
    pw = ProfileWindow(0, 8, log_dir="prof", recorder=Recorder(sinks=[sink]))
    with pytest.raises(RuntimeError, match="no profiler"):
        pw.before_chunk(0)
    assert not pw.active
    pw.close(8)  # nothing open: no stop_trace call, no event
    assert sink.by_kind("event") == []


# -- streaming data plane -----------------------------------------------------
@pytest.fixture()
def store_dir(tmp_path, small_log):
    cfg, data = small_log
    d = str(tmp_path / "store")
    write_session_store(data, d, shard_rows=150)  # 4 shards
    return d


def test_streaming_emits_spans_and_counters(store_dir):
    sink = MemorySink()
    rec = Recorder(sinks=[sink])
    loader = StreamingClickLogLoader(store_dir, batch_size=50, seed=3,
                                     verify_checksums=True, recorder=rec)
    n = len(list(iter(loader)))
    assert n == loader.batches_per_epoch
    reads = sink.by_name("shard_read", kind="span")
    assert len(reads) == 4  # every shard read exactly once
    assert {e["tags"]["shard"] for e in reads} == {0, 1, 2, 3}
    assert len(sink.by_name("crc_verify", kind="span")) == 4
    snap = rec.counters_snapshot()
    assert snap["stream.bytes_read"] > 0
    assert snap["stream.sessions"] == n * 50
    assert snap["stream.queue_stall_s"] >= 0
    assert "stream.queue_depth:gauge" in snap


def test_streaming_io_retry_telemetry(store_dir):
    sink = MemorySink()
    rec = Recorder(sinks=[sink])
    store = FlakyShardReads(SessionStore(store_dir), fail_times=2)
    loader = StreamingClickLogLoader(store, batch_size=50, seed=3,
                                     io_retries=3, io_retry_backoff=0.001,
                                     recorder=rec, log_fn=lambda *_: None)
    assert len(list(iter(loader))) == loader.batches_per_epoch
    assert rec.counters_snapshot()["stream.io_retries"] == 2
    waits = sink.by_name("io_retry_wait", kind="span")
    assert [e["tags"]["attempt"] for e in waits] == [1, 2]


def test_streaming_quarantine_event(store_dir):
    corrupt_shard_file(store_dir, shard=1, column="clicks", seed=1)
    sink = MemorySink()
    rec = Recorder(sinks=[sink])
    loader = StreamingClickLogLoader(store_dir, batch_size=50, seed=3,
                                     verify_checksums=True,
                                     corrupt_policy="skip", recorder=rec,
                                     log_fn=lambda *_: None)
    list(iter(loader))
    (e,) = sink.by_name("quarantine")
    assert e["data"]["shard"] == 1
    assert rec.counters_snapshot()["stream.quarantined_shards"] == 1


def test_streaming_watchdog_restart_event(store_dir):
    # io_retries=0: the producer dies on the first flaky open; the consumer
    # watchdog restarts it and the event records the restart, not the death
    sink = MemorySink()
    rec = Recorder(sinks=[sink])
    store = FlakyShardReads(SessionStore(store_dir), fail_times=1)
    loader = StreamingClickLogLoader(store, batch_size=50, seed=3,
                                     io_retries=0, watchdog_restarts=1,
                                     recorder=rec, log_fn=lambda *_: None)
    assert len(list(iter(loader))) == loader.batches_per_epoch
    (e,) = sink.by_name("watchdog_restart")
    assert "OSError" in e["data"]["error"]
    assert rec.counters_snapshot()["stream.watchdog_restarts"] == 1
