"""Serving-engine suite: admission, batching, degradation, chaos drills.

Every guarantee the serving engine advertises is pinned here:

* exactly one result per submitted request — under overload, poison
  floods, injected model failures, and SIGTERM drain;
* a poisoned request is rejected alone; its batch-mates are answered;
* traffic never compiles after warmup (trace-counter equality);
* breakers trip to the degraded ladder and recover half-open;
* the full chaos drill (slow model + poison + mid-flight SIGTERM) is
  bit-deterministic across seeded virtual-clock runs.
"""
import signal
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import MODEL_REGISTRY
from repro.serve import (ADMIT, ADMIT_BACKPRESSURE, CLOSED, HALF_OPEN, OPEN,
                         SHED_OVERLOAD, SHED_QUEUE_FULL, TIERS,
                         AdmissionQueue, CircuitBreaker, DeadlineBatcher,
                         DegradationLadder, ModelRegistry, ServeEngine,
                         ServeRequest, ServiceModel, VirtualClock,
                         make_request, poisson_trace, validate_request)
from repro.testing import (POISON_MODES, PoisonTrace, ServeKillSwitch,
                          SlowModel, poison_request)

N_PAIRS = 500
K = 10
BUCKETS = (1, 4, 16)
MODELS = ("pbm", "dbn")


def _perturbed_params(model, seed=0):
    """Fresh-init params are constant per leaf (quantization would be
    exact); perturb so the int8 tier has a real error to measure."""
    params = model.init(jax.random.PRNGKey(seed))
    leaves, treedef = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    out = [l + 0.5 * jax.random.normal(k, l.shape, l.dtype)
           if jnp.issubdtype(l.dtype, jnp.floating) else l
           for l, k in zip(leaves, keys)]
    return jax.tree_util.tree_unflatten(treedef, out)


@pytest.fixture(scope="module")
def registry():
    reg = ModelRegistry(buckets=BUCKETS, service_model=ServiceModel())
    for name in MODELS:
        model = MODEL_REGISTRY[name](query_doc_pairs=N_PAIRS, positions=K)
        reg.add(name, model, _perturbed_params(model), n_pairs=N_PAIRS,
                quantize_min_size=64)
    reg.warmup()
    return reg


def _engine(registry, **kw):
    kw.setdefault("clock", VirtualClock())
    return ServeEngine(registry, **kw)


def _req(request_id=0, model="pbm", deadline_s=0.2, arrival_s=0.0, seed=0):
    return make_request(request_id, model, K, np.random.default_rng(seed),
                        N_PAIRS, deadline_s=deadline_s, arrival_s=arrival_s)


def _trace(n, qps=300.0, deadline_s=0.05, seed=1, models=MODELS):
    return poisson_trace(n, qps=qps, models=list(models), positions_k=K,
                         n_pairs=N_PAIRS, deadline_s=deadline_s, seed=seed)


def _signature(results):
    return [(r.request_id, r.status, r.tier, r.reason) for r in results]


# -- validation ---------------------------------------------------------------
def test_validator_accepts_wellformed():
    assert validate_request(_req(), positions=K, n_pairs=N_PAIRS) is None


def test_validator_rejects_every_poison_mode():
    for i, mode in enumerate(POISON_MODES):
        bad = poison_request(_req(seed=i), mode, seed=i)
        reason = validate_request(bad, positions=K, n_pairs=N_PAIRS)
        assert reason is not None, f"mode {mode} slipped through"
        assert isinstance(reason, str)


def test_validator_feature_dim_contract():
    req = _req()
    # model expects features but the request has none
    assert validate_request(req, positions=K, n_pairs=N_PAIRS,
                            feature_dim=4) is not None
    req.features = np.zeros((K, 4), np.float32)
    assert validate_request(req, positions=K, n_pairs=N_PAIRS,
                            feature_dim=4) is None
    req.features = np.zeros((K, 3), np.float32)
    assert validate_request(req, positions=K, n_pairs=N_PAIRS,
                            feature_dim=4) is not None


@settings(max_examples=60)
@given(st.lists(st.integers(min_value=-10 ** 12, max_value=10 ** 12),
                min_size=0, max_size=2 * K),
       st.floats(min_value=-1e6, max_value=1e6),
       st.integers(min_value=0, max_value=len(POISON_MODES) - 1))
def test_validator_fuzz_total_function(ids, deadline, mode_i):
    """The validator is total: arbitrary junk ids/deadlines and every
    poison mode yield a reason-or-None, never an exception."""
    req = _req()
    req.query_doc_ids = np.asarray(ids)
    req.deadline_s = deadline
    out = validate_request(req, positions=K, n_pairs=N_PAIRS)
    assert out is None or isinstance(out, str)
    if len(ids) != K:
        assert out is not None
    elif any(i < 0 or i >= N_PAIRS for i in ids):
        assert out is not None
    mutated = poison_request(_req(), POISON_MODES[mode_i], seed=abs(int(
        deadline)) % 997)
    out2 = validate_request(mutated, positions=K, n_pairs=N_PAIRS)
    assert out2 is not None and isinstance(out2, str)


# -- admission queue ----------------------------------------------------------
def test_queue_watermark_ladder():
    q = AdmissionQueue(capacity=8, shed_watermark=6, backpressure_watermark=4)
    outcomes = [q.offer(_req(i), now=0.0) for i in range(8)]
    assert outcomes == [ADMIT] * 4 + [ADMIT_BACKPRESSURE] * 2 + \
        [SHED_OVERLOAD] * 2
    assert q.depth == 6  # sheds were not enqueued


def test_queue_full_when_watermark_equals_capacity():
    q = AdmissionQueue(capacity=4, shed_watermark=4, backpressure_watermark=2)
    outcomes = [q.offer(_req(i), now=0.0) for i in range(5)]
    assert outcomes[-1] == SHED_QUEUE_FULL
    assert q.depth == 4


def test_queue_admit_stamps_time_and_pops_fifo():
    q = AdmissionQueue(capacity=8)
    for i in range(3):
        q.offer(_req(i), now=float(i))
    assert q.peek("pbm").admit_s == 0.0
    popped = q.pop("pbm", 2)
    assert [r.request_id for r in popped] == [0, 1]
    assert q.depth == 1


def test_queue_remove_if_preserves_survivor_order():
    q = AdmissionQueue(capacity=8)
    for i in range(4):
        q.offer(_req(i), now=0.0)
    removed = q.remove_if("pbm", lambda r: r.request_id % 2 == 0)
    assert [r.request_id for r in removed] == [0, 2]
    assert [r.request_id for r in q.pop("pbm", 4)] == [1, 3]


# -- circuit breaker ----------------------------------------------------------
def test_breaker_full_lifecycle():
    b = CircuitBreaker("m/primary", window=8, threshold=0.5, min_samples=2,
                       cooldown=3)
    assert b.state == CLOSED and b.available()
    b.record(False)
    b.record(False)
    assert b.state == OPEN and not b.available()
    for _ in range(3):
        b.note_skipped()
    assert b.state == HALF_OPEN and b.available()
    b.begin()
    assert not b.available()  # one probe at a time
    b.record(False)
    assert b.state == OPEN  # failed probe re-opens
    for _ in range(3):
        b.note_skipped()
    b.begin()
    b.record(True)
    assert b.state == CLOSED and b.available()
    assert b.transitions == 5


def test_breaker_available_is_pure():
    b = CircuitBreaker("m/primary", min_samples=2, cooldown=2)
    b.record(False)
    b.record(False)
    assert b.state == OPEN
    for _ in range(10):  # planner may consult many times per loop
        assert not b.available()
    assert b.state == OPEN  # no cooldown ticks from observation


def test_ladder_select_walk_and_skip_ticks():
    lad = DegradationLadder("m", breaker_kwargs=dict(min_samples=2,
                                                     cooldown=2))
    assert lad.select() == "primary"
    assert lad.walk_from("primary") == ["primary", "int8", "prior"]
    lad.record("primary", False)
    lad.record("primary", False)
    assert lad.select() == "int8"
    assert lad.walk_from("int8") == ["int8", "prior"]
    # two dispatches answered below primary tick its cooldown -> half-open
    lad.finish_dispatch("int8", {"int8"})
    lad.finish_dispatch("int8", {"int8"})
    assert lad.breakers["primary"].state == HALF_OPEN
    assert lad.select() == "primary"  # probe allowed


# -- deadline batcher ---------------------------------------------------------
def _queued(registry, reqs, now=0.0):
    q = AdmissionQueue(capacity=64)
    for r in reqs:
        q.offer(r, now=now)
    return q


def test_batcher_waits_then_fires_on_max_wait(registry):
    b = DeadlineBatcher(registry, max_wait_s=0.005)
    q = _queued(registry, [_req(0, deadline_s=1.0)])
    assert b.plan(q, "pbm", "primary", now=0.0) is None
    t = b.next_decision_time(q, "pbm", "primary", now=0.0)
    assert t == pytest.approx(0.005)
    plan = b.plan(q, "pbm", "primary", now=t)
    assert plan is not None and plan.bucket == 1


def test_batcher_fires_full_batch_immediately(registry):
    b = DeadlineBatcher(registry)
    q = _queued(registry, [_req(i, deadline_s=1.0) for i in
                           range(registry.max_bucket)])
    plan = b.plan(q, "pbm", "primary", now=0.0)
    assert plan is not None
    assert plan.bucket == registry.max_bucket
    assert len(plan.requests) == registry.max_bucket


def test_batcher_slack_trigger_protects_oldest(registry):
    est = registry["pbm"].estimate("primary", 1)
    b = DeadlineBatcher(registry, max_wait_s=10.0, slack_margin_s=0.001)
    q = _queued(registry, [_req(0, deadline_s=est + 0.002)])
    # slack barely above est+margin: hold
    assert b.plan(q, "pbm", "primary", now=0.0) is None
    t = b.next_decision_time(q, "pbm", "primary", now=0.0)
    assert b.plan(q, "pbm", "primary", now=t) is not None


def test_batcher_plan_fires_exactly_at_decision_time(registry):
    """Regression: (admit + wait) - admit can round below wait in float64;
    plan() must use the same trigger expressions as next_decision_time or
    the event loop spins at the decision time without dispatching."""
    b = DeadlineBatcher(registry, max_wait_s=0.005)
    req = _req(0, deadline_s=1.0, arrival_s=0.02649782139617092)
    q = AdmissionQueue(capacity=8)
    q.offer(req, now=0.027641919546832948)
    t = b.next_decision_time(q, "pbm", "primary",
                             now=0.027641919546832948)
    assert b.plan(q, "pbm", "primary", now=t) is not None


def test_batcher_reaps_unmeetable(registry):
    b = DeadlineBatcher(registry)
    floor = registry["pbm"].estimate("primary", BUCKETS[0])
    q = _queued(registry, [_req(0, deadline_s=floor / 2),
                           _req(1, deadline_s=1.0)])
    reaped = b.reap_unmeetable(q, "pbm", "primary", now=0.0)
    assert [r.request_id for r in reaped] == [0]
    assert q.depth == 1


def test_batcher_flush_drains_partial(registry):
    b = DeadlineBatcher(registry, max_wait_s=10.0)
    q = _queued(registry, [_req(0, deadline_s=10.0)])
    assert b.plan(q, "pbm", "primary", now=0.0) is None
    assert b.plan(q, "pbm", "primary", now=0.0, flush=True) is not None


# -- engine: healthy path -----------------------------------------------------
def test_every_request_answered_exactly_once(registry):
    eng = _engine(registry)
    trace = _trace(40)
    results = eng.run_trace(trace, handle_signals=False)
    assert sorted(r.request_id for r in results) == list(range(40))
    assert all(r.status == "ok" for r in results)
    assert eng.stats["serve.answered"] == 40
    s = eng.summary(results)
    assert s["deadline_hit_rate"] == 1.0
    assert s["p99_ms"] >= s["p50_ms"] > 0


def test_warm_traffic_never_retraces(registry):
    """After warmup every (tier, bucket) program is cached: a fresh burst
    of traffic must not bump any trace counter."""
    before = {m: dict(registry[m].trace_counts) for m in MODELS}
    for m in MODELS:  # warmup compiled exactly one program per bucket
        assert before[m]["primary"] == len(BUCKETS)
        assert before[m]["int8"] == len(BUCKETS)
    eng = _engine(registry)
    eng.run_trace(_trace(60, seed=7), handle_signals=False)
    after = {m: dict(registry[m].trace_counts) for m in MODELS}
    assert after == before


def test_overload_sheds_with_reason(registry):
    eng = _engine(registry, queue=AdmissionQueue(capacity=8))
    # a burst far above service rate: everything arrives at ~t=0
    results = eng.run_trace(_trace(60, qps=100000.0, deadline_s=0.5),
                            handle_signals=False)
    assert len(results) == 60
    shed = [r for r in results if r.status == "shed"]
    assert shed and all(r.reason in ("shed_overload", "shed_queue_full")
                        for r in shed)
    answered = [r for r in results if r.answered]
    assert answered, "admitted requests must still be served"
    assert eng.stats["serve.shed"] == len(shed)


def test_unmeetable_deadline_is_shed_not_late(registry):
    eng = _engine(registry)
    floor = registry["pbm"].estimate("primary", BUCKETS[0])
    trace = [_req(0, deadline_s=floor / 3, arrival_s=0.001)]
    results = eng.run_trace(trace, handle_signals=False)
    assert results[0].status == "shed"
    assert results[0].reason == "deadline_unmeetable"
    assert eng.stats["serve.deadline_miss"] == 1


def test_unknown_model_rejected(registry):
    eng = _engine(registry)
    trace = [_req(0, model="nope", arrival_s=0.001)]
    results = eng.run_trace(trace, handle_signals=False)
    assert results[0].status == "rejected"
    assert results[0].reason == "unknown_model"


def test_force_tier_paths_and_int8_tolerance(registry):
    """Forcing each tier serves; int8 predictions match primary within the
    documented quantization tolerance (scale/2 per table read)."""
    out = {}
    for tier in TIERS:
        eng = _engine(registry, force_tier=tier)
        results = eng.run_trace(_trace(20, seed=3, models=("pbm",)),
                                handle_signals=False)
        assert all(r.answered and r.tier == tier for r in results)
        out[tier] = {r.request_id: r.log_ctr for r in results}
    for rid, primary in out["primary"].items():
        dprob = np.abs(np.exp(primary) - np.exp(out["int8"][rid])).max()
        assert dprob < 0.01, f"int8 drifted {dprob} from primary"
    prior = registry["pbm"].prior_log_ctr
    assert all(np.allclose(v, prior) for v in out["prior"].values())


def test_poison_rejected_alone_batchmates_answered(registry):
    """One poisoned request in a same-instant burst is rejected by
    validation; every batch-mate is answered normally."""
    burst = [_req(i, deadline_s=0.5, arrival_s=0.001, seed=i)
             for i in range(8)]
    trace = list(PoisonTrace(burst, at=[3], modes=("nan_ids",)))
    eng = _engine(registry)
    results = {r.request_id: r for r in
               eng.run_trace(trace, handle_signals=False)}
    assert results[3].status == "rejected"
    assert results[3].reason.startswith("nonfinite_values")
    for i in set(range(8)) - {3}:
        assert results[i].answered and results[i].deadline_hit


# -- engine: degradation ------------------------------------------------------
def test_model_failure_degrades_and_breaker_trips(registry):
    eng = _engine(registry,
                  faults=[SlowModel(model="pbm", fail=True,
                                    at_dispatches=range(0, 4))],
                  breaker_kwargs=dict(window=8, min_samples=2,
                                      threshold=0.5, cooldown=2))
    results = eng.run_trace(_trace(30, seed=5, models=("pbm",)),
                            handle_signals=False)
    assert all(r.answered for r in results)
    degraded = [r for r in results if r.degraded]
    assert degraded, "injected failures must push traffic down the ladder"
    assert eng.stats["serve.model_errors"] >= 2
    assert eng.stats["serve.degraded"] == len(degraded)
    primary = eng.ladders["pbm"].breakers["primary"]
    assert primary.transitions >= 2  # tripped open, then recovered
    assert primary.state == CLOSED  # fault window passed: recovered


def test_slow_model_misses_trip_breaker(registry):
    """Pure latency (no exceptions): deadline misses alone count as batch
    failures and open the breaker."""
    eng = _engine(registry,
                  faults=[SlowModel(model="pbm", delay_seconds=0.1,
                                    at_dispatches=range(0, 3))],
                  breaker_kwargs=dict(min_samples=2, threshold=0.5,
                                      cooldown=50))
    results = eng.run_trace(_trace(30, seed=5, models=("pbm",), qps=100.0,
                                   deadline_s=0.12),
                            handle_signals=False)
    assert len(results) == 30
    assert eng.stats["serve.deadline_miss"] >= 2
    primary = eng.ladders["pbm"].breakers["primary"]
    assert primary.transitions >= 1 and primary.state == OPEN
    assert any(r.degraded for r in results)


def test_prior_injected_failure_fails_closed(registry):
    """Even the terminal rung raising (only possible via injection) sheds
    the batch per-request instead of crashing the loop."""
    eng = _engine(registry,
                  faults=[SlowModel(model="pbm", fail=True,
                                    tiers=TIERS)])
    results = eng.run_trace(_trace(10, seed=2, models=("pbm",)),
                            handle_signals=False)
    assert len(results) == 10
    assert all(r.status == "shed" and r.reason == "model_failure"
               for r in results)


def test_multi_model_isolation(registry):
    """A failing pbm must not degrade dbn traffic."""
    eng = _engine(registry,
                  faults=[SlowModel(model="pbm", fail=True,
                                    at_dispatches=range(100))],
                  breaker_kwargs=dict(min_samples=2, cooldown=1000))
    results = eng.run_trace(_trace(40, seed=9), handle_signals=False)
    by_model = {}
    for r in results:
        by_model.setdefault(r.model, []).append(r)
    assert all(not r.degraded for r in by_model["dbn"])
    assert any(r.degraded for r in by_model["pbm"])
    health = eng.health()
    assert health["pbm"]["breakers"]["primary"] == OPEN
    assert health["dbn"]["breakers"]["primary"] == CLOSED
    assert health["dbn"]["tier"] == "primary"
    assert health["pbm"]["tier"] == "int8"


# -- engine: drain ------------------------------------------------------------
def test_sigterm_drain_zero_drops(registry):
    """SIGTERM mid-trace: admission stops (remaining arrivals rejected
    with 'draining'), queued requests are flushed, nothing is dropped."""
    eng = _engine(registry, faults=[ServeKillSwitch(at_request=20)])
    results = eng.run_trace(_trace(50, seed=4), handle_signals=True)
    assert sorted(r.request_id for r in results) == list(range(50))
    draining = [r for r in results if r.reason == "draining"]
    answered = [r for r in results if r.answered]
    assert draining and answered
    assert eng.stats["serve.drains"] == 1
    # everything admitted before the signal was served, not dropped
    assert len(answered) + len(draining) == 50
    # the handler restored the previous SIGTERM disposition on exit
    assert signal.getsignal(signal.SIGTERM) is not None


def test_disarmed_serve_killswitch_is_inert(registry):
    ks = ServeKillSwitch(at_request=5, armed=False)
    eng = _engine(registry, faults=[ks])
    results = eng.run_trace(_trace(12, seed=4), handle_signals=True)
    assert not ks.fired
    assert all(r.answered for r in results)


# -- the pinned chaos drill ---------------------------------------------------
def _chaos_drill(registry, seed=1):
    faults = [
        SlowModel(model="pbm", fail=True, at_dispatches=range(0, 6)),
        ServeKillSwitch(at_request=70),
    ]
    trace = PoisonTrace(_trace(90, qps=500.0, seed=seed),
                        at=[5, 12, 19, 26, 33], seed=0)
    eng = _engine(registry, faults=faults,
                  breaker_kwargs=dict(window=8, min_samples=2,
                                      threshold=0.5, cooldown=4))
    results = eng.run_trace(trace, handle_signals=True)
    return eng, results


def test_chaos_drill_guarantees_and_determinism(registry):
    """The flagship drill: slow/failing primary + poison flood + SIGTERM
    at request 70, twice. Zero drops, poison rejected individually,
    breaker trips, drain completes, and both runs match bit-for-bit."""
    eng1, res1 = _chaos_drill(registry)
    eng2, res2 = _chaos_drill(registry)

    # zero uncaught exceptions is implicit (we got here); zero drops:
    assert sorted(r.request_id for r in res1) == list(range(90))
    by_id = {r.request_id: r for r in res1}
    # poison rejected individually, batch-mates answered
    for rid in (5, 12, 19, 26, 33):
        assert by_id[rid].status == "rejected"
    neighbors = [by_id[i] for i in (4, 6, 11, 13)]
    assert all(r.answered or r.reason == "draining" for r in neighbors)
    # breaker tripped to degraded
    assert any(r.degraded for r in res1)
    assert eng1.ladders["pbm"].breakers["primary"].transitions >= 1
    # drain: everything after request 70 rejected, none dropped
    assert eng1.stats["serve.drains"] == 1
    assert eng1.stats["serve.rejected_draining"] > 0
    # nonzero deterministic counters, identical across runs
    assert eng1.stats["serve.model_errors"] > 0
    assert dict(eng1.stats) == dict(eng2.stats)
    assert _signature(res1) == _signature(res2)


def test_chaos_drill_counters_flow_to_recorder(registry, tmp_path):
    """Engine counters ride the standard Recorder: the drill's shed /
    degraded / breaker counters land in the JSONL sink."""
    from repro import obs

    path = str(tmp_path / "serve_metrics.jsonl")
    rec = obs.Recorder(sinks=[obs.JsonlSink(path)])
    faults = [SlowModel(model="pbm", fail=True, at_dispatches=range(0, 4))]
    eng = _engine(registry, recorder=rec, faults=faults,
                  breaker_kwargs=dict(min_samples=2, cooldown=4))
    eng.run_trace(_trace(30, seed=5, models=("pbm",)), handle_signals=False)
    rec.close()
    events = obs.read_jsonl(path)
    names = {e["name"] for e in events}
    assert "serve_latency_ms" in names
    assert "model_error" in names
    assert "breaker_transition" in names
    snapshots = [e for e in events if e.get("kind") == "counters"]
    assert snapshots, "run_trace must flush a counters snapshot"
    snap = snapshots[-1]["data"]
    assert snap.get("serve.model_errors", 0) > 0
    assert snap.get("serve.degraded", 0) > 0
    assert snap.get("serve.breaker_transitions", 0) > 0
    assert "serve.queue_depth:gauge" in snap


# -- launcher exit status -----------------------------------------------------
_LAUNCH_ARGS = ["--model", "pbm", "--pairs", "1000", "--requests", "8",
                "--batch", "4", "--virtual-time"]


@pytest.fixture()
def serve_launcher(monkeypatch):
    from repro.launch import serve as launcher

    # Tests keep to the process's own compile cache settings.
    monkeypatch.setattr(launcher, "enable_compile_cache", lambda: None)
    return launcher


def test_serve_launcher_returns_summary_when_all_primary(serve_launcher):
    summary = serve_launcher.main(_LAUNCH_ARGS)
    assert summary["answered"] == 8 and summary["degraded"] == 0


def _fail_primary_traffic(monkeypatch):
    from repro.serve.registry import ModelEntry

    real_run = ModelEntry.run

    def run(self, tier, batch):
        # warmup calls run() directly; engine dispatches are counted first
        if tier == "primary" and self.dispatches:
            raise RuntimeError("device lost")
        return real_run(self, tier, batch)

    monkeypatch.setattr(ModelEntry, "run", run)


def test_serve_launcher_fails_on_unasked_model_error(serve_launcher,
                                                      monkeypatch):
    _fail_primary_traffic(monkeypatch)
    with pytest.raises(SystemExit, match="model errors"):
        serve_launcher.main(_LAUNCH_ARGS)


def test_serve_launcher_tolerates_degradation_a_drill_asked_for(
        serve_launcher, monkeypatch):
    _fail_primary_traffic(monkeypatch)
    summary = serve_launcher.main(_LAUNCH_ARGS + ["--fault-poison-every",
                                                  "5"])
    assert summary["degraded"] > 0
