"""Serving driver: click probabilities served to rankers through the
program's own path, ``ModelRegistry`` -> ``ServeEngine.run_trace`` on the
wall clock, under an open loop of arrivals.

Set-up: parameters drawn from the seed on the device in one call (the
configuration's start plus a standard normal draw per leaf, so that no two
rows agree); the registry with the mix's buckets, warmed by its own
``warmup``; the requests, one session each from the mix's session source;
the arrival schedule.

The schedule is open loop: every request is due at a fixed time whatever
the engine is doing, and its latency runs from that time to its answer. A
run offers ``rate_per_s x seconds`` requests, whose gaps are the same set of
exponential quantiles under every seed, in an order drawn from the seed: a
Poisson process's arrivals without the seed changing the amount of work.
``serve_p50_ms`` (end to end, bounded) and the tails ``serve_p95_ms`` and
``serve_p99_ms`` (end to end, reported beside it without a bound) are taken
over every request due in the window; one that gets no answer, or is shed,
counts as waiting the whole window. The interpreter's garbage collections in the
window are counted and timed, and logged beside them.
A request fails when it is shed or rejected, answered below the ``primary``
tier, or answered after its deadline.

After the window the registry is freed and the plain reference computes the
click probabilities of every answered request from the same parameters.
The numbers compared: ``click_prob``, the widest relative gap of a served
probability from the reference's; ``missing``, requests with no result.
"""
from __future__ import annotations

import collections
import gc
import shutil
import time
from typing import Dict

import numpy as np

from harness import program
from harness.cell import jax_seed
from harness.compare import judge
from harness.device import memory_peak_bytes
from harness.record import Record
from harness.sessions import SessionSource


def schedule(seed: int, rate: float, n: int) -> np.ndarray:
    """Arrival times (s) of ``n`` requests at ``rate`` per second."""
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    return np.cumsum(np.random.default_rng((seed, 2)).permutation(gaps))


def draw_params(ref, cfg, seed32: int):
    """``{leaf: array}`` on the device: the configuration's start plus
    N(0, 1), in one jitted call."""
    import jax

    @jax.jit
    def draw(key):
        p = ref.init(cfg)
        return {k: v + jax.random.normal(jax.random.fold_in(key, i), v.shape,
                                         v.dtype)
                for i, (k, v) in enumerate(sorted(p.items()))}

    return draw(jax.random.PRNGKey(seed32))


class Setup:
    """Everything a window needs, built once."""

    def __init__(self, cell, log):
        import jax

        from repro.serve import ModelRegistry

        cfg, traffic = cell.config, cell.traffic
        self.cell = cell
        self.ref = cell.reference()
        seed32 = jax_seed(cell.seed)
        self.params = draw_params(self.ref, cfg, seed32)
        net = program.build_model(cfg)
        like = {k: (v.shape, v.dtype) for k, v in program.leaves(
            jax.eval_shape(lambda: net.init(jax.random.PRNGKey(0)))).items()}
        mine = {k: (v.shape, v.dtype) for k, v in self.params.items()}
        if like != mine:
            raise ValueError(f"the program's parameters {like} are not the "
                             f"reference's {mine}")
        self.name = cfg["name"]
        self.registry = ModelRegistry(buckets=traffic["buckets"])
        self.registry.add(self.name, net, program.nest(self.params),
                          n_pairs=int(cfg["tables"]["attraction"]["parameters"]))
        t0 = time.perf_counter()
        self.registry.warmup()
        log(f"registry warm: {len(self.registry.buckets)} buckets in "
            f"{time.perf_counter() - t0:.2f}s")
        self.source = SessionSource(traffic["sessions"], cell.seed)

    def window(self, rate: float, seconds: float, trace_dir=None, index=0):
        """Serve one window of ``rate x seconds`` requests. Each request is
        made when the engine reads it from the schedule, as from a socket,
        so the load generator holds no objects of its own in the server's
        process. Returns a :class:`Window`."""
        import jax

        from repro.obs import Recorder
        from repro.serve import (AdmissionQueue, ServeEngine, ServeRequest,
                                 WallClock)

        n = int(round(rate * seconds))
        rows = self.source.chunk(index, n)
        deadline = float(self.cell.traffic["deadline_ms"]) * 1e-3
        recorder = Recorder(span_capacity=1 << 17)  # spans stay in its ring
        engine = ServeEngine(
            self.registry, recorder=recorder, clock=WallClock(),
            queue=AdmissionQueue(capacity=int(self.cell.traffic["queue_capacity"])))

        def arrivals():
            for i in range(n):
                yield ServeRequest(request_id=i, model=self.name,
                                   positions=rows["positions"][i],
                                   query_doc_ids=rows["query_doc_ids"][i],
                                   mask=rows["mask"][i], deadline_s=deadline,
                                   arrival_s=float(due[i]))

        # Set-up's garbage is collected in set-up. Left to the interpreter,
        # the first full collection after it falls in the window's first
        # second and stalls the engine there.
        gc.collect()
        pauses = GcPauses()
        if trace_dir is not None:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(str(trace_dir), profiler_options=options)
        try:
            # The schedule starts once all of the above is done.
            due = engine.clock.now() + 0.05 + schedule(self.cell.seed + index,
                                                       rate, n)
            first_due = time.perf_counter() + (due[0] - engine.clock.now())
            with pauses:
                results = engine.run_trace(arrivals(), handle_signals=False)
        finally:
            if trace_dir is not None:
                jax.profiler.stop_trace()
        return Window(n, rows, results, recorder, first_due, due, pauses)


class GcPauses:
    """The interpreter's garbage collections while entered: how many, how
    long the longest and all of them took (s), and when each one over
    ``slow`` seconds began (s after entering) and of which generation."""

    def __init__(self, slow: float = 0.005):
        self.count, self.longest, self.total = 0, 0.0, 0.0
        self.slow, self.slow_ones = slow, []
        self._t0 = self._entered = None

    def _on(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            dt = time.perf_counter() - self._t0
            self.count += 1
            self.longest = max(self.longest, dt)
            self.total += dt
            if dt > self.slow:
                self.slow_ones.append((self._t0 - self._entered,
                                       info["generation"], dt))

    def __enter__(self):
        self._entered = time.perf_counter()
        gc.callbacks.append(self._on)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._on)


class Window:
    """One served window: the schedule, the answers and what was seen."""

    def __init__(self, n, rows, results, recorder, first_due, due, pauses):
        self.n, self.rows, self.results = n, rows, results
        self.recorder, self.first_due, self.due = recorder, first_due, due
        self.pauses = pauses
        self.by_id = {r.request_id: r for r in results}

    def latencies(self, seconds: float) -> np.ndarray:
        """Each request's latency (s); one with no answer waited the window."""
        out = np.full(self.n, float(seconds))
        for i, r in self.by_id.items():
            if r.answered:
                out[i] = r.latency_s
        return out

    def outcomes(self, seconds: float):
        """Log lines: results by status, reason and tier, and the requests
        that failed in each tenth of the schedule."""
        kinds = collections.Counter((r.status, r.reason, r.tier)
                                    for r in self.results)
        yield "outcomes: " + ", ".join(
            f"{status}/{reason}/{tier} {n}"
            for (status, reason, tier), n in kinds.most_common())
        ok = np.zeros(self.n, bool)
        for i, r in self.by_id.items():
            ok[i] = r.answered and r.tier == "primary" and r.deadline_hit
        tenth = np.minimum((self.due - self.due[0]) * 10 / seconds, 9)
        failed = np.bincount(tenth.astype(int), weights=~ok, minlength=10)
        yield "failed by tenth of the window: " + " ".join(
            str(int(f)) for f in failed)
        yield "collections over 5 ms (s into the window, generation, ms): " + \
            ", ".join(f"{t:.2f} g{g} {dt * 1e3:.1f}"
                      for t, g, dt in self.pauses.slow_ones)
        # Where the engine stood still: in a registry call, or between two.
        from harness.tap import WALL_MINUS_PERF

        calls = sorted((s.t_start - WALL_MINUS_PERF - self.first_due,
                        s.duration) for s in self.recorder.tracer.spans
                       if s.name == "serve_batch")
        gaps = [(a + da, b - a - da) for (a, da), (b, _) in zip(calls, calls[1:])]
        for what, rows in (("registry calls", calls), ("gaps between calls", gaps)):
            top = sorted(rows, key=lambda r: -r[1])[:3]
            yield f"longest {what} (s into the window, ms): " + ", ".join(
                f"{t:.3f} {d * 1e3:.1f}" for t, d in top)
        yield f"window opened at {self.first_due + WALL_MINUS_PERF:.3f} (epoch s)"

    def failed(self) -> int:
        ok = sum(1 for r in self.by_id.values() if r.answered
                 and r.tier == "primary" and r.deadline_hit)
        return self.n - ok


def served_gap(ref, cfg, params, rows, results, block: int = 65536) -> float:
    """Widest relative gap of a served click probability from the
    reference's, over every request answered on ``primary`` (a lower tier
    declares its answer degraded; it counts as failed) and real position."""
    import jax
    import jax.numpy as jnp

    answered = sorted((r for r in results
                       if r.answered and r.tier == "primary"),
                      key=lambda r: r.request_id)
    if not answered:
        return float("inf")
    ids = np.asarray([r.request_id for r in answered])
    got = np.exp(np.stack([np.asarray(r.log_ctr, np.float64)
                           for r in answered]))
    probs = jax.jit(lambda p, b: jnp.exp(ref.click_log_probs(p, b, cfg)))
    want = []
    with jax.default_matmul_precision("highest"):
        for lo in range(0, len(ids), block):
            sel = ids[lo:lo + block]
            batch = {k: rows[k][sel] for k in ("positions", "query_doc_ids",
                                               "mask")}
            want.append(np.asarray(probs(params, batch), np.float64))
    want = np.concatenate(want)
    mask = rows["mask"][ids].astype(bool)
    return float(np.max(np.abs(got - want)[mask] / want[mask]))


def run(cell, t_start: float, log) -> Record:
    traffic = cell.traffic
    setup = Setup(cell, log)
    trace_dir = None
    if cell.trace:
        trace_dir = cell.out / "trace"
        shutil.rmtree(trace_dir, ignore_errors=True)
    w = setup.window(float(traffic["rate_per_s"]), cell.seconds, trace_dir)
    lat = w.latencies(cell.seconds)
    failed = w.failed()
    log(f"window: {w.n} requests due over {cell.seconds}s, {failed} failed; "
        f"{w.pauses.count} garbage collections, longest "
        f"{w.pauses.longest * 1e3:.1f} ms, {w.pauses.total * 1e3:.1f} ms in all")
    for line in w.outcomes(cell.seconds):
        log(line)
    spans = [_span(s) for s in w.recorder.tracer.spans]
    peak = memory_peak_bytes(cell.chips)

    params = setup.params
    ref, cfg = setup.ref, cell.config
    del setup
    gc.collect()
    values = {"click_prob": served_gap(ref, cfg, params, w.rows, w.results),
              "missing": float(w.n - len(w.by_id))}
    tails = {f"serve_p{q}_ms": {"value": float(np.percentile(lat, q) * 1e3),
                                "unit": "ms"} for q in (95, 99)}
    log("tails: " + ", ".join(f"{k} {v['value']!r} ms"
                              for k, v in tails.items()))
    return Record(
        cell=cell,
        end_to_end={"setup_s": w.first_due - t_start,
                    "serve_p50_ms": float(np.percentile(lat, 50) * 1e3)},
        unbounded=tails,
        attempted=w.n, failed=failed, checks=judge(values, cell.limits),
        memory_peak_bytes=peak, window_s=float(cell.seconds), spans=spans,
        trace_dir=trace_dir)


def _span(s) -> Dict:
    from harness.tap import WALL_MINUS_PERF

    start = s.t_start - WALL_MINUS_PERF
    return {"name": s.name, "start": start, "end": start + s.duration,
            "tags": dict(s.tags)}
