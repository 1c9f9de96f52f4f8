"""Trainer: the paper's Listing-1 entry point.

    trainer = Trainer(optimizer=adamw(0.003), epochs=50)
    history = trainer.train(model, train_loader, val_loader)
    results = trainer.test(model, test_loader)

Implements: chunked scan-jitted update steps through
:class:`repro.train.engine.TrainEngine` (one dispatch and zero host syncs
per ``chunk_batches`` steps; per-step losses accumulate on device and are
fetched one chunk behind the dispatch), optional data-parallel execution
over a mesh and sparse embedding-table updates, per-epoch validation with
the paper's click metrics (compiled eval step cached LRU across epochs and
models, scanned over prefetched chunks, one host transfer per evaluate
call), early stopping after the first epoch without val-loss improvement
(paper §6), periodic + preemption-triggered atomic checkpoints at chunk
granularity, and bit-exact resume (params + optimizer + loader state +
epoch counter).

Sweep mode (``Trainer(replicas=R)``): R independent runs — distinct init
seeds always (``replica_seeds``, default ``seed + i``), distinct learning
rates optionally (``replica_lrs``, requires an ``inject_lr=True``
optimizer) — train inside one vmapped engine. Validation runs one compiled
step over an R-stacked metric state, early stopping is tracked per replica
(finished replicas freeze in place via the engine's active mask while the
rest keep training), history records carry per-replica lists, and
checkpoints hold the R-stacked trees (`repro.train.select_replica`
extracts any run for standalone resume/test).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from repro.core.metrics import (ConditionalPerplexity, LogLikelihood, MultiMetric,
                                Perplexity)
from repro.data.loader import DevicePrefetcher
from repro.obs import (ProfileWindow, TelemetryDrain, get_recorder, make_event,
                       parse_profile_steps, watch_compiles)
from repro.train.checkpoints import CheckpointManager
from repro.train.engine import TrainEngine
from repro.train.fault_tolerance import PreemptionHandler, StepWatchdog


@dataclasses.dataclass
class TrainState:
    params: Any
    opt_state: Any
    epoch: int = 0
    global_step: int = 0


def default_metrics() -> MultiMetric:
    return MultiMetric({
        "ll": LogLikelihood(),
        "ppl": Perplexity(),
        "cond_ppl": ConditionalPerplexity(),
    })


class Trainer:
    def __init__(self, optimizer, epochs: int = 100, patience: int = 1,
                 seed: int = 0, checkpoint_dir: Optional[str] = None,
                 checkpoint_every_steps: Optional[int] = None,
                 keep_checkpoints: int = 3,
                 metrics_factory: Callable[[], MultiMetric] = default_metrics,
                 log_fn: Callable[[str], None] = print,
                 handle_preemption: bool = False,
                 chunk_batches: int = 1,
                 mesh=None,
                 sparse_tables: bool = False,
                 sparse_table_kwargs: Optional[Dict[str, Any]] = None,
                 replicas: Optional[int] = None,
                 replica_lrs: Optional[List[float]] = None,
                 replica_seeds: Optional[List[int]] = None,
                 nonfinite_guard: bool = False,
                 step_budget_seconds: Optional[float] = None,
                 telemetry: bool = False,
                 recorder=None,
                 obs_every: int = 1,
                 profile_steps: Optional[str] = None,
                 profile_dir: Optional[str] = None):
        self.optimizer = optimizer
        self.epochs = epochs
        self.patience = patience
        self.seed = seed
        self.metrics_factory = metrics_factory
        self.log_fn = log_fn
        self.checkpoint_every_steps = checkpoint_every_steps
        self.ckpt = (CheckpointManager(checkpoint_dir, keep=keep_checkpoints,
                                       log_fn=log_fn)
                     if checkpoint_dir else None)
        self.handle_preemption = handle_preemption
        self.nonfinite_guard = nonfinite_guard
        self.step_budget_seconds = step_budget_seconds
        # Observability (see repro.obs): `telemetry` turns on the engine's
        # on-device per-step series; `recorder` pins a Recorder (default:
        # the process-global one, resolved at use so a later
        # obs.configure() still takes effect); `obs_every` rate-limits
        # per-step metric events; `profile_steps` ("A:B") opens a
        # jax.profiler window into `profile_dir` around those global
        # steps.
        self.telemetry = bool(telemetry)
        self.recorder = recorder
        self.obs_every = int(obs_every)
        self.profile_steps = (parse_profile_steps(profile_steps)
                              if isinstance(profile_steps, str)
                              else profile_steps)
        self.profile_dir = profile_dir
        self.chunk_batches = chunk_batches
        self.mesh = mesh
        self.sparse_tables = sparse_tables
        self.sparse_table_kwargs = sparse_table_kwargs
        if replicas is None and (replica_lrs is not None
                                 or replica_seeds is not None):
            raise ValueError("replica_lrs/replica_seeds require replicas=R")
        for name, knob in (("replica_lrs", replica_lrs),
                           ("replica_seeds", replica_seeds)):
            if knob is not None and len(knob) != replicas:
                raise ValueError(f"{name} has {len(knob)} entries for "
                                 f"replicas={replicas}")
        self.replicas = replicas
        self.replica_lrs = replica_lrs
        self.replica_seeds = replica_seeds
        # Compiled eval step per (model, replicas): _make_eval_step used to
        # be re-jitted (a fresh trace + compile) on every evaluate() call —
        # repeat evaluations reuse the cached (metrics, compiled steps)
        # entry. The cache is LRU (move-to-end on hit, evict front): the
        # model being evaluated every epoch survives a >4-model sweep.
        self._eval_cache: Dict[Any, tuple] = {}

    @property
    def final_state(self) -> TrainState:
        """The state the last :meth:`train` call ended with, in the model's
        own shapes (its :attr:`engine` is the one that ran it)."""
        return self._final_state

    def _rec(self):
        """The recorder events go to: the pinned one, else the global."""
        return self.recorder if self.recorder is not None else get_recorder()

    def _make_engine(self, model) -> TrainEngine:
        return TrainEngine(model, self.optimizer,
                           chunk_batches=self.chunk_batches, mesh=self.mesh,
                           sparse_tables=self.sparse_tables,
                           sparse_table_kwargs=self.sparse_table_kwargs,
                           replicas=self.replicas,
                           nonfinite_guard=self.nonfinite_guard,
                           telemetry=self.telemetry)

    def _eval_update_fn(self, model, metrics, replicas=None):
        def eval_step(params, state, batch):
            log_probs = model.predict_clicks(params, batch)
            cond = model.predict_conditional_clicks(params, batch)
            return metrics.update(state, log_probs=log_probs,
                                  conditional_log_probs=cond,
                                  clicks=batch["clicks"], where=batch["mask"])

        if replicas is None:
            return eval_step
        # R-stacked (params, metric state), one broadcast batch: a single
        # compiled step advances every replica's evaluation.
        return jax.vmap(eval_step, in_axes=(0, 0, None))

    def _make_eval_step(self, model, metrics, replicas=None):
        return jax.jit(self._eval_update_fn(model, metrics, replicas))

    def _make_eval_chunk_step(self, model, metrics, replicas=None):
        """Scanned eval step over a stacked ``(n, B, ...)`` chunk: one jit
        dispatch per ``chunk_batches`` eval batches, metric state as the
        scan carry (loss-free analogue of the training engine's chunk
        step)."""
        update = self._eval_update_fn(model, metrics, replicas)

        def chunk_step(params, state, chunk):
            def body(state, batch):
                return update(params, state, batch), None

            state, _ = jax.lax.scan(body, state, chunk)
            return state

        return jax.jit(chunk_step)

    def _get_eval_step(self, model, replicas=None):
        key = (model, replicas)
        if key in self._eval_cache:
            # LRU hit: move to the back of the eviction order.
            self._eval_cache[key] = self._eval_cache.pop(key)
            return self._eval_cache[key]
        # bounded: a trainer reused across a sweep of models must not
        # pin every model's metrics + compiled executable forever
        while len(self._eval_cache) >= 4:
            self._eval_cache.pop(next(iter(self._eval_cache)))
        metrics = self.metrics_factory()
        self._eval_cache[key] = (metrics,
                                 self._make_eval_step(model, metrics, replicas),
                                 self._make_eval_chunk_step(model, metrics,
                                                            replicas))
        return self._eval_cache[key]

    # -- public API ----------------------------------------------------------------
    def train(self, model, train_loader, val_loader=None,
              state: Optional[TrainState] = None,
              resume: bool = False) -> List[Dict[str, float]]:
        watch_compiles()
        engine = self.engine = self._make_engine(model)
        R = self.replicas
        if state is None:
            if R is None:
                params = model.init(jax.random.PRNGKey(self.seed))
                opt_state = engine.init_opt_state(params)
            else:
                seeds = (self.replica_seeds if self.replica_seeds is not None
                         else [self.seed + i for i in range(R)])
                params = engine.init_replica_params(seeds)
                opt_state = engine.init_opt_state(params)
                if self.replica_lrs is not None:
                    opt_state = engine.set_replica_lrs(opt_state,
                                                       self.replica_lrs)
            state = TrainState(params=params, opt_state=opt_state)
        resumed_early_stop = None
        resume_accum = None
        history: List[Dict[str, float]] = []
        if resume and self.ckpt and self.ckpt.latest_step() is not None:
            tree = {"params": state.params, "opt_state": state.opt_state}
            tree, aux, _ = self.ckpt.restore(like=tree)
            state = TrainState(params=tree["params"], opt_state=tree["opt_state"],
                               epoch=int(aux["epoch"]),
                               global_step=int(aux["global_step"]))
            resumed_early_stop = aux.get("early_stop")
            # Mid-epoch crash recovery: the checkpoint carries the epoch's
            # running loss accumulators and the completed-epoch history, so
            # the resumed run's returned history is identical to an
            # uninterrupted run's — not just from-here-on.
            resume_accum = aux.get("epoch_accum")
            history = [dict(r) for r in aux.get("history") or []]
            if aux.get("loader") is not None and hasattr(train_loader,
                                                         "load_state_dict"):
                train_loader.load_state_dict(aux["loader"])
            self.log_fn(f"[trainer] resumed at epoch={state.epoch} "
                        f"step={state.global_step}")
        dp = engine.data_parallel_size()
        batch_size = getattr(train_loader, "batch_size", None)
        if dp > 1 and batch_size is not None and batch_size % dp:
            raise ValueError(
                f"batch_size {batch_size} is not divisible by the "
                f"{dp}-way data-parallel mesh")
        if dp > 1 and getattr(train_loader, "drop_last", True) is False:
            raise ValueError(
                "data-parallel training requires drop_last=True: the "
                "tail batch generally cannot be split across the "
                f"{dp}-way data axis (same rule as multi-host streaming)")
        # Scalar tables train flat and leave in the model's own shapes
        # (_finish): engine.place and engine.unplace.
        state.params, state.opt_state = engine.place(state.params,
                                                     state.opt_state)

        preempt = PreemptionHandler() if self.handle_preemption else None
        watchdog = (StepWatchdog(
            self.step_budget_seconds,
            on_violation=lambda step, sec: self.log_fn(
                f"[trainer] watchdog: step ~{step} averaged {sec:.3f}s/step, "
                f"over budget {self.step_budget_seconds}s"),
            recorder=self.recorder)
            if self.step_budget_seconds else None)
        rec = self._rec()
        profile = (ProfileWindow(*self.profile_steps,
                                 log_dir=self.profile_dir or "profile",
                                 recorder=self.recorder)
                   if self.profile_steps else None)
        if R is None:
            best_val = float("inf")
            bad_epochs = 0
        else:
            # Per-replica early-stopping state: a replica that exhausts its
            # patience goes inactive — the engine's update mask freezes its
            # params/opt-state in place while the others keep training, so
            # the single compiled step never retraces.
            best_val = np.full(R, np.inf)
            bad_epochs = np.zeros(R, dtype=int)
            active = np.ones(R, dtype=bool)
        if resumed_early_stop is not None:
            # Without this a resumed sweep would reactivate already-stopped
            # replicas (breaking the freeze-in-place == sequential-run
            # guarantee) and a resumed scalar run would forget its patience
            # counter.
            if R is None:
                best_val = float(resumed_early_stop["best_val"])
                bad_epochs = int(resumed_early_stop["bad_epochs"])
            else:
                best_val = np.asarray(resumed_early_stop["best_val"],
                                      np.float64)
                bad_epochs = np.asarray(resumed_early_stop["bad_epochs"], int)
                active = np.asarray(resumed_early_stop["active"], bool)

        def snapshot_early_stop():
            # JSON-able early-stop state for checkpoint aux. Counters only
            # move at epoch boundaries, so a mid-epoch checkpoint correctly
            # carries the state the epoch started with.
            if R is None:
                self._early_stop_aux = {"best_val": best_val,
                                        "bad_epochs": bad_epochs}
            else:
                self._early_stop_aux = {"best_val": best_val.tolist(),
                                        "bad_epochs": bad_epochs.tolist(),
                                        "active": active.tolist()}

        snapshot_early_stop()

        # Signal handlers must not outlive the loop they guard:
        # restore on every exit path (completion, early stop,
        # preemption return, exception).
        try:
            while state.epoch < self.epochs:
                t0 = time.time()
                # The epoch's single source of truth for loss/skip/batch
                # accumulation AND per-step metric events: one TelemetryDrain,
                # fed one device_get per chunk. The trainer no longer keeps
                # its own parallel accumulators.
                acc = TelemetryDrain(replicas=R, recorder=self.recorder,
                                     every=self.obs_every, epoch=state.epoch)
                wd_epoch_start = watchdog.violations if watchdog else 0
                if resume_accum is not None:
                    # First epoch after a mid-epoch resume: start from the
                    # checkpointed accumulators so the epoch's recorded loss
                    # covers every batch, not just the post-crash ones.
                    acc.load(resume_accum)
                    resume_accum = None
                epoch_active = None if R is None else active.copy()
                epoch_span = rec.span("epoch", epoch=state.epoch)
                epoch_span.__enter__()
                # One jit dispatch per chunk of up to `chunk_batches` steps; the
                # previous chunk's on-device (n,) — or (n, R) — loss payload is
                # drained while the current chunk runs, so the host never blocks
                # on the step it just dispatched. loader_state is the bit-exact
                # resume point after the chunk's last batch (the loader itself
                # has run ahead by the prefetch depth).
                pending = None  # (payload, first global step of its chunk)
                stop = False

                chunk_t0 = time.time()
                for chunk, loader_state, n in DevicePrefetcher(
                        train_loader, chunk_batches=engine.chunk_batches,
                        device=engine.batch_sharding(),
                        recorder=self.recorder):
                    if profile is not None:
                        profile.before_chunk(state.global_step)
                    # A step annotation for xprof: its step_num is the
                    # chunk's first global step.
                    with rec.span("dispatch", step_num=state.global_step):
                        state.params, state.opt_state, losses = engine.step(
                            state.params, state.opt_state, chunk,
                            active=epoch_active)
                    if pending is not None:
                        acc.drain(*pending)
                    pending = (losses, state.global_step)
                    prev_step = state.global_step
                    state.global_step += n
                    if profile is not None:
                        profile.after_chunk(state.global_step)
                    if watchdog is not None:
                        now = time.time()
                        watchdog.check((now - chunk_t0) / max(n, 1),
                                       state.global_step)
                        chunk_t0 = now
                    every = self.checkpoint_every_steps
                    save_now = bool(self.ckpt and every and
                                    prev_step // every < state.global_step // every)
                    preempted = preempt is not None and preempt.should_stop
                    if save_now or (preempted and self.ckpt):
                        # A mid-epoch checkpoint's accumulators must cover
                        # exactly the batches its loader cursor has passed:
                        # drain the in-flight chunk before snapshotting (the
                        # one host sync a checkpoint costs).
                        acc.drain(*pending)
                        pending = None
                        with rec.span("checkpoint", step=state.global_step):
                            self._save(state, train_loader, loader_state,
                                       epoch_accum=acc.aux(),
                                       history=history)
                    if preempted:
                        if self.ckpt:
                            self.log_fn("[trainer] preempted; checkpoint written")
                        else:
                            self.log_fn("[trainer] preempted; no checkpoint_dir "
                                        "configured — stopping without saving")
                        stop = True
                        break
                if pending is not None:
                    acc.drain(*pending)
                if stop:
                    # preempted: leave _final_state usable (test() after a
                    # preempted train must not crash) and hand back history
                    epoch_span.__exit__(None, None, None)
                    if profile is not None:
                        profile.close(state.global_step)
                    self._finish(engine, state)
                    return history
                epoch_span.__exit__(None, None, None)
                state.epoch += 1
                n_batches, skipped_steps = acc.n_batches, acc.skipped_steps
                # Skipped (non-finite) steps contributed no loss; the mean is
                # over the steps that actually updated (TelemetryDrain holds
                # the exact-round-trip python-float sum).
                mean_loss = acc.mean_loss()
                record = {
                    "epoch": state.epoch,
                    "train_loss": (mean_loss if R is None else mean_loss.tolist()),
                    "seconds": time.time() - t0,
                }
                if self.nonfinite_guard:
                    record["skipped_steps"] = (int(skipped_steps) if R is None
                                               else np.asarray(skipped_steps)
                                               .tolist())
                if watchdog is not None:
                    record["watchdog_violations"] = (watchdog.violations
                                                     - wd_epoch_start)
                if R is not None:
                    record["active"] = epoch_active.tolist()
                if val_loader is not None:
                    with rec.span("eval", epoch=state.epoch):
                        val = self.evaluate(model, state.params, val_loader,
                                            replicas=R)
                    record.update({f"val_{k}": v for k, v in val.items()})
                    if R is None:
                        val_loss = -val["ll"]
                        if val_loss < best_val - 1e-6:
                            best_val, bad_epochs = val_loss, 0
                        else:
                            bad_epochs += 1
                    else:
                        # Same rule as the scalar path, applied elementwise to
                        # the replicas still training; finished replicas keep
                        # their counters (their metrics no longer move).
                        val_loss = -np.asarray(val["ll"], np.float64)
                        improved = val_loss < best_val - 1e-6
                        best_val = np.where(improved & active, val_loss, best_val)
                        bad_epochs = np.where(improved & active, 0,
                                              bad_epochs + active.astype(int))
                history.append(record)
                self.log_fn(f"[trainer] {record}")
                if rec.enabled:
                    # The full epoch record as one structured event, plus the
                    # counter snapshot and process stats — the per-epoch
                    # heartbeat a dashboard tails.
                    rec.emit(make_event("epoch", "epoch_record", data=record,
                                        epoch=state.epoch - 1,
                                        step=state.global_step))
                    rec.flush_counters(epoch=state.epoch - 1,
                                       step=state.global_step)
                    rec.process_stats(epoch=state.epoch - 1,
                                      step=state.global_step)
                # Resolve stopping BEFORE the end-of-epoch checkpoint so the
                # saved early-stop state (incl. the updated active mask) is the
                # one the next epoch would train under.
                stop_now = False
                if val_loader is not None:
                    if R is None:
                        stop_now = bad_epochs >= self.patience
                    else:
                        stopping = active & (bad_epochs >= self.patience)
                        if stopping.any():
                            active = active & ~stopping
                            self.log_fn(
                                f"[trainer] replicas "
                                f"{np.flatnonzero(stopping).tolist()} early-stop "
                                f"at epoch {state.epoch} "
                                f"({int(active.sum())}/{R} still training)")
                        stop_now = not active.any()
                snapshot_early_stop()
                if self.ckpt:
                    # End-of-epoch: loader cursor is at the next epoch's start,
                    # so the saved accumulators are a fresh epoch's (None).
                    with rec.span("checkpoint", step=state.global_step):
                        self._save(state, train_loader, history=history)
                if stop_now:
                    self.log_fn(f"[trainer] early stop at epoch {state.epoch}"
                                if R is None else
                                f"[trainer] all replicas stopped at epoch "
                                f"{state.epoch}")
                    break
            self._finish(engine, state)
            return history
        finally:
            if profile is not None:
                # idempotent: a window still open past the last trained step
                # (or an exception inside it) is flushed here
                profile.close(state.global_step)
            if preempt is not None:
                preempt.restore()

    def evaluate(self, model, params, loader, per_rank: bool = False,
                 replicas: Optional[int] = None):
        """Stream ``loader`` through the cached compiled eval step.

        Off-mesh with ``chunk_batches > 1``, eval batches ride the same
        chunked ``DevicePrefetcher`` + scanned step as training (one jit
        dispatch per chunk, metric state as the scan carry) instead of one
        dispatch per batch. With ``replicas=R``, ``params`` must be
        R-stacked and every returned metric is a length-R list.
        """
        metrics, eval_step, eval_chunk_step = self._get_eval_step(model,
                                                                  replicas)
        m_state = None
        if self.mesh is None and self.chunk_batches > 1:
            for chunk, _, _ in DevicePrefetcher(
                    loader, chunk_batches=self.chunk_batches,
                    recorder=self.recorder):
                if m_state is None:
                    m_state = metrics.init_state(chunk["positions"].shape[2],
                                                 replicas=replicas)
                m_state = eval_chunk_step(params, m_state, chunk)
        else:
            # On a mesh, shard full eval batches over the data axes so
            # validation scales with the mesh; only a batch the data axes do
            # not divide (the drop_last=False tail) falls back to
            # replication. (Chunk mode takes one fixed sharding, which the
            # odd-shaped tail chunk could not satisfy — so mesh eval stays
            # on the per-batch path.)
            device = None
            if self.mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec

                from repro.distrib.shardings import (batch_spec,
                                                     data_parallel_size)

                dp = data_parallel_size(self.mesh)
                split = NamedSharding(self.mesh, batch_spec(self.mesh,
                                                            extra_dims=0))
                replicated = NamedSharding(self.mesh, PartitionSpec())

                def device(batch):
                    rows = next(iter(batch.values())).shape[0]
                    return split if rows % dp == 0 else replicated
            for batch, _ in DevicePrefetcher(loader, device=device,
                                             recorder=self.recorder):
                if m_state is None:
                    m_state = metrics.init_state(batch["positions"].shape[1],
                                                 replicas=replicas)
                m_state = eval_step(params, m_state, batch)
        if m_state is None:
            raise ValueError(
                "evaluation loader produced no batches — dataset smaller than "
                "batch_size with drop_last=True? Pass drop_last=False.")
        # Metric state stayed on device for the whole pass; one blocking
        # device_get fetches every final scalar (and per-rank vector) at once.
        if replicas is None:
            finals = metrics.compute(m_state)
            per = metrics.compute_per_rank(m_state) if per_rank else None
        else:
            finals = jax.vmap(metrics.compute)(m_state)
            per = (jax.vmap(metrics.compute_per_rank)(m_state)
                   if per_rank else None)
        finals, per = jax.device_get((finals, per))
        if replicas is None:
            out = {k: float(v) for k, v in finals.items()}
        else:
            out = {k: np.asarray(v, np.float64).tolist()
                   for k, v in finals.items()}
        if per_rank:
            out["per_rank"] = {k: np.asarray(v).tolist()
                               for k, v in per.items()}
        return out

    def test(self, model, test_loader, params=None, per_rank: bool = True,
             replicas="auto"):
        """Evaluate on the test split. With no explicit ``params``, the
        trainer's own final state is used (R-stacked on a sweep trainer, so
        metrics come back as length-R lists). Explicitly passed ``params``
        are treated as a single unstacked run — the ``select_replica``
        workflow — unless ``replicas=R`` says otherwise."""
        if replicas == "auto":
            replicas = self.replicas if params is None else None
        if params is None:
            params = self._final_state.params
        return self.evaluate(model, params, test_loader, per_rank=per_rank,
                             replicas=replicas)

    # -- internals -------------------------------------------------------------------
    def _finish(self, engine: TrainEngine, state: TrainState) -> None:
        state.params, state.opt_state = engine.unplace(state.params,
                                                       state.opt_state)
        self._final_state = state

    def _save(self, state: TrainState, loader, loader_state=None,
              epoch_accum=None, history=None):
        if loader_state is None:
            get_state = getattr(loader, "state_dict", lambda: None)
            loader_state = get_state()
        self.ckpt.save(state.global_step,
                       {"params": state.params, "opt_state": state.opt_state},
                       aux={"epoch": state.epoch, "global_step": state.global_step,
                            "loader": loader_state,
                            "early_stop": getattr(self, "_early_stop_aux",
                                                  None),
                            "epoch_accum": epoch_accum,
                            "history": history or []})
