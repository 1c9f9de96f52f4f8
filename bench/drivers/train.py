"""Training driver: a configuration trained through the program's own path,
``Trainer.train`` -> ``TrainEngine.step`` -> ``DevicePrefetcher`` ->
``StreamingClickLogLoader`` over a session store ingested from the seed.

Set-up, in order: ingest the mix's batches into a store (the program's
``ingest_chunks``, codec ``auto``); build one ``Trainer``; drive it from its
start through its first dispatch (``chunk_batches`` steps, which compiles or
loads the chunk program) and read the state that dispatch left; then hand
the same trainer and state to a second ``train`` call, whose first dispatch
is still set-up (``train`` builds its engine per call, so its program comes
from the compile cache there). The window opens when that dispatch's losses
have been fetched and closes when the losses of the last step are fetched:
``train_sessions_per_s`` is every session of every step in between over
that time. The store's batches are replayed back to back, pass after pass,
inside one trainer epoch, so the window holds no epoch boundary.

After the window the program's state is freed and the plain reference
follows the steps of both calls' first dispatches on the same batches (see
``harness/follow.py``): the set-up dispatch from the trainer's start, then
the window call's own, which resumes from the state the first handed over.
The numbers compared: ``loss``, the widest relative gap of those steps'
losses; ``grad``, the widest per-leaf gap of the first moment's norm after
the first dispatch (the gradients as the optimizer got them); ``step``, the
widest per-leaf gap of the norm of the parameters' change after it.
"""
from __future__ import annotations

import gc
import math
import shutil
import time
from typing import Callable, Dict, List

import numpy as np

from harness import follow as follow_mod
from harness import program
from harness.cell import jax_seed
from harness.compare import counted_leaves, judge, leaf_gap, max_rel_gap
from harness.device import memory_peak_bytes
from harness.record import Record
from harness.sessions import SessionSource
from harness.tap import Tap


class Replay:
    """The store's batches back to back, loader pass after loader pass, as
    one trainer epoch. Before each dispatch's first batch it asks
    ``stop(n)`` (``n`` batches so far) whether to end the epoch; it keeps
    copies of the first ``keep`` batches it hands out."""

    def __init__(self, loader, chunk: int, stop: Callable[[int], bool],
                 keep: int = 0):
        self.loader = loader
        self.batch_size = loader.batch_size
        self.drop_last = True
        self.chunk = chunk
        self.stop = stop
        self.keep = keep
        self.kept: List[Dict[str, np.ndarray]] = []

    def __iter__(self):
        n = 0
        while True:
            it = iter(self.loader)
            try:
                while True:
                    if n % self.chunk == 0 and self.stop(n):
                        return
                    try:
                        batch = next(it)
                    except StopIteration:
                        break
                    if n < self.keep:
                        self.kept.append({k: np.array(v)
                                          for k, v in batch.items()})
                    yield batch
                    n += 1
            finally:
                it.close()


class Window:
    """The measured window, opened by the first fetched losses of the
    window's ``train`` call; optionally a profiler trace over a few of its
    dispatches."""

    def __init__(self, first_step: int, chunk: int, seconds: float,
                 trace_dir, trace_from: int, trace_chunks: int):
        self.first_step = first_step
        self.chunk = chunk
        self.seconds = seconds
        self.t0 = None
        self.trace_dir = trace_dir
        self.trace_from = trace_from
        self.trace_chunks = trace_chunks
        self.tracing = False
        self.traced = False

    def over(self, n: int) -> bool:
        return self.t0 is not None and time.perf_counter() >= self.t0 + self.seconds

    def on_event(self, event) -> None:
        step = event.get("step", -1)
        if step < self.first_step:
            return
        if self.t0 is None:
            self.t0 = time.perf_counter()
        if self.trace_dir is None or (step + 1) % self.chunk:
            return
        drained = (step + 1 - self.first_step) // self.chunk
        if drained == self.trace_from and not self.traced:
            import jax

            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(str(self.trace_dir),
                                     profiler_options=options)
            self.tracing = self.traced = True
        elif drained == self.trace_from + self.trace_chunks:
            self.stop_trace()

    def stop_trace(self) -> None:
        if self.tracing:
            import jax

            self.tracing = False
            jax.profiler.stop_trace()


def ingest(cell, seed: int, log) -> str:
    from repro.data.ingest import ingest_chunks

    cfg, traffic = cell.config, cell.traffic
    store = cell.out / "store"
    shutil.rmtree(store, ignore_errors=True)
    source = SessionSource(traffic["sessions"], seed)
    batch = int(cfg["batch"])
    t0 = time.perf_counter()
    ingest_chunks(lambda c: source.chunk(c, batch),
                  [batch] * int(traffic["batches"]), str(store),
                  shard_rows=int(traffic["shard_rows"]),
                  codec=traffic["codec"], workers=1, seed=jax_seed(seed))
    log(f"ingested {traffic['batches']} x {batch} sessions in "
        f"{time.perf_counter() - t0:.2f}s")
    return str(store)


def program_readings(net, seed32: int, state, paths) -> Dict[str, Dict]:
    """Per-leaf norms of the parameters' change and of the first moment,
    from the state the first dispatch left."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def read(params, opt_state):
        p = program.leaves(params)
        p0 = program.leaves(net.init(jax.random.PRNGKey(seed32)))
        mu = program.moments(opt_state, "mu", paths)
        return ({k: jnp.linalg.norm((p[k] - p0[k]).ravel()) for k in paths},
                {k: jnp.linalg.norm(mu[k].astype(jnp.float32).ravel())
                 for k in paths})

    step, mu = jax.device_get(read(state.params, state.opt_state))
    return {"step": {k: float(v) for k, v in step.items()},
            "grad": {k: float(v) for k, v in mu.items()}}


def run(cell, t_start: float, log) -> Record:
    from repro.data import StreamingClickLogLoader
    from repro.obs import Recorder, get_recorder, set_recorder
    from repro.train import Trainer

    cfg, traffic = cell.config, cell.traffic
    seed32 = jax_seed(cell.seed)
    chunk = int(traffic["chunk_batches"])
    batch = int(cfg["batch"])
    ref = cell.reference()
    store = ingest(cell, cell.seed, log)

    net = program.build_model(cfg)
    paths = list(ref.LEAVES)
    mesh = None
    if traffic.get("data_parallel"):
        from repro.launch.mesh import make_data_parallel_mesh

        mesh = make_data_parallel_mesh(cell.chips)
        log(f"data-parallel mesh {dict(mesh.shape)}")

    tap = Tap()
    recorder = Recorder(sinks=[tap])
    previous = get_recorder()
    set_recorder(recorder)   # the loader's spans come here too
    trainer = Trainer(optimizer=program.optimizer(cfg), epochs=1, seed=seed32,
                      chunk_batches=chunk, mesh=mesh, recorder=recorder,
                      obs_every=1, log_fn=log)
    loader = StreamingClickLogLoader(store, batch_size=batch, seed=seed32)
    try:
        # Set-up 1: the first dispatch, from the trainer's own start.
        first = Replay(loader, chunk, stop=lambda n: n >= chunk, keep=chunk)
        trainer.train(net, first)
        got = {"losses": [e["value"] for e in sorted(tap.steps,
                                                     key=lambda e: e["step"])]}
        got.update(program_readings(net, seed32, trainer.final_state, paths))
        log(f"first dispatch: losses {got['losses']}")

        # Set-up 2 and the window: the same trainer, one more epoch.
        trace_dir = None
        if cell.trace:
            trace_dir = cell.out / "trace"
            shutil.rmtree(trace_dir, ignore_errors=True)
        window = Window(first_step=chunk, chunk=chunk, seconds=cell.seconds,
                        trace_dir=trace_dir,
                        trace_from=int(traffic["trace_from"]),
                        trace_chunks=int(traffic["trace_chunks"]))
        tap.hook = window.on_event
        trainer.epochs += 1
        timed = Replay(loader, chunk, stop=window.over, keep=chunk)
        try:
            trainer.train(net, timed, state=trainer.final_state)
        finally:
            window.stop_trace()
        tap.hook = None
    finally:
        loader.close()
        set_recorder(previous)

    events = sorted((e for e in tap.steps if e["step"] >= chunk),
                    key=lambda e: e["step"])
    opening = [e for e in events if e["step"] < 2 * chunk]
    inside = [e for e in events if e["step"] >= 2 * chunk]
    if not opening or not inside:
        raise RuntimeError(f"the window ran {len(inside)} steps: too short "
                           "for a measurement")
    t0 = max(e["at"] for e in opening)
    t1 = max(e["at"] for e in inside)
    steps = len(inside)
    seconds = t1 - t0
    spans = [s for s in tap.spans if t0 <= s["end"] <= t1]
    failed = sum(1 for e in inside if not math.isfinite(e["value"]))
    log(f"window: {steps} steps in {seconds:.3f}s")
    peak = memory_peak_bytes(cell.chips)

    # The program's state goes before the reference runs.
    kept = first.kept + timed.kept
    got["losses"] = [e["value"] for e in sorted(tap.steps,
                                                key=lambda e: e["step"])
                     ][:len(kept)]
    del trainer, first, timed, loader
    gc.collect()
    want = follow_mod.follow(ref, cfg, kept, read_at=chunk)
    counted = counted_leaves(want["grad1"])
    values = {"loss": max_rel_gap(got["losses"], want["losses"]),
              "grad": leaf_gap(got["grad"], want["mu"]),
              "step": leaf_gap(got["step"], want["step"], counted)}
    log(f"reference losses {want['losses']}")
    log(f"program grad-moment norms {got['grad']}, reference {want['mu']}")
    log(f"program change norms {got['step']}, reference {want['step']}; "
        f"counted leaves {counted}")

    return Record(
        cell=cell,
        end_to_end={"setup_s": t0 - t_start,
                    "train_sessions_per_s": steps * batch / seconds},
        attempted=steps, failed=failed, checks=judge(values, cell.limits),
        memory_peak_bytes=peak, window_s=seconds, steps=steps, spans=spans,
        steps_per_execution=chunk, trace_dir=trace_dir)
