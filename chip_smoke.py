#!/usr/bin/env python3
"""Chip smoke test: the paper's click-model deployment, train -> serve, on TPU.

    python chip_smoke.py             # one chip: the whole main path
    python chip_smoke.py --chips 4   # four chips: the data-parallel path only

One chip, in order:

1. device check: the platform must be ``tpu``, and no ``CLAX_KERNEL_IMPL*``
   variable may reroute a kernel;
2. ingest a seeded synthetic log into a session store (codec ``auto``);
3. kernels the trained models leave out, against ``ref`` at real width:
   ``embedding_bag`` over a recsys field (4096 bags of 10 ids into 2^20
   rows of 16, float32 and bfloat16 tables), and ``examination_nll`` at
   B=65,536, K=10 where most clicks are likely (p > 1/2 is the kernel's
   other branch; the smoke's DBN stays near p = 0.1);
4. train DBN at the ``clax_baidu`` width (2^31 ids hashed 10x into two
   214.7M-row tables, B=65,536, K=10) through Trainer -> TrainEngine ->
   StreamingClickLogLoader -> DevicePrefetcher, with a jax.profiler window
   over one chunk. The first batch's loss must match the ``ref`` kernels to
   1e-5, every loss must be finite, the compiled chunk must hold the Pallas
   ``examination_nll`` kernel (``tpu_custom_call``) and the trace a TPU plane;
5. the same checks for a CTR-family model (DocumentCTR, ``session_nll``);
6. train UBM at the same width (one such table and a (K, K) examination
   table; its loss takes no kernel): every loss finite, and the first
   batch's loss equal to the model's own loss on that batch to 1e-5;
7. serve the trained DBN through repro.serve (ModelRegistry -> warmup ->
   ServeEngine, wall clock): every request answered on ``primary``, no model
   error, click probabilities equal to ``predict_clicks`` under ``ref``.

``--chips 4`` trains the same DBN steps on one device and on a 4-device
``data`` mesh; their per-step losses must agree to 1e-5.

Everything runs in this process: a chip belongs to one process, so nothing
here starts a child. Compile, throughput and memory figures are printed on
the way; the throughput is a smoke figure, not a benchmark. Any failed check
exits non-zero. The last line of stdout, printed only when every phase
passed, is ``{"ok": true, "device": {"platform", "kind", "count"}}``. Work
files go to ``.chip_smoke/`` next to this script.
"""
from __future__ import annotations

import argparse
import glob
import json
import math
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(ROOT, ".chip_smoke")
SEED = 0
CHUNK = 4          # optimizer steps per dispatch
TRAIN_CHUNKS = 3   # dispatches per epoch; the first one compiles
N_REQUESTS = 48
RTOL = 1e-5
# A recsys EmbeddingBag field: B bags of L ids over N rows of D.
BAG_SHAPE = (4096, 10, 1 << 20, 16)
# What the chip leaves behind: a Mosaic kernel in the compiled program, and
# the device's own plane in the profiler trace.
KERNEL_MARK = "tpu_custom_call"
DEVICE_PLANE = "/device:TPU:"


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        print(f"[smoke] FAIL: {msg}", file=sys.stderr, flush=True)
        raise SystemExit(1)


def close(got: float, want: float) -> bool:
    return abs(got - want) <= RTOL * abs(want)


# -- phases -------------------------------------------------------------------

def device_check(chips: int) -> dict:
    rerouted = sorted(k for k in os.environ if k.startswith("CLAX_KERNEL_IMPL"))
    check(not rerouted, f"{rerouted} reroute kernels off their chip default")
    import jax

    devices = jax.devices()
    dev = devices[0]
    log(f"platform={dev.platform} device_kind={dev.device_kind} "
        f"count={len(devices)}")
    check(dev.platform == "tpu", f"no TPU: JAX found {dev.platform!r}")
    check(len(devices) >= chips, f"--chips {chips} but {len(devices)} found")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def check_embedding_bag() -> None:
    """The Pallas ``embedding_bag`` against its ref: every output within
    RTOL of the sum of |weight * row| it adds up."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro import kernels

    B, L, N, D = BAG_SHAPE
    k_table, k_ids, k_pad, k_w = jax.random.split(jax.random.PRNGKey(SEED), 4)
    table = jax.random.normal(k_table, (N, D), jnp.float32)
    ids = jnp.where(jax.random.uniform(k_pad, (B, L)) < 0.1, -1,
                    jax.random.randint(k_ids, (B, L), 0, N))  # -1: padding
    weights = jax.random.uniform(k_w, (B, L), jnp.float32, -1.0, 1.0)

    def bag(impl):
        return jax.jit(lambda t, i, w: kernels.embedding_bag(t, i, w,
                                                             impl=impl))

    for dtype in (jnp.float32, jnp.bfloat16):
        t = table.astype(dtype)
        compiled = bag("pallas").lower(t, ids, weights).compile()
        check(KERNEL_MARK in compiled.as_text(),
              f"embedding_bag {dtype.__name__}: no {KERNEL_MARK} in the "
              "compiled program")
        got = np.asarray(compiled(t, ids, weights))
        with jax.default_matmul_precision("highest"):  # the ref's einsum
            want = np.asarray(bag("ref")(t, ids, weights))
            scale = np.asarray(bag("ref")(jnp.abs(t), ids, jnp.abs(weights)))
        diff = np.abs(got - want)
        err = float(np.max(diff / np.maximum(scale, np.finfo(np.float32).tiny)))
        log(f"embedding_bag {dtype.__name__} {B}x{L} bags over {N}x{D}: max "
            f"|pallas - ref| / sum|w row| = {err:.3g}")
        check(got.shape == (B, D) and bool(np.all(diff <= RTOL * scale)),
              f"embedding_bag {dtype.__name__}: pallas off ref by {err}")


def check_examination_nll_near_one(batch: int) -> None:
    """The Pallas ``examination_nll`` against its ref on sessions where
    most click probabilities lie above 1/2."""
    import jax
    import jax.numpy as jnp
    from repro import kernels
    from repro.core.recursions import conditional_examination_odds

    K = 10
    k_x, k_c, k_f = jax.random.split(jax.random.PRNGKey(SEED + 1), 3)
    x = 3.0 + jax.random.normal(k_x, (batch, K))
    clicks = (jax.random.uniform(k_c, (batch, K)) < 0.5).astype(jnp.float32)
    mask = jnp.ones((batch, K), jnp.float32)
    u = jax.random.uniform(k_f, (4, batch, K))
    # Near-certain survival, rare death and sure-ish resets keep the death
    # odds small, so P(C=1) stays close to sigmoid(x).
    factors = (0.95 + 0.05 * u[0], 0.02 * u[1], 0.9 + 0.1 * u[2], 0.1 * u[3])
    r = conditional_examination_odds(clicks, *factors)
    e = jnp.exp(-jnp.abs(x))
    log_p = jnp.minimum(x, 0.0) - jnp.log1p(r + e + r * e)
    share = float(jnp.mean(log_p > -math.log(2.0)))
    losses = {impl: float(jax.jit(
        lambda *a, impl=impl: kernels.examination_nll(*a, impl=impl))(
            x, clicks, mask, *factors)) for impl in ("pallas", "ref")}
    log(f"examination_nll {batch}x{K}, {share:.3f} of P(C=1) > 1/2: loss "
        f"{losses['pallas']!r}, ref {losses['ref']!r}")
    check(share > 0.5, f"examination_nll: only {share} of P(C=1) > 1/2")
    check(close(losses["pallas"], losses["ref"]),
          f"examination_nll near one: {losses['pallas']} != ref "
          f"{losses['ref']}")


def ingest(batch: int) -> str:
    from repro.data import SyntheticConfig, ingest_synthetic

    cfg = SyntheticConfig(n_sessions=batch * CHUNK * TRAIN_CHUNKS,
                          n_queries=100_000, docs_per_query=20, positions=10,
                          behavior="dbn", seed=SEED)
    store = os.path.join(OUT, "store")
    t0 = time.perf_counter()
    ingest_synthetic(cfg, store, chunk_sessions=batch, shard_rows=2 * batch,
                     codec="auto")
    log(f"ingested {cfg.n_sessions} sessions in "
        f"{time.perf_counter() - t0:.2f}s -> {store}")
    return store


def first_batch(store: str, batch: int):
    """The first batch the training loader yields (same store and seed)."""
    import jax
    from repro.data import StreamingClickLogLoader

    loader = StreamingClickLogLoader(store, batch_size=batch, seed=SEED)
    it = iter(loader)
    host = next(it)
    it.close()
    loader.close()
    return host, jax.device_put(host)


def loss_under(impl: str, model, params, batch) -> float:
    import jax
    from repro.kernels import override_impl

    with override_impl(impl):  # resolved while this fresh jit traces
        return float(jax.jit(lambda p, b: model.compute_loss(p, b))(
            params, batch))


class CompileClock:
    """Sums JAX's trace, lower and compile durations while it is entered."""

    def __init__(self):
        self.seconds = 0.0

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on_event)

    def _on_event(self, event, secs, **_):
        if event.startswith("/jax/core/compile/"):
            self.seconds += secs

    def lap(self) -> float:
        secs, self.seconds = self.seconds, 0.0
        return secs


def model(kind: str):
    """A click model at the clax_baidu width (config/clax_baidu.py)."""
    from repro.configs.clax_baidu import make_model

    return make_model(kind)


def train(kind: str, store: str, batch: int, epochs: int = 1, mesh=None,
          profile_dir=None):
    """Returns (trainer, model, per-step losses, epoch history)."""
    from repro import optim
    from repro.data import StreamingClickLogLoader
    from repro.obs import MemorySink, Recorder
    from repro.train import Trainer

    net = model(kind)
    sink = MemorySink()
    trainer = Trainer(optimizer=optim.adamw(3e-3, weight_decay=1e-4),
                      epochs=epochs, seed=SEED, chunk_batches=CHUNK,
                      mesh=mesh, recorder=Recorder(sinks=[sink]),
                      log_fn=lambda msg: log(f"{kind}: {msg}"),
                      profile_steps=((CHUNK, 2 * CHUNK) if profile_dir
                                     else None),
                      profile_dir=profile_dir)
    loader = StreamingClickLogLoader(store, batch_size=batch, seed=SEED)
    history = trainer.train(net, loader)
    loader.close()
    losses = [e["value"] for e in sink.by_name("train_step")]
    check(len(losses) == epochs * CHUNK * TRAIN_CHUNKS,
          f"{kind}: {len(losses)} train steps reported")
    check(all(math.isfinite(x) for x in losses),
          f"{kind}: non-finite loss in {losses}")
    return trainer, net, losses, history


def check_kernel_ran(kind: str, trainer, store: str, batch: int,
                     kernel: str) -> None:
    """The chunk program the trainer dispatched holds a Mosaic kernel."""
    import jax

    from repro.core.parameterization import relayout_scalar_tables
    from repro.data import DevicePrefetcher, StreamingClickLogLoader

    loader = StreamingClickLogLoader(store, batch_size=batch, seed=SEED)
    for chunk, _, _ in DevicePrefetcher(loader, chunk_batches=CHUNK):
        break
    loader.close()
    engine, state = trainer.engine, trainer.final_state
    # The shapes the chunk ran on: the final state is in the model's own.
    params, opt_state = jax.eval_shape(
        lambda t: relayout_scalar_tables(engine.model, t, flat=True),
        (state.params, state.opt_state))
    text = engine.compiled(params, opt_state, chunk).as_text()
    check(KERNEL_MARK in text,
          f"{kind}: no {KERNEL_MARK} in the compiled chunk: {kernel} did "
          "not run as a kernel")
    log(f"{kind}: compiled chunk runs {kernel} as a Pallas kernel")


def check_first_loss(kind: str, net, losses, device_batch) -> None:
    import jax

    params0 = net.init(jax.random.PRNGKey(SEED))  # what Trainer starts at
    want = loss_under("ref", net, params0, device_batch)
    log(f"{kind}: first-batch loss {losses[0]!r}, ref {want!r}")
    check(close(losses[0], want),
          f"{kind}: first-batch loss {losses[0]} != ref {want}")


def check_trace(profile_dir: str) -> None:
    import jax

    files = glob.glob(os.path.join(profile_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    check(len(files) == 1, f"expected one .xplane.pb, found {files}")
    planes = jax.profiler.ProfileData.from_file(files[0]).planes
    busy = {p.name: sum(1 for line in p.lines for _ in line.events)
            for p in planes if p.name.startswith(DEVICE_PLANE)}
    check(any(busy.values()), f"no {DEVICE_PLANE} plane with events in "
          f"{files[0]}: {busy}")
    log(f"trace {files[0]}: events per device plane {busy}")


def serve(net, params, host_batch) -> None:
    import jax
    import numpy as np
    from repro.kernels import override_impl
    from repro.obs import MemorySink, Recorder
    from repro.serve import (OK, ModelRegistry, ServeEngine, ServeRequest,
                             WallClock)

    registry = ModelRegistry(buckets=(1, 4, 16, 64))
    registry.add("dbn", net, params, n_pairs=1 << 31)
    t0 = time.perf_counter()
    registry.warmup()
    log(f"serve: warmed {len(registry.buckets)} buckets x 2 tiers in "
        f"{time.perf_counter() - t0:.2f}s")
    rows = {k: np.asarray(v[:N_REQUESTS]) for k, v in host_batch.items()}
    trace = [ServeRequest(request_id=i, model="dbn",
                          positions=rows["positions"][i],
                          query_doc_ids=rows["query_doc_ids"][i],
                          mask=rows["mask"][i], deadline_s=2.0,
                          arrival_s=0.002 * i)
             for i in range(N_REQUESTS)]
    sink = MemorySink()
    engine = ServeEngine(registry, clock=WallClock(),
                         recorder=Recorder(sinks=[sink]))
    results = sorted(engine.run_trace(trace), key=lambda r: r.request_id)
    summary = engine.summary(results)
    log(f"serve: {summary}")
    bad = [(r.request_id, r.status, r.reason, r.tier) for r in results
           if r.status != OK or r.tier != "primary"]
    check(len(results) == N_REQUESTS and not bad,
          f"requests not answered on primary: {bad}")
    check(engine.stats["serve.degraded"] == 0,
          f"serve.degraded = {engine.stats['serve.degraded']}")
    check(engine.stats["serve.model_errors"] == 0
          and not sink.by_name("model_error"),
          f"model errors: {sink.by_name('model_error')}")
    with override_impl("ref"):
        want = jax.jit(lambda p, b: net.predict_clicks(p, b))(
            params, {k: jax.device_put(v) for k, v in rows.items()})
    got = np.exp(np.stack([r.log_ctr for r in results]))
    want = np.exp(np.asarray(want))
    mask = rows["mask"].astype(bool)
    err = float(np.max(np.abs(got - want)[mask] / want[mask]))
    log(f"serve: click probabilities vs ref predict_clicks: max rel err "
        f"{err:.3g}")
    check(err <= RTOL, f"served click probabilities off ref by {err}")


def peak_bytes() -> int:
    import jax

    stats = jax.devices()[0].memory_stats()
    check(bool(stats) and "peak_bytes_in_use" in stats,
          f"the backend reports no peak_bytes_in_use: {stats}")
    return int(stats["peak_bytes_in_use"])


def one_chip(store: str, batch: int, clock: CompileClock) -> None:
    check_embedding_bag()
    check_examination_nll_near_one(batch)
    log(f"kernels: compile {clock.lap():.2f}s")
    host_batch, device_batch = first_batch(store, batch)

    # DBN: two epochs, the first traced over its second chunk and paying
    # the compile, the second untraced and warm.
    profile_dir = os.path.join(OUT, "profile")
    clock.lap()
    trainer, dbn, losses, history = train("dbn", store, batch, epochs=2,
                                          profile_dir=profile_dir)
    log(f"dbn: compile {clock.lap():.2f}s; losses {losses}")
    sessions, seconds = CHUNK * TRAIN_CHUNKS * batch, history[-1]["seconds"]
    log(f"dbn: warm epoch of {sessions} sessions in {seconds:.3f}s = "
        f"{sessions / seconds:.0f} sessions/s (smoke figure, not a "
        "benchmark)")
    check_trace(profile_dir)
    check_kernel_ran("dbn", trainer, store, batch, "examination_nll")
    check_first_loss("dbn", dbn, losses, device_batch)
    params = trainer.final_state.params
    trained = loss_under("pallas", dbn, params, device_batch)
    want = loss_under("ref", dbn, params, device_batch)
    log(f"dbn: trained first-batch loss {trained!r}, ref {want!r}")
    check(close(trained, want), f"dbn: trained loss {trained} != ref {want}")
    del trainer
    log(f"dbn: peak_bytes_in_use {peak_bytes()}")

    clock.lap()
    trainer, ctr, losses, _ = train("dctr", store, batch)
    log(f"dctr: compile {clock.lap():.2f}s; losses {losses}")
    check_kernel_ran("dctr", trainer, store, batch, "session_nll")
    check_first_loss("dctr", ctr, losses, device_batch)
    del trainer

    clock.lap()
    trainer, ubm, losses, _ = train("ubm", store, batch)
    log(f"ubm: compile {clock.lap():.2f}s; losses {losses}")
    check_first_loss("ubm", ubm, losses, device_batch)
    del trainer

    clock.lap()
    serve(dbn, params, host_batch)
    log(f"serve: compile {clock.lap():.2f}s")


def four_chips(store: str, batch: int) -> None:
    import numpy as np
    from repro.launch.mesh import make_data_parallel_mesh

    one = train("dbn", store, batch)[2]
    mesh = make_data_parallel_mesh()
    log(f"data-parallel mesh {dict(mesh.shape)}")
    dp = train("dbn", store, batch, mesh=mesh)[2]
    rel = np.abs(np.asarray(dp) - np.asarray(one)) / np.abs(np.asarray(one))
    log(f"dbn: one device {one}")
    log(f"dbn: {mesh.shape['data']}-way data-parallel {dp}")
    log(f"dbn: per-step max rel diff {float(rel.max()):.3g}")
    check(float(rel.max()) <= RTOL,
          f"data-parallel losses off single-device by {float(rel.max())}")


def run(chips: int, batch: int) -> dict:
    """Every phase for ``chips``; returns the device it ran on."""
    device = device_check(chips)
    from repro.launch.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    store = ingest(batch)
    if chips == 4:
        four_chips(store, batch)
    else:
        with CompileClock() as clock:
            one_chip(store, batch, clock)
    log(f"peak_bytes_in_use {peak_bytes()}")
    return device


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the data-parallel path and the "
                         "single-device run it is compared with")
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.configs.clax_baidu import SHAPES
    except ImportError as e:
        check(False, f"no repro package under {ROOT}/src: {e}")
    shutil.rmtree(OUT, ignore_errors=True)
    os.makedirs(OUT)
    # libtpu's own logs stay with this run's files
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(OUT, "tpu_logs"))
    device = run(args.chips, SHAPES["train_batch"]["batch"])
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
