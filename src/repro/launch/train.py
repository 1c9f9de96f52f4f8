"""Production training launcher for CLAX click models.

In-memory path (log must fit in host RAM):

    PYTHONPATH=src python -m repro.launch.train --model ubm \
        [--sessions 200000] [--epochs 20] [--ckpt-dir ckpts/ubm] \
        [--compression hash --ratio 10] [--host-id 0 --host-count 1]

Out-of-core path — ingest once into a sharded on-disk session store, then
stream batches from it (peak data memory is O(chunk + shard), so the log can
be far larger than RAM):

    PYTHONPATH=src python -m repro.launch.train --model ubm \
        --store-dir /data/clicklog --ingest --sessions 100000000 \
        [--chunk-sessions 1000000] [--shard-rows 1000000] \
        [--ingest-workers 8] [--store-codec auto]

``--ingest-workers N`` fans chunk synthesis + shard writing over N worker
processes (byte-identical output to serial); ``--store-codec auto``
compresses each column per shard (bitpack/zlib/raw, chosen from the bytes).

A directory that already holds ingested ``train/val/test`` stores is reused
when ``--ingest`` is omitted; the model is sized from the ``SyntheticConfig``
recorded in the store manifest, so train-from-store needs no generation
flags at all.

Training-engine knobs (see README "Training engine"): ``--chunk-batches N``
fuses N optimizer steps into one scan-jitted dispatch, ``--data-parallel``
shards the batch axis over all local devices, ``--sparse-tables`` switches
embedding tables to lazy-AdamW scatter updates. Sweep knobs (README
"Sweeps"): ``--replicas R`` trains R seed/lr variants in one vmapped run,
with ``--replica-seeds`` / ``--replica-lrs`` setting the per-replica knobs.

Fault tolerance (see README "Fault tolerance"): ``--max-restarts N``
supervises training in a child process and relaunches it after crashes
(resuming from ``--ckpt-dir``), ``--verify-store`` crc-checks shards at
read time with ``--corrupt-shards raise|skip`` deciding policy,
``--nonfinite-guard`` skips non-finite optimizer steps on-device, and
``--fault-kill-at-step`` arms a chaos-test kill switch.

Single-host here; at pod scale the same entry point runs per host with
--host-id/--host-count carving the data shard (rows of the in-memory dict,
or whole store shards for the streaming path) and jax.distributed
initializing the mesh — the dry-run (repro/launch/dryrun.py) proves the
sharded program compiles for the production meshes.
"""
from __future__ import annotations

import argparse
import os
import signal
import sys

from repro import optim
from repro.core import (Compression, EmbeddingParameterConfig, MODEL_REGISTRY)
from repro.data import (ClickLogLoader, SessionStore, StreamingClickLogLoader,
                        SyntheticConfig, generate_click_log, ingest_synthetic,
                        split_sessions)
from repro.launch.compile_cache import enable_compile_cache
from repro.train import Trainer


def _synthetic_config(args) -> SyntheticConfig:
    return SyntheticConfig(n_sessions=args.sessions,
                           n_queries=max(args.sessions // 100, 1),
                           docs_per_query=20, positions=10, behavior="dbn",
                           seed=args.seed)


def make_loaders(args):
    """Returns (train_loader, val_loader, test_loader, data_cfg) where
    data_cfg is the SyntheticConfig describing the data (for the store path,
    reconstructed from the manifest metadata, so models are sized against
    what was actually ingested)."""
    if args.store_dir:
        if args.ingest:
            cfg = _synthetic_config(args)
            chunk = args.chunk_sessions or max(args.sessions // 20, 1)
            print(f"[train] ingesting {cfg.n_sessions} sessions into "
                  f"{args.store_dir} (chunk={chunk}, "
                  f"shard_rows={args.shard_rows}, "
                  f"codec={args.store_codec}, "
                  f"workers={args.ingest_workers})")
            ingest_synthetic(cfg, args.store_dir, chunk_sessions=chunk,
                             shard_rows=args.shard_rows,
                             splits={"train": 0.8, "val": 0.1, "test": 0.1},
                             codec=args.store_codec,
                             workers=args.ingest_workers)
        train_store = SessionStore(os.path.join(args.store_dir, "train"))
        syn = train_store.metadata.get("synthetic_config")
        if syn is None:
            raise SystemExit(
                f"{args.store_dir}/train has no synthetic_config metadata — "
                "was it ingested with --ingest / ingest_synthetic?")
        data_cfg = SyntheticConfig(**syn)
        train = StreamingClickLogLoader(train_store, batch_size=args.batch,
                                        seed=args.seed, host_id=args.host_id,
                                        host_count=args.host_count,
                                        window_rows=args.window_rows,
                                        verify_checksums=args.verify_store,
                                        corrupt_policy=args.corrupt_shards,
                                        io_retries=args.io_retries)
        val = StreamingClickLogLoader(os.path.join(args.store_dir, "val"),
                                      batch_size=8192, shuffle=False,
                                      drop_last=False)
        test = StreamingClickLogLoader(os.path.join(args.store_dir, "test"),
                                       batch_size=8192, shuffle=False,
                                       drop_last=False)
        return train, val, test, data_cfg

    cfg = _synthetic_config(args)
    data, _ = generate_click_log(cfg)
    train, val, test = split_sessions(data, (0.8, 0.1, 0.1), seed=args.seed)
    return (ClickLogLoader(train, batch_size=args.batch, seed=args.seed,
                           host_id=args.host_id, host_count=args.host_count),
            ClickLogLoader(val, batch_size=8192, shuffle=False, drop_last=False),
            ClickLogLoader(test, batch_size=8192, shuffle=False, drop_last=False),
            cfg)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="ubm", choices=sorted(MODEL_REGISTRY))
    ap.add_argument("--sessions", type=int, default=200_000)
    ap.add_argument("--epochs", type=int, default=20)
    ap.add_argument("--batch", type=int, default=2048)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--compression", default="none",
                    choices=["none", "hash", "quotient_remainder"])
    ap.add_argument("--ratio", type=float, default=10.0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--host-id", type=int, default=0)
    ap.add_argument("--host-count", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--store-dir", default=None,
                    help="session-store directory; train via the streaming "
                         "out-of-core loader instead of in-memory arrays")
    ap.add_argument("--ingest", action="store_true",
                    help="synthesize --sessions sessions chunk-by-chunk into "
                         "--store-dir/{train,val,test} before training")
    ap.add_argument("--chunk-sessions", type=int, default=None,
                    help="ingest chunk size in sessions (default: sessions/20)")
    ap.add_argument("--shard-rows", type=int, default=1_000_000,
                    help="rows per store shard (unit of shuffle/host placement)")
    ap.add_argument("--ingest-workers", type=int, default=1,
                    help="worker processes for --ingest; each owns a "
                         "disjoint shard block per split, byte-identical "
                         "output to --ingest-workers 1")
    ap.add_argument("--store-codec", default="auto", choices=["auto", "raw"],
                    help="per-column store codec for --ingest: 'auto' picks "
                         "bitpack/zlib/raw per column per shard; 'raw' pins "
                         "the v1-byte-compatible memmap layout")
    ap.add_argument("--window-rows", type=int, default=None,
                    help="streaming read window within a shard (default: full "
                         "shard)")
    ap.add_argument("--chunk-batches", type=int, default=8,
                    help="batches fused into one scan-jitted dispatch "
                         "(1 = the historical per-batch loop, bit-exact)")
    ap.add_argument("--data-parallel", action="store_true",
                    help="shard the batch axis over all local devices "
                         "(requires --batch divisible by the device count)")
    ap.add_argument("--sparse-tables", action="store_true",
                    help="lazy-AdamW scatter updates for embedding tables: "
                         "optimizer state traffic O(unique batch rows) "
                         "instead of O(table rows)")
    ap.add_argument("--replicas", type=int, default=None,
                    help="train R independent replicas in one vmapped sweep "
                         "(R x params/opt-state memory, 1x data; one scan "
                         "dispatch advances all runs)")
    ap.add_argument("--replica-lrs", type=float, nargs="+", default=None,
                    help="one learning rate per replica (default: --lr for "
                         "all); switches the optimizer to inject_lr=True")
    ap.add_argument("--replica-seeds", type=int, nargs="+", default=None,
                    help="one init seed per replica (default: --seed + i)")
    ap.add_argument("--max-restarts", type=int, default=0,
                    help="supervise training in a child process and relaunch "
                         "it after crashes up to N times; resumes from "
                         "--ckpt-dir (required)")
    ap.add_argument("--verify-store", action="store_true",
                    help="crc32-verify every store shard's columns at read "
                         "time (streaming path)")
    ap.add_argument("--corrupt-shards", default="raise",
                    choices=["raise", "skip"],
                    help="what a corrupt train shard does under "
                         "--verify-store: fail the run, or quarantine the "
                         "shard and keep training deterministically")
    ap.add_argument("--io-retries", type=int, default=2,
                    help="transient shard-read failures retried with "
                         "exponential backoff (streaming path)")
    ap.add_argument("--nonfinite-guard", action="store_true",
                    help="detect non-finite loss/grads on-device and skip "
                         "those optimizer steps (counted in history as "
                         "skipped_steps)")
    ap.add_argument("--step-budget-seconds", type=float, default=None,
                    help="flag steps slower than this wall-clock budget "
                         "(watchdog_violations in history)")
    ap.add_argument("--fault-kill-at-step", type=int, default=None,
                    help="CHAOS TESTING: kill this process when train batch "
                         "N is produced — armed only while --ckpt-dir has "
                         "no committed checkpoint, so a restarted run "
                         "completes")
    ap.add_argument("--fault-kill-signal", default="KILL",
                    choices=["TERM", "KILL"],
                    help="signal --fault-kill-at-step sends (TERM exercises "
                         "graceful preemption, KILL an instant crash)")
    ap.add_argument("--kernel-impl", default=None,
                    choices=["pallas", "ref", "xla"],
                    help="force every repro.kernels op onto one "
                         "implementation (default: backend-resolved — "
                         "pallas on TPU, xla elsewhere); equivalent to "
                         "CLAX_KERNEL_IMPL but set before the engine traces")
    ap.add_argument("--metrics-out", default=None,
                    help="write structured telemetry events (JSONL, one per "
                         "line — see README 'Observability') to this file; "
                         "also enables the engine's on-device per-step "
                         "grad/param-norm series")
    ap.add_argument("--trace-out", default=None,
                    help="export host wall-time spans (epoch/eval/checkpoint/"
                         "shard_read/...) as a Chrome-trace JSON for Perfetto "
                         "at the end of the run")
    ap.add_argument("--obs-every", type=int, default=1,
                    help="emit every Nth per-step train metric event "
                         "(loss/grad-norm/...); skips and epoch records are "
                         "always emitted")
    ap.add_argument("--profile-steps", default=None, metavar="A:B",
                    help="open a jax.profiler trace window around the chunk "
                         "dispatches covering global steps A..B")
    ap.add_argument("--profile-dir", default="profile",
                    help="directory the --profile-steps trace is written to")
    args = ap.parse_args()
    if args.ingest_workers < 1:
        ap.error(f"--ingest-workers must be >= 1, got {args.ingest_workers}")
    if (args.ingest_workers > 1 or args.store_codec != "auto") \
            and not args.store_dir:
        ap.error("--ingest-workers/--store-codec only apply to the store "
                 "path — pass --store-dir (and --ingest)")
    if args.max_restarts:
        if not args.ckpt_dir:
            ap.error("--max-restarts requires --ckpt-dir (the restarted "
                     "child resumes from it)")
        from repro.train import run_with_restarts

        # Re-run this exact invocation as a supervised child, minus the
        # --max-restarts flag itself (the child must not recurse).
        child_args, skip = [], False
        for a in sys.argv[1:]:
            if skip:
                skip = False
                continue
            if a == "--max-restarts":
                skip = True
                continue
            if a.startswith("--max-restarts="):
                continue
            child_args.append(a)
        raise SystemExit(run_with_restarts(
            [sys.executable, "-m", "repro.launch.train"] + child_args,
            args.max_restarts))
    if args.ingest and not args.store_dir:
        ap.error("--ingest requires --store-dir")
    if args.sparse_tables and args.compression == "quotient_remainder":
        # fail before a potentially hours-long ingest, not inside train()
        ap.error("--sparse-tables does not support quotient_remainder "
                 "compression (two coupled tables, no single row-id stream)")
    if args.replicas is None and (args.replica_lrs or args.replica_seeds):
        ap.error("--replica-lrs/--replica-seeds require --replicas")
    for name, knob in (("--replica-lrs", args.replica_lrs),
                       ("--replica-seeds", args.replica_seeds)):
        if knob is not None and len(knob) != args.replicas:
            ap.error(f"{name} needs exactly --replicas {args.replicas} values")
    if args.replica_lrs and args.sparse_tables:
        ap.error("--replica-lrs is not supported with --sparse-tables (the "
                 "lazy-AdamW lr is a static hyperparameter shared by all "
                 "replicas); per-seed sweeps (--replica-seeds) are fine")

    enable_compile_cache()
    if args.kernel_impl:
        # Before anything traces: the dispatch registry resolves at trace
        # time, so the override must exist before the engine compiles.
        from repro.kernels import set_impl_override

        set_impl_override(args.kernel_impl)

    # Observability: configure the process-global recorder BEFORE the loaders
    # exist so the streaming data plane's spans/counters land in the same
    # stream. Spans are always captured in the host ring buffer (for
    # --trace-out); the JSONL sink is attached only under --metrics-out.
    from repro import obs

    recorder = obs.get_recorder()
    if args.metrics_out:
        recorder = obs.configure(sinks=[obs.JsonlSink(args.metrics_out)])
        print(f"[train] telemetry -> {args.metrics_out}")

    mesh = None
    if args.data_parallel:
        from repro.launch.mesh import make_data_parallel_mesh

        mesh = make_data_parallel_mesh()
        print(f"[train] data-parallel mesh: {dict(mesh.shape)}")

    train_loader, val_loader, test_loader, data_cfg = make_loaders(args)

    if args.fault_kill_at_step is not None:
        from repro.testing import KillSwitch

        has_ckpt = bool(args.ckpt_dir) and os.path.isdir(args.ckpt_dir) and any(
            n.startswith("step_") and
            os.path.exists(os.path.join(args.ckpt_dir, n, "COMMIT"))
            for n in os.listdir(args.ckpt_dir))
        if not has_ckpt:
            sig = (signal.SIGKILL if args.fault_kill_signal == "KILL"
                   else signal.SIGTERM)
            train_loader = KillSwitch(train_loader, args.fault_kill_at_step,
                                      sig=sig)
            print(f"[train] chaos: SIG{args.fault_kill_signal} armed at "
                  f"train batch {args.fault_kill_at_step}")

    attraction = EmbeddingParameterConfig(
        parameters=data_cfg.n_query_doc_pairs,
        compression=Compression(args.compression),
        compression_ratio=args.ratio,
        baseline_correction=True, init_logit=-2.0)
    model = MODEL_REGISTRY[args.model](
        query_doc_pairs=data_cfg.n_query_doc_pairs,
        positions=data_cfg.positions,
        attraction=attraction)

    optimizer = optim.adamw(args.lr, weight_decay=1e-4,
                            inject_lr=args.replica_lrs is not None)
    trainer = Trainer(optimizer=optimizer,
                      epochs=args.epochs, patience=1,
                      checkpoint_dir=args.ckpt_dir,
                      checkpoint_every_steps=200 if args.ckpt_dir else None,
                      handle_preemption=True,
                      chunk_batches=args.chunk_batches, mesh=mesh,
                      sparse_tables=args.sparse_tables,
                      # must mirror the dense optimizer above — the sparse
                      # path cannot introspect the transformation chain
                      sparse_table_kwargs=dict(lr=args.lr, weight_decay=1e-4),
                      replicas=args.replicas,
                      replica_lrs=args.replica_lrs,
                      replica_seeds=args.replica_seeds,
                      nonfinite_guard=args.nonfinite_guard,
                      step_budget_seconds=args.step_budget_seconds,
                      seed=args.seed,
                      telemetry=bool(args.metrics_out),
                      obs_every=args.obs_every,
                      profile_steps=args.profile_steps,
                      profile_dir=args.profile_dir)
    try:
        trainer.train(model, train_loader, val_loader,
                      resume=bool(args.ckpt_dir))
        results = trainer.test(model, test_loader)
    finally:
        if args.trace_out:
            n_spans = recorder.export_chrome_trace(args.trace_out)
            print(f"[train] {n_spans} spans -> {args.trace_out} "
                  "(open in Perfetto / chrome://tracing)")
        recorder.flush_counters()
        recorder.close()
    if args.replicas is None:
        print("[train] test:", {k: round(v, 4) for k, v in results.items()
                                if k != "per_rank"})
    else:
        for i in range(args.replicas):
            print(f"[train] test replica {i}:",
                  {k: round(v[i], 4) for k, v in results.items()
                   if k != "per_rank"})


if __name__ == "__main__":
    main()
