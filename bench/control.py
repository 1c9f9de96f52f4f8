#!/usr/bin/env python3
"""Readings that set the upper end of each limit: the control and the
faults, at a cell's own size, on a chip. Not part of a benchmark run.

    python bench/control.py --workload <name> --seeds 11,12,13

For a training cell, the reference follows the batches of both ``train``
calls' first dispatches in float32 (what the program is held to) and then
takes the program's place: as the control, the same reference in bfloat16;
with half of each batch left out, the mean taken over the rest; with the
optimizer's moments and step count started afresh where the window's call
resumes; and, on a data-parallel cell, with only the first chip's share of
each batch, as when the chips leave out the gradient exchange. (A step that
returns its state unchanged reads 1 on ``step`` by construction.) For a
serving cell, the control is the reference in bfloat16 over the requests of
one window. Each line of standard output is one JSON object: the cell, the
seed, the reading and its numbers.
"""
import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def log(msg):
    print(f"[control] {msg}", file=sys.stderr, flush=True)


def train_readings(cell, seed):
    import jax.numpy as jnp

    from harness.compare import counted_leaves, leaf_gap, max_rel_gap
    from harness.follow import follow
    from harness.cell import jax_seed
    from repro.data import StreamingClickLogLoader

    drivers = cell.driver()
    store = drivers.ingest(cell, seed, log)
    chunk = int(cell.traffic["chunk_batches"])
    loader = StreamingClickLogLoader(store, batch_size=int(cell.config["batch"]),
                                     seed=jax_seed(seed))
    batches = []
    for _ in range(2):  # the set-up call's dispatch, then the window call's
        replay = drivers.Replay(loader, chunk, stop=lambda n: n >= chunk,
                                keep=chunk)
        for _batch in replay:
            pass
        batches += replay.kept
    loader.close()
    ref, cfg = cell.reference(), cell.config
    want = follow(ref, cfg, batches, read_at=chunk)
    counted = counted_leaves(want["grad1"])
    B = int(cfg["batch"])
    variants = {"control_bf16": dict(dtype=jnp.bfloat16),
                "half_batch": dict(rows=slice(0, B // 2)),
                "fresh_moments": dict(reset_at=chunk)}
    if cell.traffic.get("data_parallel"):
        variants["no_exchange"] = dict(rows=slice(0, B // cell.chips))
    out = {}
    for name, kw in variants.items():
        got = follow(ref, cfg, batches, read_at=chunk, **kw)
        out[name] = {"loss": max_rel_gap(got["losses"], want["losses"]),
                     "grad": leaf_gap(got["mu"], want["mu"]),
                     "step": leaf_gap(got["step"], want["step"], counted)}
    out["reference"] = {"losses": want["losses"], "grad1": want["grad1"],
                        "mu": want["mu"], "step": want["step"],
                        "counted": counted}
    return out


def serve_readings(cell, seed):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from harness.cell import jax_seed
    from harness.sessions import SessionSource

    serve = cell.driver()
    ref, cfg = cell.reference(), cell.config
    params = serve.draw_params(ref, cfg, jax_seed(seed))
    n = int(round(float(cell.traffic["rate_per_s"]) * cell.seconds))
    rows = SessionSource(cell.traffic["sessions"], seed).chunk(0, n)
    low = jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16), params)
    f32 = jax.jit(lambda p, b: jnp.exp(ref.click_log_probs(p, b, cfg)))
    bf16 = jax.jit(lambda p, b: jnp.exp(ref.click_log_probs(p, b, cfg)
                                        .astype(jnp.float32)))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(f32(params, rows), np.float64)
        got = np.asarray(bf16(low, rows), np.float64)
    mask = rows["mask"].astype(bool)
    return {"control_bf16": {"click_prob": float(np.max(
        np.abs(got - want)[mask] / want[mask]))}}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=None)
    args = ap.parse_args()
    from harness.cell import find_cell, load_json

    cell = find_cell(args.workload)
    cell.seconds = args.seconds or load_json(BENCH.parent / "BENCHMARK.json")["run_seconds"]
    reading = train_readings if cell.traffic["driver"] == "train" else serve_readings
    for seed in (int(s) for s in args.seeds.split(",")):
        cell.seed = seed
        t0 = time.perf_counter()
        out = reading(cell, seed)
        log(f"{cell.name} seed {seed}: {time.perf_counter() - t0:.1f}s")
        print(json.dumps({"workload": cell.name, "seed": seed, **out}), flush=True)


if __name__ == "__main__":
    main()
