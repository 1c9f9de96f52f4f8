#!/usr/bin/env python3
"""The knee of a serving cell: windows at each offered rate, in one process
on one set-up. Not part of a benchmark run.

    python bench/sweep.py --workload <name> --seed 7 --seconds 10 --repeats 3 --rates 4000,6000,8000

The rates are swept ``--repeats`` times, one window each, round after
round, so that a slow stretch of the host falls on several rates rather
than on every window of one. For each window, one line of JSON: requests
offered, the share answered on ``primary`` within the deadline, p50, p95
and p99 from the scheduled arrival, requests shed, and the p50 latency of
the window's last fifth over its first fifth (above 1 where a backlog
grows). The last line gives each rate's medians over its windows and the
knee: the highest rate whose median share answered in time is at least 99%
and whose median backlog growth is under 1.5. The cell runs at four fifths
of it.
"""
import argparse
import json
import statistics
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

IN_TIME = 0.99
BACKLOG = 1.5


def knee(rows):
    """``(knee, medians)``: medians per rate over its windows, and the
    highest rate that meets the rule (None where none does)."""
    by_rate = {}
    for r in rows:
        by_rate.setdefault(r["rate_per_s"], []).append(r)
    medians = {rate: {k: statistics.median(r[k] for r in rs)
                      for k in ("in_time_share", "backlog_growth", "p50_ms",
                                "p95_ms", "p99_ms")}
               for rate, rs in sorted(by_rate.items())}
    ok = [rate for rate, m in medians.items()
          if m["in_time_share"] >= IN_TIME and m["backlog_growth"] < BACKLOG]
    return (max(ok) if ok else None), medians


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    import numpy as np

    from harness.cell import find_cell

    cell = find_cell(args.workload)
    cell.seed = args.seed
    log = lambda msg: print(f"[sweep] {msg}", file=sys.stderr, flush=True)  # noqa: E731
    serve = cell.driver()
    setup = serve.Setup(cell, log)
    rates = [float(r) for r in args.rates.split(",")]
    rows, index = [], 0
    for _ in range(args.repeats):
        for rate in rates:
            index += 1
            t0 = time.perf_counter()
            w = setup.window(rate, args.seconds, index=index)
            lat = w.latencies(args.seconds)
            fifth = (w.due - w.due[0]) / max(w.due[-1] - w.due[0], 1e-9)
            first, last = lat[fifth < 0.2], lat[fifth >= 0.8]
            spans = [s for s in w.recorder.tracer.spans
                     if s.name == "serve_batch"]
            row = {
                "rate_per_s": rate, "requests": w.n,
                "in_time_share": 1.0 - w.failed() / w.n,
                "p50_ms": float(np.percentile(lat, 50) * 1e3),
                "p95_ms": float(np.percentile(lat, 95) * 1e3),
                "p99_ms": float(np.percentile(lat, 99) * 1e3),
                "shed": sum(1 for r in w.results if r.status == "shed"),
                "backlog_growth": float(np.median(last) / np.median(first)),
                "batches": len(spans),
                "mean_batch": (float(np.mean([s.tags["n"] for s in spans]))
                               if spans else 0.0),
                "batch_ms": (float(np.mean([s.duration for s in spans]) * 1e3)
                             if spans else 0.0),
                "gc_count": w.pauses.count,
                "gc_longest_ms": w.pauses.longest * 1e3,
                "wall_s": time.perf_counter() - t0}
            rows.append(row)
            print(json.dumps(row), flush=True)
    best, medians = knee(rows)
    print(json.dumps({"knee_per_s": best, "rule": {"in_time_share": IN_TIME,
                                                    "backlog_growth": BACKLOG},
                      "medians": {str(k): v for k, v in medians.items()}}),
          flush=True)


if __name__ == "__main__":
    main()
