#!/usr/bin/env python3
"""One run of one benchmark cell on the chips of this machine.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration, traffic mix, driver, limits and per-layer metrics
are found by name (see ``bench/harness/cell.py``). The run refuses to
measure without a TPU, with fewer chips than the cell asks for, or with a
``CLAX_KERNEL_IMPL*`` variable set. It sets the cell up, measures for
``--seconds``, then compares what the timed path produced with the plain
reference. The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the end-to-end metrics
with ``--trace 0``, the per-layer ones with ``--trace 1``), ``device``,
``breakdown`` with ``--trace 1``, ``unbounded`` where the cell reports
end-to-end figures held to no bound (the serving tails), and last ``checks``: each number compared with its limit. Those are also the last lines of standard error. Logs go to
standard error; traces and the session store to ``bench/out/<workload>/``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def prepare(args):
    """The cell, with the program importable and the caches in place."""
    sys.path.insert(0, str(BENCH))
    from harness.cell import find_cell
    from harness.device import check_environment

    check_environment()
    cell = find_cell(args.workload)
    cell.seed, cell.seconds, cell.trace = args.seed, args.seconds, bool(args.trace)
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        raise FileNotFoundError(f"no program under {src}")
    sys.path.insert(0, str(src))
    os.makedirs(cell.out, exist_ok=True)
    # Compiled programs persist at a fixed path inside the checkout unless
    # the environment names a directory; libtpu's logs stay with the run.
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(ROOT / ".jax_cache"))
    os.environ.setdefault("TPU_LOG_DIR", str(cell.out / "tpu_logs"))
    import jax

    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cell


def per_layer(cell, record):
    """The cell's per-layer metrics that found something to read."""
    out = {}
    for entry in cell.per_layer:
        value = cell.metric(entry["name"]).reduce(record)
        if value is not None:
            out[entry["name"]] = {"value": float(value), "unit": entry["unit"]}
    return out


def main(argv=None) -> int:
    args = parse(argv)
    try:
        cell = prepare(args)
        from harness.device import check_devices

        device = check_devices(cell.chips)
    except Exception as e:  # no chip, no program, no such cell: no result
        log(f"cannot run: {type(e).__name__}: {e}")
        return 2
    from harness import compare, xtrace

    log(f"{cell.name} seed {cell.seed} on {device}")
    record = cell.driver().run(cell, T_START, log)
    record.device_kind = device["kind"]
    device["memory_peak_bytes"] = record.memory_peak_bytes
    breakdown = None
    if cell.trace:
        record.trace = xtrace.load(str(record.trace_dir))
        for line in xtrace.inventory(str(record.trace_dir)):
            log(f"trace: {line}")
        by_program = record.steps_per_execution > 0
        record.devices = [f for f in (xtrace.figures(d, by_program)
                                      for d in record.trace.devices[:cell.chips])
                          if f is not None]
        if not record.devices:
            raise RuntimeError(f"no device window in {record.trace.path}")
        device["busy_s"] = record.mean_device("busy_s")
        device["window_s"] = record.mean_device("window_s")
        breakdown = xtrace.breakdown(record.trace, record.devices)
        metrics = per_layer(cell, record)
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        metrics = {name: {"value": float(record.end_to_end[name]),
                          "unit": units[name]} for name in units}
    for note in record.notes:
        log(note)
    correct = all(c.ok for c in record.checks)
    result = {"correct": correct, "attempted": int(record.attempted),
              "failed": int(record.failed), "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if record.unbounded:
        result["unbounded"] = record.unbounded
    result["checks"] = compare.as_json(record.checks)
    for line in compare.as_lines(record.checks):
        print(line, file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
