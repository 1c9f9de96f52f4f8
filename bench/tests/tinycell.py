"""Cells of the benchmark cut to a size the CPU runs in seconds.

Importing this puts the benchmark's harness and the program on the path.
The shapes stay those of the configuration; only the id space, the batch,
the mix's volume and the window shrink.
"""
import dataclasses
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for _p in (BENCH.parent / "src", BENCH):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from harness.cell import find_cell, load_json  # noqa: E402

SEED = 12345678901


def cell_named(name, chips=1):
    """The cell ``<config>.<traffic>``; one prepared in ``bench/`` but not
    entered in ``BENCHMARK.json`` is found by its files too. Where such a
    cell has no limits measured on the chip yet, these tests hold it to
    those of the entered cell of the same configuration and driver."""
    spec = load_json(BENCH.parent / "BENCHMARK.json")
    if name in {w["name"] for w in spec["workloads"]}:
        return find_cell(name, spec=spec)
    config, traffic = name.split(".", 1)
    spec["workloads"].append({"name": name, "config": config,
                              "traffic": traffic, "chips": chips})
    if (BENCH / "limits" / f"{name}.json").is_file():
        return find_cell(name, spec=spec)

    def driver(mix):
        return load_json(BENCH / "traffic" / f"{mix}.json")["driver"]

    sibling = next(w["name"] for w in spec["workloads"]
                   if w["config"] == config and w["name"] != name
                   and driver(w["traffic"]) == driver(traffic))
    return dataclasses.replace(
        find_cell(sibling, spec=spec), name=name, chips=chips,
        traffic=load_json(BENCH / "traffic" / f"{traffic}.json"),
        per_layer=[m for m in spec["per_layer"]
                   if name in m.get("workloads", ())])


def tiny_cell(name, out_dir, seed=SEED, seconds=0.5, chips=1):
    cell = cell_named(name, chips)
    for table in cell.config["tables"].values():
        table["parameters"] = 1 << 16
    cell.config["batch"] = 64
    traffic = cell.traffic
    traffic["sessions"]["n_queries"] = 500
    if traffic["driver"] == "train":
        traffic.update(batches=8, chunk_batches=2, shard_rows=128)
    else:
        traffic.update(rate_per_s=300, buckets=[1, 4, 16, 64])
    cell.seed, cell.seconds, cell.trace = seed, seconds, False
    cell.out_dir = Path(out_dir) / name
    return cell


def run(cell):
    """Drive the rest of a run (everything after the look for a chip)."""
    import time

    return cell.driver().run(cell, time.perf_counter(), lambda msg: None)
