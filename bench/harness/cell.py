"""Find a cell's files by name and hold what one run needs.

``BENCHMARK.json`` names each cell's configuration and traffic mix; every
other piece is a file whose name is the entry's name:

- ``bench/configs/<config>.json``: the configuration as it is run, with its
  plain reference beside it in ``bench/configs/<config>.py``;
- ``bench/traffic/<traffic>.json``: the mix, naming the driver that runs it
  (``bench/drivers/<driver>.py``);
- ``bench/limits/<workload>.json``: the limit of each number compared;
- ``bench/work/<config>.py``: the least work per step of the configuration;
- ``bench/metrics/<metric>.py``: one reducer per per-layer metric.

So a later cell, configuration, mix or metric is new files and new entries.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Any, Dict, List, Optional

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def load_json(path: Path) -> Dict[str, Any]:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_module(path: Path):
    """Import a file of the benchmark by its path (names may hold '.' and
    '-', which no import statement accepts)."""
    if not path.is_file():
        raise FileNotFoundError(f"no such benchmark file: {path}")
    name = "bench_" + "".join(c if c.isalnum() else "_"
                              for c in str(path.relative_to(path.anchor)))
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def jax_seed(seed: int) -> int:
    """A PRNG seed JAX accepts (it takes 32 bits) for any whole ``seed``."""
    return int(seed) % (2 ** 31 - 1)


@dataclasses.dataclass
class Cell:
    name: str
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, float]
    chips: int
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    seed: int = 0
    seconds: float = 10.0
    trace: bool = False
    bench: Path = BENCH
    out_dir: Optional[Path] = None

    @property
    def out(self) -> Path:
        """Where this cell's run writes: ``bench/out/<workload>`` (a
        directory ``.gitignore`` lists) unless ``out_dir`` says otherwise."""
        return self.out_dir or self.bench / "out" / self.name

    def driver(self):
        return load_module(self.bench / "drivers" / f"{self.traffic['driver']}.py")

    def reference(self):
        return load_module(self.bench / "configs" / f"{self.config['name']}.py")

    def work(self):
        return load_module(self.bench / "work" / f"{self.config['name']}.py")

    def metric(self, name: str):
        return load_module(self.bench / "metrics" / f"{name}.py")

    def peaks(self, device_kind: str) -> Dict[str, Any]:
        table = load_json(self.bench / "peaks.json")
        if device_kind not in table:
            raise KeyError(f"no peaks for device kind {device_kind!r} in "
                           f"{self.bench / 'peaks.json'}")
        return table[device_kind]


def _applies(entry: Dict[str, Any], cell: str, e2e_names=None) -> bool:
    if "workloads" in entry:
        return cell in entry["workloads"]
    return e2e_names is None or entry.get("moves") in e2e_names


def find_cell(name: str, spec: Optional[Dict[str, Any]] = None,
              bench: Path = BENCH) -> Cell:
    """The cell called ``name`` in ``BENCHMARK.json``, with its files."""
    spec = spec if spec is not None else load_json(bench.parent / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    e2e = [m for m in spec["end_to_end"] if _applies(m, name)]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"] if _applies(m, name, names)]
    return Cell(name=name,
                config=load_json(bench / "configs" / f"{w['config']}.json"),
                traffic=load_json(bench / "traffic" / f"{w['traffic']}.json"),
                limits=load_json(bench / "limits" / f"{name}.json"),
                chips=int(w["chips"]), end_to_end=e2e, per_layer=per_layer,
                bench=bench)
