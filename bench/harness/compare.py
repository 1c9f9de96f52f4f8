"""The comparison that decides ``correct``.

Each number compared is a gap between what the timed path produced and what
the plain reference gives for the same inputs, with a limit of its own
(``bench/limits/<workload>.json``). A run is correct when every number is
finite and within its limit.

Norms of a tree are taken leaf by leaf. The gap of a leaf is
``| |prog| - |ref| |`` over the larger of the reference's norm of that leaf
and of the median leaf, since some leaves are all but zero. Leaves whose
reference gradient is under a thousandth of the median leaf's are left out
of the comparison of changes: Adam moves them by round-off alone.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Iterable, List, Mapping, Optional

import numpy as np

SMALL_GRAD = 1e-3


@dataclasses.dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


def rel_gap(got: float, want: float) -> float:
    return abs(float(got) - float(want)) / max(abs(float(want)), 1e-30)


def max_rel_gap(got: Iterable[float], want: Iterable[float]) -> float:
    got, want = list(got), list(want)
    if len(got) != len(want) or not got:
        return math.inf
    return max(rel_gap(g, w) for g, w in zip(got, want))


def counted_leaves(ref_grad: Mapping[str, float]) -> List[str]:
    """Leaves whose reference gradient is not nought to rounding."""
    median = float(np.median(list(ref_grad.values())))
    return [k for k, v in ref_grad.items() if v >= SMALL_GRAD * median]


def leaf_gap(prog: Mapping[str, float], ref: Mapping[str, float],
             leaves: Optional[Iterable[str]] = None) -> float:
    """Widest gap of per-leaf norms (see the module's docstring)."""
    leaves = list(ref) if leaves is None else list(leaves)
    if not leaves or any(k not in prog for k in leaves):
        return math.inf
    median = float(np.median([ref[k] for k in leaves]))
    return max(abs(float(prog[k]) - float(ref[k]))
               / max(float(ref[k]), median, 1e-30) for k in leaves)


def judge(values: Mapping[str, float], limits: Mapping[str, float]
          ) -> List[Check]:
    """One Check per limit; a number that was never read is infinite."""
    return [Check(name, float(values.get(name, math.inf)), float(limit))
            for name, limit in limits.items()]


def as_json(checks: List[Check]) -> Dict[str, Dict[str, float]]:
    return {c.name: {"value": c.value if math.isfinite(c.value) else None,
                     "limit": c.limit} for c in checks}


def as_lines(checks: List[Check]) -> List[str]:
    return [f"check {c.name} {c.value!r} limit {c.limit!r} "
            f"{'ok' if c.ok else 'FAIL'}" for c in checks]
