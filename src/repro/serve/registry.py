"""Warm multi-model registry: one process serves many models, three tiers.

Each :class:`ModelEntry` owns the three rungs of its degradation ladder:

* ``primary`` — the f32 checkpoint params through the model's jit'd
  unconditional-click path.
* ``int8`` — a 4x-smaller resident copy where every large table leaf is
  int8-quantized (:func:`repro.distrib.compression.quantize_tree`); the
  jit'd program dequantizes in-graph, so worst-case per-logit error is
  ``scale/2`` per quantized factor (documented tolerance; pinned in tests
  and measured in ``BENCH_serve.json``).
* ``prior`` — a constant log-CTR, pure host numpy: the answer of last
  resort that cannot fail and costs nothing.

Every (tier, bucket) program is compiled at :meth:`ModelRegistry.warmup`,
before the first request, and each compile bumps a per-tier trace counter
— the *no-retrace* guarantee ("serving traffic never eats a compile") is a
counter equality, pinned in tests/test_serve.py. Warmup also seeds the
per-bucket service-time estimates (EMA of measured wall time, or the exact
:class:`~repro.serve.clock.ServiceModel` under virtual time) that the
deadline-aware batcher plans with.
"""
from __future__ import annotations

import math
from typing import Dict, Iterable, Optional

import jax
import numpy as np

from repro.core.parameterization import relayout_scalar_tables
from repro.distrib.compression import quantize_tree, tree_nbytes
from repro.serve.request import TIERS, make_request

DEFAULT_BUCKETS = (1, 4, 16, 64, 256)

# EMA weight for wall-mode service-time estimates: new = (1-a)*old + a*obs.
_EMA_ALPHA = 0.3


class ModelEntry:
    def __init__(self, name: str, model, params, n_pairs: int,
                 prior_ctr: float = 0.1, feature_dim: Optional[int] = None,
                 quantize_min_size: int = 512, service_model=None):
        self.name = name
        self.model = model
        # Served from flat scalar tables, the layout the gather reads: a
        # (rows, 1) table would be copied into it in full every batch.
        params = relayout_scalar_tables(model, params, flat=True)
        self.params = params
        self.n_pairs = int(n_pairs)
        self.positions = int(model.positions)
        self.feature_dim = feature_dim
        self.prior_log_ctr = math.log(min(max(float(prior_ctr), 1e-6),
                                          1.0 - 1e-6))
        self.service_model = service_model
        self.trace_counts: Dict[str, int] = {"primary": 0, "int8": 0}
        self.dispatches = 0
        self.errors = 0
        self._estimates: Dict[tuple, float] = {}

        self.qparams = quantize_tree(params, min_size=quantize_min_size)
        self.primary_nbytes = tree_nbytes(params)
        self.int8_nbytes = tree_nbytes(self.qparams)

        def _primary(p, batch):
            self.trace_counts["primary"] += 1  # bumps only at trace time
            return model.predict_clicks(p, batch)

        def _int8(qp, batch):
            self.trace_counts["int8"] += 1
            from repro.distrib.compression import dequantize_tree

            return model.predict_clicks(dequantize_tree(qp), batch)

        self._fns = {"primary": jax.jit(_primary), "int8": jax.jit(_int8)}

    # -- execution -----------------------------------------------------------
    def run(self, tier: str, batch: Dict[str, np.ndarray]) -> np.ndarray:
        """Run one padded batch on ``tier``; blocks until the answer is on
        the host. Raises whatever the computation raises — the engine's
        ladder walk is the catch site."""
        if tier == "prior":
            return np.full(batch["positions"].shape, self.prior_log_ctr,
                           np.float32)
        params = self.params if tier == "primary" else self.qparams
        out = self._fns[tier](params, batch)
        return np.asarray(jax.block_until_ready(out))

    # -- service-time estimates ----------------------------------------------
    def estimate(self, tier: str, bucket: int) -> float:
        if self.service_model is not None:
            return self.service_model(tier, bucket)
        return self._estimates.get((tier, bucket), 0.0)

    def observe(self, tier: str, bucket: int, seconds: float) -> None:
        if self.service_model is not None:
            return
        key = (tier, bucket)
        old = self._estimates.get(key)
        self._estimates[key] = seconds if old is None else \
            (1.0 - _EMA_ALPHA) * old + _EMA_ALPHA * seconds

    def health(self) -> Dict:
        return {"dispatches": self.dispatches, "errors": self.errors,
                "trace_counts": dict(self.trace_counts),
                "primary_nbytes": self.primary_nbytes,
                "int8_nbytes": self.int8_nbytes}


class ModelRegistry:
    def __init__(self, buckets: Iterable[int] = DEFAULT_BUCKETS,
                 service_model=None):
        self.buckets = tuple(sorted(set(int(b) for b in buckets)))
        if not self.buckets or self.buckets[0] < 1:
            raise ValueError("buckets must be positive ints")
        self.service_model = service_model
        self.entries: Dict[str, ModelEntry] = {}

    def add(self, name: str, model, params, n_pairs: int,
            prior_ctr: float = 0.1, feature_dim: Optional[int] = None,
            quantize_min_size: int = 512) -> ModelEntry:
        entry = ModelEntry(name, model, params, n_pairs,
                           prior_ctr=prior_ctr, feature_dim=feature_dim,
                           quantize_min_size=quantize_min_size,
                           service_model=self.service_model)
        self.entries[name] = entry
        return entry

    def __getitem__(self, name: str) -> ModelEntry:
        return self.entries[name]

    def __contains__(self, name: str) -> bool:
        return name in self.entries

    @property
    def max_bucket(self) -> int:
        return self.buckets[-1]

    def choose_bucket(self, n: int) -> int:
        """Smallest pre-compiled bucket holding ``n`` requests."""
        for b in self.buckets:
            if b >= n:
                return b
        return self.buckets[-1]

    def dummy_batch(self, entry: ModelEntry, bucket: int):
        """A well-formed padded batch for warmup compiles."""
        rng = np.random.default_rng(0)
        reqs = [make_request(-1 - i, entry.name, entry.positions, rng,
                             entry.n_pairs) for i in range(1)]
        return pad_batch(reqs, bucket, entry)

    def warmup(self, log_fn=None) -> Dict[str, float]:
        """Compile every (model, tier, bucket) program and seed service-time
        estimates. After this returns, a request can only ever hit a cached
        executable — first-compile latency is paid here, not by traffic."""
        import time

        seeded = {}
        for entry in self.entries.values():
            for tier in TIERS:
                for bucket in self.buckets:
                    batch = self.dummy_batch(entry, bucket)
                    t0 = time.perf_counter()
                    entry.run(tier, batch)
                    # compile + run; re-run for a compile-free estimate
                    t1 = time.perf_counter()
                    entry.run(tier, batch)
                    dt = time.perf_counter() - t1
                    entry.observe(tier, bucket, dt)
                    seeded[f"{entry.name}/{tier}/{bucket}"] = dt
                    if log_fn:
                        log_fn(f"[serve] warm {entry.name}/{tier} bucket "
                               f"{bucket}: compile {t1 - t0:.3f}s "
                               f"run {dt * 1e3:.2f}ms")
        return seeded


def pad_batch(requests, bucket: int, entry: ModelEntry):
    """Stack validated requests into a (bucket, K) batch dict; pad rows are
    fully masked out so they cannot influence real rows."""
    k = entry.positions
    positions = np.tile(np.arange(1, k + 1, dtype=np.int32), (bucket, 1))
    ids = np.zeros((bucket, k), np.int32)
    mask = np.zeros((bucket, k), bool)
    for i, req in enumerate(requests):
        positions[i] = np.asarray(req.positions, np.int32)
        ids[i] = np.asarray(req.query_doc_ids, np.int32)
        mask[i] = np.asarray(req.mask, bool)
    batch = {"positions": positions, "query_doc_ids": ids, "mask": mask,
             "clicks": np.zeros((bucket, k), np.float32)}
    if entry.feature_dim is not None:
        feats = np.zeros((bucket, k, entry.feature_dim), np.float32)
        for i, req in enumerate(requests):
            if req.features is not None:
                feats[i] = np.asarray(req.features, np.float32)
        batch["query_doc_features"] = feats
    return batch
