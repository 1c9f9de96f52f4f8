"""Host-side consumer of the engine's per-chunk device telemetry.

:class:`TelemetryDrain` is the **single source of truth** for everything the
trainer used to double-bookkeep by hand: the per-epoch ``train_loss`` sum,
``n_batches``, and ``skipped_steps`` are accumulated here, from exactly one
``jax.device_get`` per chunk (the same drain the loss history always
needed — telemetry keys ride along in the same transfer, which is the
"zero extra host syncs per step" guarantee made concrete), and the same
drained numpy feeds per-step metric events to the recorder's sinks.

Accumulation semantics are bit-compatible with the historical trainer loop:

* scalar runs accumulate per-element ``float(loss)`` additions into a
  python float (a vectorized f32 sum would round differently), which also
  round-trips JSON exactly for crash-exact resume;
* sweep runs accumulate an ``(R,)`` float64 vector, with skipped steps
  contributing zero loss and one skip count.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import numpy as np

from repro.obs.recorder import Recorder, get_recorder

#: telemetry payload keys that are not per-step metric series
_STRUCTURAL_KEYS = ("loss", "skipped")


class TelemetryDrain:
    """Accumulate one epoch's drained chunk payloads; emit per-step events.

    ``payload`` is whatever ``TrainEngine.step`` returned: an ``(n,)`` (or
    ``(n, R)``) loss array, or a dict of same-shaped arrays (``loss``,
    optional ``skipped`` bool mask, optional telemetry series such as
    ``grad_norm``/``param_norm``/``lr``). ``drain`` performs the chunk's
    single host transfer and never blocks anywhere else.
    """

    def __init__(self, replicas: Optional[int] = None,
                 recorder: Optional[Recorder] = None, every: int = 1,
                 epoch: Optional[int] = None):
        self.R = replicas
        self.recorder = recorder
        self.every = max(int(every), 1)
        self.epoch = epoch
        self.n_batches = 0
        if replicas is None:
            self.train_loss: Any = 0.0
            self.skipped_steps: Any = 0
        else:
            self.train_loss = np.zeros(replicas, np.float64)
            self.skipped_steps = np.zeros(replicas, np.int64)

    def _rec(self) -> Recorder:
        return self.recorder if self.recorder is not None else get_recorder()

    # -- resume ------------------------------------------------------------
    def load(self, accum: Dict[str, Any]) -> None:
        """Restore mid-epoch accumulators from checkpoint aux (the
        ``epoch_accum`` dict written by :meth:`aux`)."""
        self.n_batches = int(accum["n_batches"])
        if self.R is None:
            self.train_loss = float(accum["train_loss"])
            self.skipped_steps = int(accum.get("skipped", 0))
        else:
            self.train_loss = np.asarray(accum["train_loss"], np.float64)
            self.skipped_steps = np.asarray(
                accum.get("skipped", [0] * self.R), np.int64)

    def aux(self) -> Dict[str, Any]:
        """JSON-able mid-epoch accumulators for checkpoint aux. Python
        floats round-trip json exactly (repr-based), so a resumed epoch's
        loss sum stays bit-identical to an uninterrupted run's."""
        if self.R is None:
            return {"train_loss": self.train_loss,
                    "n_batches": int(self.n_batches),
                    "skipped": int(self.skipped_steps)}
        return {"train_loss": np.asarray(self.train_loss,
                                         np.float64).tolist(),
                "n_batches": int(self.n_batches),
                "skipped": np.asarray(self.skipped_steps).tolist()}

    # -- the drain ---------------------------------------------------------
    def drain(self, payload, first_step: Optional[int] = None) -> None:
        """Fetch one chunk's telemetry (ONE ``jax.device_get`` for every
        leaf at once) and fold it into the epoch accumulators + sinks.
        ``first_step`` is the global index of the chunk's first step, used
        only to tag emitted events."""
        # Deferred so importing repro.obs (and through it repro.data — the
        # parallel-ingest worker processes) stays jax-free; only the one
        # method that touches device memory pays the jax import.
        import jax
        rec = self._rec()
        # "drain": the host blocked on the chunk's losses (its headroom
        # over the device).
        with rec.span("drain", step=first_step):
            data = jax.device_get(payload)
        if isinstance(data, dict):
            losses = np.asarray(data["loss"])
            skipped = (np.asarray(data["skipped"])
                       if "skipped" in data else None)
            extras = {k: np.asarray(v) for k, v in data.items()
                      if k not in _STRUCTURAL_KEYS}
        else:
            losses, skipped, extras = np.asarray(data), None, {}
        n = losses.shape[0]
        if self.R is None:
            # Per-element accumulation into the python float keeps the sum
            # bit-identical to the historical one-float(loss)-per-step loop.
            if skipped is None:
                for loss in losses:
                    self.train_loss += float(loss)
            else:
                for loss, skip in zip(losses, skipped):
                    if skip:
                        self.skipped_steps += 1
                    else:
                        self.train_loss += float(loss)
        else:
            arr = np.asarray(losses, np.float64)
            if skipped is None:
                self.train_loss += arr.sum(axis=0)
            else:
                self.train_loss += np.where(skipped, 0.0, arr).sum(axis=0)
                self.skipped_steps += skipped.sum(axis=0)
        start = self.n_batches if first_step is None else first_step
        self.n_batches += n
        if rec.enabled:
            self._emit(rec, losses, skipped, extras, start)

    def _emit(self, rec, losses, skipped, extras, start) -> None:
        for i in range(losses.shape[0]):
            step = start + i
            if self.R is None:
                if step % self.every == 0:
                    rec.metric("train_step", losses[i], step=step,
                               epoch=self.epoch,
                               data=self._extras_at(extras, i, None))
                if skipped is not None and skipped[i]:
                    rec.event("skipped_step", step=step, epoch=self.epoch)
            else:
                for r in range(self.R):
                    if step % self.every == 0:
                        rec.metric("train_step", losses[i, r], step=step,
                                   epoch=self.epoch, replica=r,
                                   data=self._extras_at(extras, i, r))
                    if skipped is not None and skipped[i, r]:
                        rec.event("skipped_step", step=step,
                                  epoch=self.epoch, replica=r)

    @staticmethod
    def _extras_at(extras, i, r) -> Optional[Dict[str, float]]:
        if not extras:
            return None
        if r is None:
            return {k: float(v[i]) for k, v in extras.items()}
        return {k: float(v[i, r]) for k, v in extras.items()}

    # -- derived views -----------------------------------------------------
    def mean_loss(self):
        """Epoch mean over the steps that actually updated (skipped steps
        contributed no loss; guard off means skipped is identically 0 and
        this is the historical denominator)."""
        if self.R is None:
            return self.train_loss / max(self.n_batches - self.skipped_steps,
                                         1)
        return self.train_loss / np.maximum(
            self.n_batches - self.skipped_steps, 1)
