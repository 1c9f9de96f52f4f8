"""Deterministic, checkpointable, shard-aware batch loader.

Designed for the fault-tolerance story: loader state (epoch, step, shuffle
seed) is a tiny pytree saved with every checkpoint, so a preempted run resumes
mid-epoch bit-exactly. For multi-host setups, ``host_id``/``host_count`` carve
disjoint session shards per host (each host loads only its slice, the standard
data-parallel input pipeline at pod scale).
"""
from __future__ import annotations

import collections
import dataclasses
import threading
from typing import Dict, Iterator, Optional, Tuple

import numpy as np

from repro.obs import get_recorder

MODEL_KEYS = ("positions", "query_doc_ids", "clicks", "mask",
              "query_doc_features", "bias_features")


def split_sessions(data: Dict[str, np.ndarray], fractions=(0.8, 0.1, 0.1),
                   seed: int = 0):
    """Shuffle-split a session dict into train/val/test dicts.

    The last split takes the exact remainder (independent per-fraction
    rounding could overlap splits or silently drop tail sessions); the
    splits always partition the input.
    """
    n = data["positions"].shape[0]
    order = np.random.default_rng(seed).permutation(n)
    sizes = [int(round(n * frac)) for frac in fractions[:-1]]
    sizes.append(n - sum(sizes))
    if sizes[-1] < 0:
        raise ValueError(f"fractions {fractions} overflow {n} sessions")
    assert sum(sizes) == n, (sizes, n)
    out = []
    start = 0
    for size in sizes:
        idx = order[start:start + size]
        out.append({k: v[idx] for k, v in data.items()})
        start += size
    return tuple(out)


@dataclasses.dataclass
class LoaderState:
    epoch: int = 0
    step: int = 0  # batch index within the epoch

    def to_dict(self):
        return {"epoch": self.epoch, "step": self.step}

    @classmethod
    def from_dict(cls, d):
        return cls(epoch=int(d["epoch"]), step=int(d["step"]))


class ClickLogLoader:
    def __init__(self, data: Dict[str, np.ndarray], batch_size: int,
                 shuffle: bool = True, seed: int = 0, drop_last: bool = True,
                 host_id: int = 0, host_count: int = 1,
                 include_keys: Optional[Tuple[str, ...]] = None):
        keys = include_keys or tuple(k for k in data if k in MODEL_KEYS)
        self.data = {k: data[k] for k in keys}
        n = next(iter(self.data.values())).shape[0]
        # host shard: contiguous slice per host
        per_host = n // host_count
        lo, hi = host_id * per_host, (host_id + 1) * per_host
        self.data = {k: v[lo:hi] for k, v in self.data.items()}
        self.n = per_host
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.seed = seed
        self.drop_last = drop_last
        self.state = LoaderState()

    @property
    def batches_per_epoch(self) -> int:
        if self.drop_last:
            return self.n // self.batch_size
        return -(-self.n // self.batch_size)

    def _epoch_order(self, epoch: int) -> np.ndarray:
        if not self.shuffle:
            return np.arange(self.n)
        return np.random.default_rng((self.seed, epoch)).permutation(self.n)

    def __iter__(self) -> Iterator[Dict[str, np.ndarray]]:
        """Resumes from self.state; advances it as batches are consumed."""
        while True:
            order = self._epoch_order(self.state.epoch)
            nb = self.batches_per_epoch
            while self.state.step < nb:
                i = self.state.step
                idx = order[i * self.batch_size:(i + 1) * self.batch_size]
                self.state.step += 1
                yield {k: v[idx] for k, v in self.data.items()}
            self.state = LoaderState(epoch=self.state.epoch + 1, step=0)
            return  # one epoch per __iter__ call

    def epochs(self, n_epochs: int):
        start = self.state.epoch
        while self.state.epoch < start + n_epochs:
            yield from iter(self)

    # -- checkpointing -----------------------------------------------------------
    def state_dict(self):
        return self.state.to_dict()

    def load_state_dict(self, d):
        self.state = LoaderState.from_dict(d)


class DevicePrefetcher:
    """Double-buffered device-put prefetch over one loader epoch.

    Keeps ``size`` batches resident on device so the host->device copy of
    batch i+1 (and the host-side slicing behind it) overlaps the
    asynchronously dispatched step on batch i — the train loop never blocks
    on input, and the per-batch ``jnp.asarray`` re-wrap disappears.

    With ``overlap=True`` (default) the whole host side — pulling loader
    batches (which drains the streaming loader's read-ahead queue, i.e.
    shard decompress + window assembly), chunk stacking, and
    ``jax.device_put`` — runs in a dedicated staging thread feeding a
    bounded queue of device-resident items. The consumer then only pops
    finished device buffers: the H2D copy of chunk k+1 genuinely overlaps
    the dispatched scan over chunk k instead of running between dispatches.
    Item order, payloads, and the recorded resume states are identical to
    ``overlap=False`` (single producer, FIFO queue) — bit-exact mid-epoch
    checkpoint/resume is preserved, and a staging-thread exception (e.g.
    ``ShardCorruptionError`` from a fail-closed reader) re-raises on the
    consumer with its original traceback. Abandoning the iterator mid-epoch
    closes the staging thread, which in turn closes the loader's epoch
    generator *from the thread that was consuming it*.

    Iterating yields ``(device_batch, loader_state)`` pairs. ``loader_state``
    is the loader's resume point recorded *when that batch was produced*;
    mid-epoch checkpoints must save it (not ``loader.state_dict()``, which has
    run up to ``size`` batches ahead) to stay bit-exact across preemption.

    **Chunk mode** (``chunk_batches=N``): stacks N consecutive host batches
    into one ``(N, B, ...)`` array per key before the single ``device_put``,
    and yields ``(chunk, loader_state, n)`` triples instead of pairs, where
    ``loader_state`` is the resume point of the chunk's *last* batch (the
    correct cursor after all ``n`` contained steps ran) and ``n <= N`` (the
    epoch tail may form a partial chunk). A batch whose shapes differ from
    the chunk being accumulated (e.g. the final ``drop_last=False`` partial
    batch) flushes the current chunk and starts its own, so every yielded
    chunk is rectangular. This feeds the scan-jitted
    :class:`repro.train.engine.TrainEngine` one dispatch per N steps.

    ``device`` may be a ``jax.sharding.Sharding`` (e.g. a NamedSharding
    splitting the batch axis over a data-parallel mesh) — ``device_put``
    then places each (stacked) batch directly into its sharded layout — or
    a callable ``batch -> device/sharding`` for per-batch placement (e.g.
    shard divisible batches, replicate the ``drop_last=False`` tail).

    Spans (on ``recorder``, default the process-global one): ``stage``
    around each item's stack and ``device_put`` (tag ``n``, the batches in
    it), on whichever thread stages; ``batch_wait`` around each wait of the
    consumer for the next item, the end-of-epoch marker included.
    """

    def __init__(self, loader, size: int = 2, device=None,
                 chunk_batches: Optional[int] = None, overlap: bool = True,
                 recorder=None):
        if size < 1:
            raise ValueError(f"prefetch size must be >= 1, got {size}")
        if chunk_batches is not None and chunk_batches < 1:
            raise ValueError(
                f"chunk_batches must be >= 1, got {chunk_batches}")
        if chunk_batches is not None and callable(device):
            # A batch-shaped callable would see the stacked (N, B, ...)
            # chunk and shard the scanned axis; chunks take one fixed
            # sharding (e.g. TrainEngine.batch_sharding()).
            raise ValueError(
                "callable device is not supported with chunk_batches — "
                "pass a fixed sharding shaped for the stacked chunk")
        self.loader = loader
        self.size = size
        self.device = device
        self.chunk_batches = chunk_batches
        self.overlap = overlap
        self.recorder = recorder

    def _rec(self):
        return self.recorder if self.recorder is not None else get_recorder()

    def _put(self, batch):
        import jax

        device = self.device(batch) if callable(self.device) else self.device
        return {k: jax.device_put(v, device) for k, v in batch.items()}

    # -- host-side item stream (shared by both execution modes) ----------------
    def _items(self):
        """Generator of finished queue items: loader pull + (chunk stack) +
        ``device_put`` + resume-state capture. Everything host-side lives
        here, so whichever thread iterates it does all the staging work.
        The loader's epoch iterator is created on first next() — in overlap
        mode that is the staging thread, which therefore also owns closing
        it (a generator must be closed from the thread executing it)."""
        it = iter(self.loader)
        get_state = getattr(self.loader, "state_dict", lambda: None)
        rec = self._rec()
        if self.chunk_batches is None:
            for batch in it:
                with rec.span("stage", n=1):
                    item = (self._put(batch), get_state())
                yield item
            return
        pushback = []  # one-batch lookahead for the shape-change flush
        while True:
            batches, state, sig = [], None, None
            while len(batches) < self.chunk_batches:
                if pushback:
                    item = pushback.pop()
                else:
                    try:
                        item = (next(it), get_state())
                    except StopIteration:
                        break
                batch, s = item
                bsig = {k: (v.shape, v.dtype) for k, v in batch.items()}
                if sig is not None and bsig != sig:
                    pushback.append(item)
                    break
                sig = bsig
                batches.append(batch)
                state = s
            if not batches:
                return
            with rec.span("stage", n=len(batches)):
                chunk = self._put({k: np.stack([b[k] for b in batches])
                                   for k in batches[0]})
            yield (chunk, state, len(batches))

    # -- execution modes -------------------------------------------------------
    def _pump(self, items):
        """Inline mode: prime ``size`` items, then refill one ahead of each
        yield, all on the consumer thread (``overlap=False``)."""
        queue = collections.deque()
        rec = self._rec()
        try:
            with rec.span("batch_wait"):
                for item in items:
                    queue.append(item)
                    if len(queue) >= self.size:
                        break
            while queue:
                with rec.span("batch_wait"):
                    nxt = next(items, None)
                if nxt is not None:  # refill before handing back to compute
                    queue.append(nxt)
                yield queue.popleft()
        finally:
            items.close()

    def _staged(self, items):
        """Overlap mode: run the item stream in a staging thread feeding a
        bounded queue; the consumer only pops device-resident items."""
        import queue as queue_mod

        q: queue_mod.Queue = queue_mod.Queue(maxsize=self.size)
        stop = threading.Event()
        done = object()
        fail = []  # [exception] — surfaced on the consumer

        def send(item) -> bool:
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except queue_mod.Full:
                    continue
            return False

        def run():
            try:
                for item in items:
                    if not send(item):
                        return
                send(done)
            except BaseException as e:
                fail.append(e)
                send(done)
            finally:
                # consumed here => closed here; for a streaming loader this
                # unwinds its epoch generator's finally (read-ahead shutdown)
                items.close()

        def take():
            while True:
                try:
                    return q.get(timeout=0.2)
                except queue_mod.Empty:
                    if not thread.is_alive() and q.empty() and not fail:
                        return done  # crashed harder than except: nothing to raise

        thread = threading.Thread(target=run, daemon=True,
                                  name="device-prefetch")
        thread.start()
        rec = self._rec()
        try:
            while True:
                with rec.span("batch_wait"):
                    item = take()
                if item is done:
                    if fail:
                        raise fail[0]  # original traceback intact
                    return
                yield item
        finally:
            stop.set()
            thread.join(timeout=10.0)

    def __iter__(self):
        if self.overlap:
            yield from self._staged(self._items())
        else:
            yield from self._pump(self._items())
