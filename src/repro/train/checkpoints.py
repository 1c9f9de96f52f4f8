"""Atomic, durable, keep-k, elastic-restore checkpointing for pytrees.

Layout per step:  <dir>/step_<n>/
    arrays.npz      — flat {path: array} of every leaf (host numpy)
    structure.json  — treedef + dtypes + aux metadata (loader state, step,
                      rng) + per-leaf crc32 checksums
A ``COMMIT`` marker file is written last; directories without it are treated
as partial writes (e.g. a preemption mid-save) and ignored + garbage-collected.

Durability ordering (what makes a crash at *any* instant recoverable):
``arrays.npz`` and ``structure.json`` are fsynced, then ``COMMIT`` is
written and fsynced, then the tmp directory itself is fsynced (so the
marker's directory entry is durable), then the atomic rename into place,
then the parent directory is fsynced (so the rename is durable). A power
cut between any two steps leaves either no ``step_<n>`` entry or a
COMMIT-less partial — both GC'd on the next manager construction — never a
committed-but-torn checkpoint.

Restore is **corruption-aware**: every checkpoint is validated before use
(COMMIT present, ``structure.json`` parses, ``arrays.npz`` unzips, per-leaf
crc32 matches). ``restore(step=None)`` walks committed steps newest-first
and returns the first *valid* one, quarantining (deleting) invalid entries
as it goes — a torn or bit-rotted latest checkpoint costs one save
interval, not the run. An explicitly requested step that fails validation
raises :class:`CheckpointCorruptionError`.

Elastic restore: arrays are saved unsharded (host-gathered). ``restore`` takes
optional ``shardings`` (a pytree of NamedSharding) and device_puts each leaf
accordingly — so a checkpoint written on an N-device mesh restores onto any
M-device mesh whose axis sizes divide the array dims (re-sharding happens at
device_put time). This is the standard reshard-on-restore elasticity model.
"""
from __future__ import annotations

import json
import os
import shutil
import tempfile
import zlib
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

COMMIT_MARKER = "COMMIT"


class CheckpointCorruptionError(ValueError):
    """A committed checkpoint failed validation (unreadable archive, missing
    leaf, or crc32 mismatch). Raised only for an explicitly requested step;
    latest-checkpoint restore skips invalid entries instead."""


def select_replica(tree, index: int):
    """Slice replica ``index`` out of an R-stacked pytree (params, opt-state,
    or a whole restored checkpoint tree): every leaf loses its leading
    replica axis. The result is shaped exactly like a single sequential
    run's state, so any replica of a sweep checkpoint resumes or tests
    standalone."""
    return jax.tree_util.tree_map(lambda x: x[index], tree)


def stack_replicas(trees):
    """Inverse of :func:`select_replica`: stack per-replica pytrees (e.g.
    checkpoints of R independent sequential runs) into one R-stacked tree a
    ``TrainEngine(replicas=R)`` sweep can resume from."""
    if not trees:
        raise ValueError("stack_replicas needs at least one tree")
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


def _flatten_with_paths(tree):
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    out = {}
    for path, leaf in flat:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        out[key] = leaf
    return out, treedef


def _leaf_crc32(arr: np.ndarray) -> str:
    return f"{zlib.crc32(np.ascontiguousarray(arr).tobytes()):08x}"


def _conform(key: str, arr: np.ndarray, like) -> np.ndarray:
    """``arr`` in the shape of the template leaf ``like``. Shapes that differ
    only by axes of length 1 hold the same values in the same order: a
    scalar table, which training holds as ``(rows,)``, restores into the
    model's ``(rows, 1)`` and back. Any other difference is an error."""
    shape = tuple(np.shape(like))
    if arr.shape == shape:
        return arr
    if [d for d in arr.shape if d != 1] == [d for d in shape if d != 1]:
        return arr.reshape(shape)
    raise ValueError(f"checkpoint leaf {key!r} has shape {arr.shape}, "
                     f"the template {shape}")


def _fsync_file(path: str) -> None:
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def _fsync_dir(path: str) -> None:
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


class CheckpointManager:
    def __init__(self, directory: str, keep: int = 3, log_fn=print):
        self.directory = directory
        self.keep = keep
        self.log_fn = log_fn
        os.makedirs(directory, exist_ok=True)
        self._gc_partial()

    # -- public API ---------------------------------------------------------------
    def save(self, step: int, tree: Any, aux: Optional[Dict] = None) -> str:
        """Atomically + durably write a checkpoint for ``step`` (see module
        docstring for the fsync/COMMIT/rename ordering)."""
        final_dir = self._step_dir(step)
        tmp_dir = tempfile.mkdtemp(prefix=f".tmp_step_{step}_", dir=self.directory)
        try:
            arrays, structure = self._to_host(tree)
            checksums = {k: _leaf_crc32(v) for k, v in arrays.items()}
            arrays_path = os.path.join(tmp_dir, "arrays.npz")
            np.savez(arrays_path, **arrays)
            _fsync_file(arrays_path)
            structure_path = os.path.join(tmp_dir, "structure.json")
            with open(structure_path, "w") as f:
                json.dump({"step": step, "aux": aux or {}, "keys": structure,
                           "checksums": checksums}, f)
                f.flush()
                os.fsync(f.fileno())
            with open(os.path.join(tmp_dir, COMMIT_MARKER), "w") as f:
                f.write("ok")
                f.flush()
                os.fsync(f.fileno())
            _fsync_dir(tmp_dir)
            if os.path.exists(final_dir):
                shutil.rmtree(final_dir)
            os.rename(tmp_dir, final_dir)
            _fsync_dir(self.directory)
        except BaseException:
            shutil.rmtree(tmp_dir, ignore_errors=True)
            raise
        self._gc_old()
        return final_dir

    def latest_step(self) -> Optional[int]:
        steps = self._committed_steps()
        return max(steps) if steps else None

    def restore(self, step: Optional[int] = None, like: Any = None,
                shardings: Any = None):
        """Restore (tree, aux, step). ``like`` provides the pytree structure.

        With ``step=None`` the newest committed checkpoint that passes
        validation wins; invalid ones (torn archive, crc mismatch) are
        logged and deleted so they can't shadow an older good save. An
        explicit ``step`` that fails validation raises
        :class:`CheckpointCorruptionError` — the caller asked for *that*
        state, so silently substituting another would be wrong.

        If ``shardings`` is given (pytree of NamedSharding matching ``like``),
        every leaf is device_put with its sharding — elastic restore onto a
        different mesh.
        """
        if step is None:
            meta = arrays = None
            for cand in sorted(self._committed_steps(), reverse=True):
                try:
                    meta, arrays = self._load_validated(cand)
                    break
                except CheckpointCorruptionError as e:
                    self.log_fn(f"[checkpoints] step {cand} is corrupt "
                                f"({e}); deleting and falling back")
                    shutil.rmtree(self._step_dir(cand), ignore_errors=True)
            if meta is None:
                raise FileNotFoundError(
                    f"no valid committed checkpoints in {self.directory}")
        else:
            meta, arrays = self._load_validated(step)
        if like is None:
            tree = dict(arrays)
        else:
            flat, treedef = _flatten_with_paths(like)
            leaves = []
            for key, want in flat.items():
                if key not in arrays:
                    raise KeyError(f"checkpoint missing leaf {key!r}")
                leaves.append(_conform(key, arrays[key], want))
            # order must match tree_flatten order of `like`
            paths_in_order = list(flat.keys())
            restored = dict(zip(paths_in_order, leaves))
            tree = jax.tree_util.tree_unflatten(
                jax.tree_util.tree_structure(like),
                [restored[k] for k in paths_in_order])
        if shardings is not None:
            tree = jax.tree_util.tree_map(
                lambda x, s: jax.device_put(x, s), tree, shardings)
        return tree, meta["aux"], meta["step"]

    # -- internals -----------------------------------------------------------------
    def _load_validated(self, step: int):
        """Load + validate one committed checkpoint → (meta, {key: array}).

        Validation: COMMIT marker present, structure.json parses, arrays.npz
        opens and every member decompresses (the zip layer checks its own
        crc), and — for checkpoints that recorded them — per-leaf crc32
        matches. Pre-checksum checkpoints (no "checksums" key) stay
        restorable. Any failure raises CheckpointCorruptionError.
        """
        d = self._step_dir(step)
        if not os.path.exists(os.path.join(d, COMMIT_MARKER)):
            raise CheckpointCorruptionError(f"step {step}: no COMMIT marker")
        try:
            with open(os.path.join(d, "structure.json")) as f:
                meta = json.load(f)
        except (OSError, ValueError) as e:
            raise CheckpointCorruptionError(
                f"step {step}: unreadable structure.json ({e})") from e
        try:
            with np.load(os.path.join(d, "arrays.npz")) as npz:
                arrays = {k: npz[k] for k in npz.files}
        except Exception as e:
            raise CheckpointCorruptionError(
                f"step {step}: unreadable arrays.npz ({e})") from e
        checksums = meta.get("checksums")
        if checksums is not None:
            for key, want in checksums.items():
                if key not in arrays:
                    raise CheckpointCorruptionError(
                        f"step {step}: leaf {key!r} missing from arrays.npz")
                got = _leaf_crc32(arrays[key])
                if got != want:
                    raise CheckpointCorruptionError(
                        f"step {step}: crc mismatch on leaf {key!r} "
                        f"(recorded {want}, found {got})")
        return meta, arrays

    def _step_dir(self, step: int) -> str:
        return os.path.join(self.directory, f"step_{step:010d}")

    def _committed_steps(self):
        steps = []
        for name in os.listdir(self.directory):
            if name.startswith("step_") and os.path.exists(
                    os.path.join(self.directory, name, COMMIT_MARKER)):
                steps.append(int(name.split("_")[1]))
        return steps

    def _gc_partial(self):
        for name in os.listdir(self.directory):
            path = os.path.join(self.directory, name)
            is_partial = (name.startswith(".tmp_") or
                          (name.startswith("step_") and
                           not os.path.exists(os.path.join(path, COMMIT_MARKER))))
            if is_partial:
                shutil.rmtree(path, ignore_errors=True)

    def _gc_old(self):
        steps = sorted(self._committed_steps())
        for step in steps[:-self.keep] if self.keep else []:
            shutil.rmtree(self._step_dir(step), ignore_errors=True)

    @staticmethod
    def _to_host(tree):
        flat, _ = _flatten_with_paths(tree)
        arrays = {}
        for key, leaf in flat.items():
            arr = np.asarray(jax.device_get(leaf))
            arrays[key] = arr
        return arrays, list(flat.keys())
