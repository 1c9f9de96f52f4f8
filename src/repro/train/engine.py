"""Fused multi-batch training engine: scan-jitted steps, device-resident
loss accumulation, data-parallel sharding, and sparse embedding updates.

The per-batch Python loop (one jit dispatch + one blocking ``float(loss)``
host round-trip per step) starves the accelerator: the vectorized recursions
and the streaming store produce batches faster than the host can dispatch
them one at a time. The engine replaces it with a chunked execution core:

* **Chunked scan** — :class:`repro.data.DevicePrefetcher` with
  ``chunk_batches=N`` stacks N host batches into one ``(N, B, ...)`` device
  array (assembled and ``device_put`` on the prefetcher's staging thread,
  overlapped with compute); ``TrainEngine.step`` runs a single jit'd
  ``lax.scan`` over the chunk with donated ``(params, opt_state)``. One dispatch per N optimizer
  steps, per-step losses accumulated on device as an ``(N,)`` array the
  caller fetches asynchronously (one chunk behind — see ``Trainer.train``).
* **Data parallelism** — given a ``mesh`` (see
  :func:`repro.launch.mesh.make_data_parallel_mesh`), batches get a
  ``P(None, 'data')`` NamedSharding (chunk axis replicated, batch rows
  split) and params/opt-state get :func:`repro.distrib.shardings.clax_param_rule`
  shardings, so the same scanned step runs SPMD across all local devices.
  With ``mesh=None`` nothing is placed and the math is bit-exact with the
  historical per-batch loop (pinned by tests/test_engine.py).
* **Sparse tables** — with ``sparse_tables=True``, gradients of every
  :class:`~repro.core.parameterization.EmbeddingParameter` table part are
  routed through :mod:`repro.optim.sparse` lazy AdamW: the optimizer
  read-modify-writes only the batch's unique rows (O(U·d) state traffic
  instead of the dense 3×O(R·d) moment update), all other params keep the
  trainer's dense optimizer. Requires explicit hyperparameters
  (``sparse_table_kwargs``) because gradient-transformation chains cannot
  be introspected; lr schedules are not supported on the sparse side.
* **Replica sweeps** — ``TrainEngine(replicas=R)`` stacks R independent
  training runs on a leading replica axis of ``(params, opt_state)`` and
  ``jax.vmap``s the per-batch step over that axis while the data chunk is
  broadcast: one ``lax.scan`` dispatch advances all R runs per chunk with
  batched BLAS, so an R-way seed/lr sweep costs ~1 run of dispatch
  overhead instead of R. Per-replica seeds come from
  :meth:`TrainEngine.init_replica_params`; per-replica learning rates ride
  in the optimizer state via ``optim.adamw(lr, inject_lr=True)`` +
  :meth:`TrainEngine.set_replica_lrs`. ``step`` takes an optional
  ``active`` ``(R,)`` mask: inactive replicas' params/opt-state are frozen
  in place (per-replica early stopping without retracing the compiled
  step). Per-step losses come back as an ``(n, R)`` device array.
  Memory cost is R× params/opt-state but 1× data. The ``replicas=None``
  path is byte-for-byte the PR-4 engine (pinned by tests).
* **Non-finite guard** — ``nonfinite_guard=True`` hardens every scanned
  step: the loss and every gradient leaf are reduced to one on-device
  finiteness flag, and a per-leaf ``where`` carries the previous
  ``(params, opt_state)`` through unchanged when the flag is false. The
  step is *skipped*, not retried — one poisoned batch costs one step of
  progress instead of a dead run — and the skip flag rides back with the
  per-step losses (``{"loss", "skipped"}``) so the trainer can count
  skips without a host sync. Composes with every mode above: the scan
  carries the selected state, vmapped replicas each get their own flag,
  the mesh sees only elementwise selects, and the sparse path's scatter
  results are discarded by the same select. Guard off is byte-for-byte
  the unguarded engine.
* **On-device telemetry** — ``telemetry=True`` computes per-step scalars
  (global grad-norm, post-update param-norm, and the injected learning
  rate when the optimizer carries one) *inside* the scanned step and
  stacks them next to the per-step losses the caller already drains one
  chunk behind. The telemetry rides the existing chunk payload: enabling
  it adds **zero extra host syncs per step** and zero extra dispatches,
  does not retrace the compiled chunk across steps, and leaves the update
  math untouched (params are bit-identical to ``telemetry=False`` —
  pinned by tests/test_obs.py). The payload becomes a dict
  ``{"loss", "grad_norm", "param_norm"[, "lr"][, "skipped"]}`` of
  ``(n,)`` — or ``(n, R)`` — arrays; feed it to
  :class:`repro.obs.TelemetryDrain` to accumulate epoch stats and emit
  per-step events.
"""
from __future__ import annotations

import contextlib
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import optim as optim_lib
from repro.core.parameterization import (Compression, EmbeddingParameter,
                                        relayout_scalar_tables)
from repro.optim.sparse import (init_sparse_table_state, sparse_adamw_update,
                                unique_rows_with_sentinel)

SPARSE_PATH_SEP = "/"


def _tree_get(tree, path: Tuple[str, ...]):
    for key in path:
        tree = tree[key]
    return tree


def _tree_set(tree, path: Tuple[str, ...], value):
    """Functionally replace ``tree[path]`` (nested dicts) with ``value``."""
    if not path:
        return value
    out = dict(tree)
    out[path[0]] = _tree_set(tree[path[0]], path[1:], value)
    return out


def discover_sparse_tables(model) -> Dict[Tuple[str, ...], EmbeddingParameter]:
    """Map param path -> EmbeddingParameter for every table part of ``model``.

    Only single-table parameterizations qualify: QR compression splits each
    logical row across two tables and has no single row-id stream.
    """
    parts = getattr(model, "parts", None) or {}
    out = {}
    for name, part in parts.items():
        if isinstance(part, EmbeddingParameter):
            if part.config.compression == Compression.QR:
                raise NotImplementedError(
                    f"sparse_tables: part {name!r} uses quotient-remainder "
                    "compression (two coupled tables, no single row-id "
                    "stream) — train it with the dense optimizer")
            out[(name, "table")] = part
    if not out:
        raise ValueError(
            "sparse_tables=True but the model has no EmbeddingParameter "
            "parts — nothing to update sparsely")
    return out


class TrainEngine:
    """Chunked, optionally data-parallel and table-sparse, train-step core.

    Usage (what ``Trainer.train`` does)::

        engine = TrainEngine(model, optimizer, chunk_batches=16, mesh=mesh)
        opt_state = engine.init_opt_state(params)
        params, opt_state = engine.place(params, opt_state)
        for chunk, loader_state, n in DevicePrefetcher(
                loader, chunk_batches=engine.chunk_batches,
                device=engine.batch_sharding()):
            params, opt_state, losses = engine.step(params, opt_state, chunk)
            # losses: (n,) device array — fetch it one chunk behind

    ``step`` retraces per distinct chunk shape: full chunks plus one
    compile per tail shape (a shorter trailing chunk, and the odd-sized
    ``drop_last=False`` batch in its own chunk).
    """

    def __init__(self, model, optimizer, *, chunk_batches: int = 1,
                 mesh=None, sparse_tables: bool = False,
                 sparse_table_kwargs: Optional[Dict[str, Any]] = None,
                 loss_fn: Optional[Callable] = None,
                 replicas: Optional[int] = None,
                 nonfinite_guard: bool = False,
                 telemetry: bool = False):
        if chunk_batches < 1:
            raise ValueError(f"chunk_batches must be >= 1, got {chunk_batches}")
        if replicas is not None and replicas < 1:
            raise ValueError(f"replicas must be >= 1, got {replicas}")
        self.model = model
        self.optimizer = optimizer
        self.chunk_batches = int(chunk_batches)
        self.mesh = mesh
        self.replicas = None if replicas is None else int(replicas)
        self.nonfinite_guard = bool(nonfinite_guard)
        self.telemetry = bool(telemetry)
        self.loss_fn = loss_fn or model.compute_loss
        self.sparse_parts = discover_sparse_tables(model) if sparse_tables else {}
        if self.sparse_parts:
            kwargs = dict(sparse_table_kwargs or {})
            missing = [k for k in ("lr", "weight_decay") if k not in kwargs]
            if missing:
                # Gradient-transformation chains can't be introspected, and
                # the defaults disagree (optim.adamw decays at 1e-4,
                # sparse_adamw_update at 0.0) — silence here would quietly
                # break the touched-rows == dense-AdamW guarantee.
                raise ValueError(
                    f"sparse_tables=True needs sparse_table_kwargs with "
                    f"{missing} mirroring the dense optimizer (pass b1/b2/"
                    f"eps too if the dense optimizer overrides them)")
            self.sparse_kwargs = kwargs
        else:
            self.sparse_kwargs = {}
        if self.replicas is None:
            chunk_fn = (self._chunk_step_guarded if self.nonfinite_guard
                        else self._chunk_step)
            self._step = jax.jit(chunk_fn, donate_argnums=(0, 1))
        else:
            # Two compiled variants: the all-active fast path skips the
            # per-leaf freeze select entirely (the whole sweep until the
            # first replica early-stops), the masked path freezes inactive
            # replicas in place. `step` picks host-side per call.
            self._step = jax.jit(self._replica_chunk_step,
                                 donate_argnums=(0, 1))
            self._step_masked = jax.jit(self._replica_chunk_step_masked,
                                        donate_argnums=(0, 1))

    # -- optimizer state -------------------------------------------------------
    def _init_opt_state_single(self, params):
        if not self.sparse_parts:
            return self.optimizer.init(params)
        dense_params = params
        sparse = {}
        for path in self.sparse_parts:
            sparse[SPARSE_PATH_SEP.join(path)] = init_sparse_table_state(
                _tree_get(params, path))
            dense_params = _tree_set(dense_params, path, None)
        return {"dense": self.optimizer.init(dense_params), "sparse": sparse}

    def init_opt_state(self, params):
        """Dense optimizer state, or ``{"dense": ..., "sparse": {...}}`` when
        table grads are routed through the lazy-AdamW path (table leaves are
        masked to ``None`` in the dense subtree so dense moments never
        materialize for them). With ``replicas=R``, ``params`` must carry the
        leading replica axis (see :meth:`init_replica_params`) and every
        state leaf comes back R-stacked."""
        if self.replicas is None:
            return self._init_opt_state_single(params)
        return jax.vmap(self._init_opt_state_single)(params)

    # -- replica sweeps --------------------------------------------------------
    def init_replica_params(self, seeds) -> Any:
        """Stacked params: replica i initialized from ``PRNGKey(seeds[i])``.

        Replica i's slice is exactly what ``model.init(PRNGKey(seeds[i]))``
        would produce standalone, so a vmapped sweep run is comparable
        leaf-for-leaf with a sequential run of the same seed.
        """
        if self.replicas is None:
            raise ValueError("init_replica_params needs TrainEngine(replicas=R)")
        seeds = jnp.asarray(seeds)
        if seeds.ndim != 1 or seeds.shape[0] != self.replicas:
            raise ValueError(f"need exactly {self.replicas} seeds, got "
                             f"shape {seeds.shape}")
        keys = jax.vmap(jax.random.PRNGKey)(seeds)
        return jax.vmap(self.model.init)(keys)

    def set_replica_lrs(self, opt_state, lrs):
        """Give every replica its own learning rate.

        Requires an optimizer built with ``inject_lr=True`` (the lr must be
        a state leaf to differ across the vmapped replica axis) and no
        sparse tables (the lazy-AdamW path takes its lr as a static
        hyperparameter shared by all replicas).
        """
        from repro.optim import set_injected_lr

        if self.replicas is None:
            raise ValueError("set_replica_lrs needs TrainEngine(replicas=R)")
        if self.sparse_parts:
            raise NotImplementedError(
                "per-replica learning rates are not supported with "
                "sparse_tables: sparse_table_kwargs['lr'] is a static "
                "hyperparameter shared across replicas")
        lrs = jnp.asarray(lrs, jnp.float32)
        if lrs.shape != (self.replicas,):
            raise ValueError(f"need exactly {self.replicas} learning rates, "
                             f"got shape {lrs.shape}")
        return set_injected_lr(opt_state, lrs)

    # -- sharding --------------------------------------------------------------
    def batch_sharding(self):
        """NamedSharding for a stacked ``(chunk, batch, ...)`` array: chunk
        axis replicated (it is scanned over), batch rows split over the data
        axes. ``None`` (single-device) when no mesh is configured."""
        if self.mesh is None:
            return None
        from jax.sharding import NamedSharding

        from repro.distrib.shardings import chunked_batch_spec

        return NamedSharding(self.mesh, chunked_batch_spec(self.mesh))

    def data_parallel_size(self) -> int:
        if self.mesh is None:
            return 1
        from repro.distrib.shardings import data_parallel_size

        return data_parallel_size(self.mesh)

    def place(self, params, opt_state):
        """The state in the form the step runs on: scalar embedding tables
        and their moments flat (see
        :func:`~repro.core.parameterization.relayout_scalar_tables`; the
        arguments' arrays are spent), then, given a mesh, device_put with
        ``clax_param_rule`` shardings (big tables row-sharded over 'model',
        everything else replicated). :meth:`unplace` undoes the relayout."""
        params, opt_state = relayout_scalar_tables(
            self.model, (params, opt_state), flat=True, consume=True)
        if self.mesh is None:
            return params, opt_state
        from repro.distrib.shardings import clax_param_rule, make_shardings

        # With replicas, every leaf carries a leading (R,) axis that stays
        # replicated; the row-sharding size test must look one dim deeper.
        rule = clax_param_rule(self.mesh,
                               leading_axes=0 if self.replicas is None else 1)
        params = jax.device_put(params, make_shardings(self.mesh, params, rule))
        opt_state = jax.device_put(
            opt_state, make_shardings(self.mesh, opt_state, rule))
        return params, opt_state

    def unplace(self, params, opt_state):
        """The state in the model's own shapes: scalar tables and their
        moments back to ``(rows, 1)``, as ``model.init`` gives them (the
        arguments' arrays are spent)."""
        return relayout_scalar_tables(self.model, (params, opt_state),
                                      flat=False, consume=True)

    # -- the scanned step ------------------------------------------------------
    def _apply_update(self, params, opt_state, grads, batch):
        # The trace attributes the optimizer's device time by this scope.
        with jax.named_scope("update"):
            return self._update(params, opt_state, grads, batch)

    def _update(self, params, opt_state, grads, batch):
        if not self.sparse_parts:
            updates, opt_state = self.optimizer.update(grads, opt_state, params)
            return optim_lib.apply_updates(params, updates), opt_state
        # Sparse route: mask table leaves out of the dense update (None is an
        # empty pytree node, so the dense optimizer never touches them), then
        # scatter-update each table from the batch's unique rows.
        dense_params, dense_grads = params, grads
        for path in self.sparse_parts:
            dense_params = _tree_set(dense_params, path, None)
            dense_grads = _tree_set(dense_grads, path, None)
        updates, dense_state = self.optimizer.update(
            dense_grads, opt_state["dense"], dense_params)
        new_params = optim_lib.apply_updates(dense_params, updates)
        sparse_state = {}
        for path, part in self.sparse_parts.items():
            key = SPARSE_PATH_SEP.join(path)
            table = _tree_get(params, path)
            d_table = _tree_get(grads, path)
            # Autodiff already summed duplicate lookups into d_table's rows;
            # dedupe the id stream and gather exactly those row-grads. Pad
            # slots use an out-of-range sentinel whose writes the scatter
            # drops (see optim/sparse.py).
            rows = unique_rows_with_sentinel(part.row_ids(batch),
                                             table.shape[0])
            new_table, st = sparse_adamw_update(
                table, opt_state["sparse"][key], rows,
                d_table.at[rows].get(mode="clip"), **self.sparse_kwargs)
            new_params = _tree_set(new_params, path, new_table)
            sparse_state[key] = st
        return new_params, {"dense": dense_state, "sparse": sparse_state}

    def _telemetry_out(self, out, grads, params, opt_state):
        """Fill the per-step telemetry series (device scalars that stack
        into the scan's ys — they leave the device only when the caller
        drains the chunk payload, never per step). ``param_norm`` is taken
        post-update (and post-skip-select on the guarded path), so a
        skipped step reports the norm of the params it kept."""
        out["grad_norm"] = optim_lib.global_norm(grads)
        out["param_norm"] = optim_lib.global_norm(params)
        lr = optim_lib.get_injected_lr(opt_state)
        if lr is not None:
            out["lr"] = lr
        return out

    def _one_step(self, params, opt_state, batch):
        """One optimizer step. Returns the new state plus the per-step
        output dict: always ``{"loss"}``, extended with the telemetry
        series when ``telemetry=True``."""
        loss, grads = jax.value_and_grad(self.loss_fn)(params, batch)
        params, opt_state = self._apply_update(params, opt_state, grads, batch)
        out = {"loss": loss}
        if self.telemetry:
            out = self._telemetry_out(out, grads, params, opt_state)
        return params, opt_state, out

    def _guarded_one_step(self, params, opt_state, batch):
        """One step that survives a non-finite loss or gradient.

        Finiteness of the loss and of every gradient leaf is reduced to one
        on-device scalar ``ok``; the update is computed unconditionally (a
        ``cond`` would break vmap/batching) and a per-leaf ``where`` carries
        the *old* params and opt_state through when ``ok`` is false — the
        poisoned step is skipped in place, with no host sync and no retrace.
        The output dict carries the loss (non-finite on a skipped step —
        the trainer drains it as telemetry, not into the epoch mean) and
        the skip flag.
        """
        loss, grads = jax.value_and_grad(self.loss_fn)(params, batch)
        ok = jnp.isfinite(loss)
        for leaf in jax.tree_util.tree_leaves(grads):
            ok = ok & jnp.all(jnp.isfinite(leaf))
        new_params, new_opt = self._apply_update(params, opt_state, grads,
                                                 batch)

        def keep(new, old):
            return jnp.where(ok, new, old)

        params = jax.tree_util.tree_map(keep, new_params, params)
        opt_state = jax.tree_util.tree_map(keep, new_opt, opt_state)
        out = {"loss": loss, "skipped": ~ok}
        if self.telemetry:
            out = self._telemetry_out(out, grads, params, opt_state)
        return params, opt_state, out

    def _step_out_ys(self, out):
        """A bare-loss ys keeps the telemetry-off payload identical to the
        historical ``(n,)`` array; any extra key promotes it to a dict."""
        return out["loss"] if set(out) == {"loss"} else out

    def _chunk_step(self, params, opt_state, chunk):
        def body(carry, batch):
            params, opt_state = carry
            params, opt_state, out = self._one_step(params, opt_state, batch)
            return (params, opt_state), self._step_out_ys(out)

        (params, opt_state), losses = jax.lax.scan(
            body, (params, opt_state), chunk)
        return params, opt_state, losses

    def _chunk_step_guarded(self, params, opt_state, chunk):
        def body(carry, batch):
            params, opt_state = carry
            params, opt_state, out = self._guarded_one_step(
                params, opt_state, batch)
            return (params, opt_state), out

        (params, opt_state), telemetry = jax.lax.scan(
            body, (params, opt_state), chunk)
        return params, opt_state, telemetry

    # -- the vmapped replica step ----------------------------------------------
    def _replica_one_step(self, params, opt_state, batch, active):
        if self.nonfinite_guard:
            # vmapping the guarded step gives each replica its own on-device
            # ok flag: a NaN batch (broadcast to all replicas) or a replica
            # whose own trajectory diverged skips only where it is non-finite.
            new_p, new_o, out = jax.vmap(
                self._guarded_one_step,
                in_axes=(0, 0, None))(params, opt_state, batch)
        else:
            new_p, new_o, out = jax.vmap(
                self._one_step, in_axes=(0, 0, None))(params, opt_state, batch)
        if active is None:
            return new_p, new_o, out

        def keep(new, old):
            # Freeze inactive replicas in place: expand the (R,) mask to the
            # leaf rank so params, moments, AND step counts all hold still —
            # an early-stopped replica's slice stays exactly the state it
            # stopped at, matching a sequential run that halted there.
            mask = active.reshape(active.shape + (1,) * (new.ndim - 1))
            return jnp.where(mask, new, old)

        params = jax.tree_util.tree_map(keep, new_p, params)
        opt_state = jax.tree_util.tree_map(keep, new_o, opt_state)
        if "skipped" in out:
            # A frozen replica attempted no update — don't report it skipped.
            out["skipped"] = out["skipped"] & active
        return params, opt_state, out

    def _replica_chunk_body(self, params, opt_state, chunk, active):
        def body(carry, batch):
            params, opt_state = carry
            params, opt_state, out = self._replica_one_step(
                params, opt_state, batch, active)
            return (params, opt_state), self._step_out_ys(out)

        (params, opt_state), losses = jax.lax.scan(
            body, (params, opt_state), chunk)
        return params, opt_state, losses  # losses: (n, R)

    def _replica_chunk_step(self, params, opt_state, chunk):
        return self._replica_chunk_body(params, opt_state, chunk, None)

    def _replica_chunk_step_masked(self, params, opt_state, chunk, active):
        return self._replica_chunk_body(params, opt_state, chunk, active)

    def step(self, params, opt_state, chunk, active=None):
        """One fused dispatch: ``n = chunk.shape[0]`` optimizer steps.

        Donates ``(params, opt_state)``; returns the new state plus the
        per-step loss array — ``(n,)``, or ``(n, R)`` with ``replicas=R`` —
        still on device: do not block on it before dispatching the next
        chunk. With ``nonfinite_guard=True`` the loss payload is instead a
        dict ``{"loss": (n,)|(n, R), "skipped": same-shape bool}`` where
        ``skipped[i]`` marks a step whose non-finite loss/grads were
        discarded (params and opt_state carried through unchanged). With
        ``telemetry=True`` the dict additionally carries per-step
        ``grad_norm``/``param_norm`` (and ``lr`` for inject_lr optimizers)
        series of the same shape — drain it with
        :class:`repro.obs.TelemetryDrain`.

        With replicas, ``active`` is an optional ``(R,)`` bool mask (default
        all-on): inactive replicas' state is frozen in place. An all-true
        (or omitted) mask takes the select-free fast path; a partial mask is
        a traced argument, so flipping further replicas off never retraces.
        """
        with self._mesh_context():
            if self.replicas is None:
                if active is not None:
                    raise ValueError(
                        "active mask requires TrainEngine(replicas=R)")
                return self._step(params, opt_state, chunk)
            if active is None or bool(np.asarray(active).all()):
                return self._step(params, opt_state, chunk)
            return self._step_masked(params, opt_state, chunk,
                                     jnp.asarray(active))

    def _mesh_context(self):
        """The engine's mesh as the context mesh while tracing, so kernels
        that XLA cannot partition run per data shard (kernels/ops.py)."""
        if self.mesh is None:
            return contextlib.nullcontext()
        return jax.set_mesh(self.mesh)

    def compiled(self, params, opt_state, chunk):
        """The compiled chunk program for these arguments' shapes (the one
        :meth:`step` runs; a cache hit once ``step`` has run them)."""
        with self._mesh_context():
            return self._step.lower(params, opt_state, chunk).compile()
