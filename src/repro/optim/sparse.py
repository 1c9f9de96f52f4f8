"""Sparse-row (lazy) AdamW for huge embedding tables — beyond-paper opt.

The paper notes ("Our implementation currently lacks support for sparse
embeddings") that dense optimizers touch the ENTIRE table every step even
though only the batch's rows have non-zero gradient. At 2^31-scale tables the
dense AdamW read-modify-write dominates the memory roofline term.

This module implements the production fix (torch SparseAdam / DLRM-style):
the train step computes gradients **with respect to the gathered rows** (a
(B*K, d) tensor), and the optimizer scatter-updates only those rows of the
parameter/moment tables:

    emb = take(table, ids)               # forward gather (unchanged)
    d_emb = grad wrt emb                 # (N_lookups, d), NOT (R, d)
    rows = segment_sum(d_emb, ids)       # dedupe duplicate ids in the batch
    m[ids], v[ids], table[ids] updated via .at[rows]

**Lazy-Adam semantics** (standard for sparse training, torch SparseAdam):

* A row is *touched* on a step iff it appears in that step's batch (for
  click models: in ``EmbeddingParameter.row_ids(batch)``, including rows
  reached only through masked padding items — exactly the rows whose dense
  gradient can be non-zero).
* Touched rows update exactly like dense AdamW with the same
  hyperparameters: on a table whose every row is touched every step, lazy
  and dense AdamW produce bit-identical params and moments
  (tests/test_engine.py pins this).
* Untouched rows are left **entirely** alone: their moments do not decay,
  they receive no weight decay, and they do not catch up on missed bias
  correction when next touched (the correction uses the global step count,
  not a per-row count).

Fixed-size dedupe pads the unique-row buffer with an **out-of-range
sentinel** (``n_rows``): scatter updates at out-of-bounds indices are
dropped (``mode="drop"``), so padding slots are true no-ops — they cannot
alias row 0 and decay its moments (the old ``fill_value=0`` convention did
exactly that whenever row 0 sat out a batch).

HBM traffic of the optimizer state update drops from 3×O(R·d) dense
read-modify-writes (params, mu, nu) per step to O(unique_batch_rows·d).
Two integration points: :func:`make_sparse_embedding_train_step` (fully
lazy — differentiates w.r.t. the gathered rows, never materializes an
(R, d) gradient) and ``TrainEngine(sparse_tables=True)`` (takes the rows
of the autodiff table gradient, so the scatter-shaped gradient still
materializes but the optimizer state update is O(U·d)).
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp


class SparseTableState(NamedTuple):
    count: jax.Array  # global step (for bias correction)
    mu: jax.Array     # first moment, shaped like the table
    nu: jax.Array     # second moment, shaped like the table


def init_sparse_table_state(table: jax.Array,
                            moment_dtype=jnp.float32) -> SparseTableState:
    return SparseTableState(
        count=jnp.zeros((), jnp.int32),
        mu=jnp.zeros_like(table, dtype=moment_dtype),
        nu=jnp.zeros_like(table, dtype=moment_dtype),
    )


def unique_rows_with_sentinel(ids: jax.Array, n_rows: int, *,
                              return_inverse: bool = False,
                              max_unique: int | None = None):
    """Fixed-size dedupe of a row-id stream, padded with the out-of-range
    sentinel ``n_rows``.

    The single home of the sentinel convention: every producer of a row
    buffer for :func:`sparse_adamw_update` must pad with exactly ``n_rows``
    (an index the ``mode="drop"`` scatters discard) — any in-range fill
    value would alias a real row and decay its moments.
    """
    flat = ids.reshape(-1)
    return jnp.unique(flat, return_inverse=return_inverse,
                      size=max_unique or flat.shape[0], fill_value=n_rows)


def sparse_row_grads(row_grads: jax.Array, ids: jax.Array, n_rows: int,
                     max_unique: int | None = None
                     ) -> Tuple[jax.Array, jax.Array]:
    """Dedupe per-lookup grads (ids' shape plus the table's row shape: none
    for a 1-D scalar table, (d,) for a wide one) into per-unique-row grads.

    Returns (unique_ids (U,), grads (U, *row)) with U = min(N, max_unique or N);
    surplus slots hold the out-of-range sentinel ``n_rows`` (zero gradient),
    which :func:`sparse_adamw_update` scatters with ``mode="drop"`` — a true
    no-op that touches no real row.
    """
    flat_ids = ids.reshape(-1)
    g = row_grads.reshape(flat_ids.shape[0], *row_grads.shape[ids.ndim:])
    unique_ids, inv = unique_rows_with_sentinel(
        flat_ids, n_rows, return_inverse=True, max_unique=max_unique)
    grads = jax.ops.segment_sum(g, inv.reshape(-1),
                                num_segments=unique_ids.shape[0])
    return unique_ids, grads


def sparse_adamw_update(table: jax.Array, state: SparseTableState,
                        unique_ids: jax.Array, grads: jax.Array, *,
                        lr: float, b1: float = 0.9, b2: float = 0.999,
                        eps: float = 1e-8, weight_decay: float = 0.0
                        ) -> Tuple[jax.Array, SparseTableState]:
    """Scatter-update only the touched rows of (table, mu, nu).

    ``unique_ids`` may contain out-of-range sentinel entries (padding from a
    fixed-size dedupe): their gathers clamp to the last row (the computed
    garbage is discarded) and their scatters are dropped, so sentinel slots
    modify nothing.
    """
    count = state.count + 1
    g32 = grads.astype(jnp.float32)
    rows = unique_ids
    mu_rows = state.mu.at[rows].get(mode="clip").astype(jnp.float32)
    nu_rows = state.nu.at[rows].get(mode="clip").astype(jnp.float32)
    mu_new = b1 * mu_rows + (1 - b1) * g32
    nu_new = b2 * nu_rows + (1 - b2) * jnp.square(g32)
    c1 = 1 - b1 ** count.astype(jnp.float32)
    c2 = 1 - b2 ** count.astype(jnp.float32)
    update = (mu_new / c1) / (jnp.sqrt(nu_new / c2) + eps)
    p_rows = table.at[rows].get(mode="clip").astype(jnp.float32)
    if weight_decay:
        update = update + weight_decay * p_rows
    new_rows = (p_rows - lr * update).astype(table.dtype)
    return (
        table.at[rows].set(new_rows, mode="drop"),
        SparseTableState(
            count=count,
            mu=state.mu.at[rows].set(mu_new.astype(state.mu.dtype), mode="drop"),
            nu=state.nu.at[rows].set(nu_new.astype(state.nu.dtype), mode="drop"),
        ),
    )


def make_sparse_embedding_train_step(forward_from_rows, gather_rows, *,
                                     lr: float, n_rows: int,
                                     weight_decay: float = 0.0,
                                     dense_optimizer=None):
    """Build a train step that is sparse in the table and dense elsewhere.

    * ``gather_rows(table, batch) -> (rows, ids)`` — the forward gather,
      returning the gathered row values and their ids.
    * ``forward_from_rows(dense_params, rows, batch) -> loss`` — the rest of
      the model, treating the gathered rows as an input.
    * ``dense_optimizer`` — repro.optim transformation for the dense params.
    """
    from repro import optim as optim_lib

    def init(table, dense_params):
        dense_opt = (dense_optimizer.init(dense_params)
                     if dense_optimizer else None)
        return init_sparse_table_state(table), dense_opt

    def step(table, sparse_state, dense_params, dense_opt, batch):
        rows, ids = gather_rows(table, batch)

        def loss_fn(rows_in, dense_in):
            return forward_from_rows(dense_in, rows_in, batch)

        loss, (d_rows, d_dense) = jax.value_and_grad(
            loss_fn, argnums=(0, 1))(rows, dense_params)
        uids, ugrads = sparse_row_grads(d_rows, ids, n_rows)
        table, sparse_state = sparse_adamw_update(
            table, sparse_state, uids, ugrads, lr=lr,
            weight_decay=weight_decay)
        if dense_optimizer is not None:
            updates, dense_opt = dense_optimizer.update(
                d_dense, dense_opt, dense_params)
            dense_params = optim_lib.apply_updates(dense_params, updates)
        return table, sparse_state, dense_params, dense_opt, loss

    return init, step
