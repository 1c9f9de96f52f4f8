"""shard_map collectives: sharded embedding lookup + MoE dispatch.

Two lookup strategies for row-sharded tables (the CLAX scale story):

* ``sharded_embedding_lookup`` — pjit-auto: annotate shardings and let XLA
  pick collectives. Paper-faithful baseline ("let JAX handle it"). XLA
  typically all-gathers indices to every model shard and reduce-scatters or
  all-reduces the gathered rows.

* ``masked_psum_lookup`` — explicit shard_map: every model shard gathers the
  rows it owns (ids outside its range contribute zeros) and one psum over the
  model axis assembles full activations. Wire bytes = batch_items x dim x 4,
  *independent of table size*, and the gather stays local to the shard. This
  is the beyond-paper optimization measured in EXPERIMENTS.md §Perf.
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.distrib.shardings import DATA_AXES, MODEL_AXIS


def sharded_embedding_lookup(table: jax.Array, ids: jax.Array, mesh) -> jax.Array:
    """pjit-auto baseline: constrain shardings, let XLA insert collectives."""
    table = jax.lax.with_sharding_constraint(
        table, jax.sharding.NamedSharding(mesh, P(MODEL_AXIS, None)))
    ids = jax.lax.with_sharding_constraint(
        ids, jax.sharding.NamedSharding(mesh, P(DATA_AXES(mesh), None)))
    return jnp.take(table, ids, axis=0)


def masked_psum_lookup(mesh, *, batch_dims: int = 2):
    """Build a shard_map lookup: (table (N, d) P(model,None), ids (B, K) or
    (B,) P(data...)) -> embeddings (B, K, d) P(data..., None, None).

    Differentiable: the transpose scatters grads back into the owning shard
    (scatter-add stays shard-local; only activations cross the wire).
    """
    data_axes = DATA_AXES(mesh)
    ids_spec = P(data_axes, *([None] * (batch_dims - 1)))
    out_spec = P(data_axes, *([None] * batch_dims))

    def lookup(table_shard: jax.Array, ids: jax.Array) -> jax.Array:
        midx = jax.lax.axis_index(MODEL_AXIS)
        rows = table_shard.shape[0]
        local = ids - midx * rows
        owned = (local >= 0) & (local < rows)
        safe = jnp.clip(local, 0, rows - 1)
        emb = jnp.take(table_shard, safe, axis=0)
        emb = jnp.where(owned[..., None], emb, jnp.zeros_like(emb))
        return jax.lax.psum(emb, MODEL_AXIS)

    return shard_map(
        lookup, mesh=mesh,
        in_specs=(P(MODEL_AXIS, None), ids_spec),
        out_specs=out_spec,
    )


def moe_all_to_all_dispatch(mesh, n_experts: int, capacity: int):
    """GShard-style capacity-bounded MoE dispatch (top-1), shard_map body.

    Each data shard routes its local tokens into per-expert-shard send
    buffers (capacity-bounded, overflow dropped), all_to_all exchanges them
    across the model axis, expert shards run their local experts, and the
    reverse all_to_all + scatter restores token order. Exposed for the MoE
    layer in repro/models/lm/moe.py; see that module for the full layer.
    """
    raise NotImplementedError(
        "dispatch lives in repro.models.lm.moe.MoELayer (kept with the model)")
