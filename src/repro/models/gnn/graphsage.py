"""GraphSAGE [Hamilton et al. 2017, arXiv:1706.02216], mean aggregator.

Two execution regimes (kernel_taxonomy §GNN: SpMM / gather-scatter):

* **full-graph**: message passing over the raw edge list via
  ``jax.ops.segment_sum`` (src->dst scatter). Distribution: edges sharded
  over every mesh axis, node states replicated per device; each shard
  aggregates its edge slice locally and one psum merges partial node sums —
  collective bytes = n_nodes * d * 4 per layer, independent of edge count.

* **sampled minibatch**: fixed-fanout neighbor tensors from the host-side
  :class:`~repro.models.gnn.sampler.NeighborSampler`. The per-hop
  mean-aggregation is exactly the embedding_bag kernel regime
  (gather + segment-mean with static bag size), so the TPU path reuses
  kernels/embedding_bag.py.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro import optim as optim_lib


@dataclasses.dataclass
class SAGEConfig:
    name: str = "graphsage"
    n_layers: int = 2
    d_in: int = 602
    d_hidden: int = 128
    n_classes: int = 41
    sample_sizes: Sequence[int] = (25, 10)
    dtype: Any = jnp.float32
    # beyond-paper: edges pre-partitioned by dst range -> each shard owns a
    # disjoint node block; aggregation needs NO reduction (output is node-
    # sharded) and only one all-gather of h per layer (half an all-reduce's
    # wire). Input contract: edge i lives on the shard owning dst[i].
    partitioned_edges: bool = False


def init_params(cfg: SAGEConfig, rng: jax.Array):
    dims = [cfg.d_in] + [cfg.d_hidden] * (cfg.n_layers - 1) + [cfg.n_classes]
    keys = jax.random.split(rng, 2 * cfg.n_layers)
    params = {}
    for l in range(cfg.n_layers):
        fan_in = dims[l]
        std = (1.0 / fan_in) ** 0.5
        params[f"layer_{l}"] = {
            "w_self": (jax.random.normal(keys[2 * l], (dims[l], dims[l + 1]))
                       * std).astype(cfg.dtype),
            "w_neigh": (jax.random.normal(keys[2 * l + 1], (dims[l], dims[l + 1]))
                        * std).astype(cfg.dtype),
            "bias": jnp.zeros((dims[l + 1],), cfg.dtype),
        }
    return params


def param_specs(cfg: SAGEConfig, mesh):
    """Weights are tiny -> replicated; graph tensors shard over all axes."""
    return jax.tree_util.tree_map(lambda _: P(), init_shapes(cfg))


def init_shapes(cfg: SAGEConfig):
    dims = [cfg.d_in] + [cfg.d_hidden] * (cfg.n_layers - 1) + [cfg.n_classes]
    return {f"layer_{l}": {"w_self": jnp.zeros((dims[l], dims[l + 1])),
                           "w_neigh": jnp.zeros((dims[l], dims[l + 1])),
                           "bias": jnp.zeros((dims[l + 1],))}
            for l in range(cfg.n_layers)}


# ---------------------------------------------------------------------------
# Full-graph path
# ---------------------------------------------------------------------------

def _aggregate_dense(h, src, dst, n_nodes, degree_inv, edge_weight=None):
    msgs = jnp.take(h, src, axis=0)
    if edge_weight is not None:
        msgs = msgs * edge_weight[:, None]
    agg = jax.ops.segment_sum(msgs, dst, num_segments=n_nodes)
    return agg * degree_inv[:, None]


def _aggregate_sharded(mesh, h, src, dst, n_nodes, degree_inv,
                        edge_weight=None):
    """Edge-sharded mean aggregation: local segment_sum + psum over shards.

    Edges are padded to a multiple of the device count; padded entries carry
    edge_weight 0 so they contribute nothing."""
    axes = tuple(mesh.axis_names)

    def body(h_rep, src_loc, dst_loc, w_loc):
        msgs = jnp.take(h_rep, src_loc, axis=0) * w_loc[:, None]
        partial = jax.ops.segment_sum(msgs, dst_loc, num_segments=n_nodes)
        return jax.lax.psum(partial, axes)

    if edge_weight is None:
        edge_weight = jnp.ones(src.shape, h.dtype)
    agg = shard_map(
        body, mesh=mesh,
        in_specs=(P(None, None), P(axes), P(axes), P(axes)),
        out_specs=P(None, None), check_vma=False,
    )(h, src, dst, edge_weight)
    return agg * degree_inv[:, None]


def _aggregate_dst_partitioned(mesh, h, src, dst, n_nodes, degree_inv,
                               edge_weight=None):
    """Aggregation with dst-partitioned edges: shard i's edge slice only
    targets nodes [i*Nl, (i+1)*Nl), so the local segment_sum IS the final
    block — no psum. h arrives replicated (one all-gather per layer upstream,
    i.e. half the wire of the replicated+psum scheme)."""
    axes = tuple(mesh.axis_names)
    n_shards = 1
    for a in axes:
        n_shards *= mesh.shape[a]
    n_local = n_nodes // n_shards

    def body(h_rep, src_loc, dst_loc, w_loc, deg_loc):
        shard = jnp.zeros((), jnp.int32)
        for a in axes:
            shard = shard * mesh.shape[a] + jax.lax.axis_index(a)
        msgs = jnp.take(h_rep, src_loc, axis=0) * w_loc[:, None]
        local = jax.ops.segment_sum(msgs, dst_loc - shard * n_local,
                                    num_segments=n_local)
        return local * deg_loc[:, None]

    if edge_weight is None:
        edge_weight = jnp.ones(src.shape, h.dtype)
    agg = shard_map(
        body, mesh=mesh,
        in_specs=(P(None, None), P(axes), P(axes), P(axes), P(axes)),
        out_specs=P(axes, None), check_vma=False,
    )(h, src, dst, edge_weight, degree_inv)
    return agg  # node-sharded (gathered lazily by the next matmul)


def full_graph_forward(cfg: SAGEConfig, params, graph: Dict[str, jax.Array],
                       mesh=None) -> jax.Array:
    """graph: features (N, F), src (E,), dst (E,), degree_inv (N,)."""
    h = graph["features"].astype(cfg.dtype)
    n_nodes = h.shape[0]
    for l in range(cfg.n_layers):
        lp = params[f"layer_{l}"]
        ew = graph.get("edge_weight")
        if mesh is None:
            neigh = _aggregate_dense(h, graph["src"], graph["dst"], n_nodes,
                                     graph["degree_inv"], ew)
        elif cfg.partitioned_edges:
            neigh = _aggregate_dst_partitioned(mesh, h, graph["src"],
                                               graph["dst"], n_nodes,
                                               graph["degree_inv"], ew)
        else:
            neigh = _aggregate_sharded(mesh, h, graph["src"], graph["dst"],
                                       n_nodes, graph["degree_inv"], ew)
        h = (h @ lp["w_self"].astype(cfg.dtype)
             + neigh @ lp["w_neigh"].astype(cfg.dtype) + lp["bias"])
        if l < cfg.n_layers - 1:
            h = jax.nn.relu(h)
    return h  # (N, n_classes)


# ---------------------------------------------------------------------------
# Sampled-minibatch path (fixed fanout)
# ---------------------------------------------------------------------------

def sampled_forward(cfg: SAGEConfig, params, batch: Dict[str, jax.Array],
                    use_kernel_bag: bool = False) -> jax.Array:
    """batch: feats_hop_0 (B, F), feats_hop_1 (B, f1, F),
    feats_hop_2 (B, f1, f2, F), ... (n_layers hops; -1-padded neighbors have
    zero features and a validity mask per hop).

    2-layer SAGE: aggregate hop2 -> hop1, then hop1 -> hop0.
    """
    hops = [batch[f"feats_hop_{i}"].astype(cfg.dtype)
            for i in range(cfg.n_layers + 1)]
    masks = [batch.get(f"mask_hop_{i}") for i in range(cfg.n_layers + 1)]

    def mean_agg(x, mask):
        # x: (..., fanout, F) -> (..., F) masked mean over the fanout dim
        if mask is None:
            return jnp.mean(x, axis=-2)
        m = mask.astype(x.dtype)[..., None]
        return jnp.sum(x * m, axis=-2) / jnp.maximum(
            jnp.sum(m, axis=-2), 1.0)

    # Iteratively collapse the deepest hop.
    for l in range(cfg.n_layers):
        lp = params[f"layer_{l}"]
        new_hops = []
        for depth in range(len(hops) - 1):
            self_h = hops[depth]
            neigh_h = mean_agg(hops[depth + 1], masks[depth + 1])
            h = (self_h @ lp["w_self"].astype(cfg.dtype)
                 + neigh_h @ lp["w_neigh"].astype(cfg.dtype) + lp["bias"])
            if l < cfg.n_layers - 1:
                h = jax.nn.relu(h)
            new_hops.append(h)
        hops = new_hops
        masks = masks[:len(hops)]
    return hops[0]  # (B, n_classes)


# ---------------------------------------------------------------------------
# Loss / train steps
# ---------------------------------------------------------------------------

def node_classification_loss(logits, labels, mask=None):
    logz = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    gold = jnp.take_along_axis(logits.astype(jnp.float32),
                               jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is None:
        mask = labels >= 0
    m = mask.astype(jnp.float32)
    return jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)


def make_full_graph_train_step(cfg: SAGEConfig, optimizer=None, mesh=None):
    optimizer = optimizer or optim_lib.adam(1e-2)

    def step(params, opt_state, graph):
        def loss_fn(p):
            logits = full_graph_forward(cfg, p, graph, mesh)
            return node_classification_loss(logits, graph["labels"],
                                            graph.get("label_mask"))

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optim_lib.apply_updates(params, updates), opt_state, loss

    return step


def make_sampled_train_step(cfg: SAGEConfig, optimizer=None):
    optimizer = optimizer or optim_lib.adam(1e-2)

    def step(params, opt_state, batch):
        def loss_fn(p):
            logits = sampled_forward(cfg, p, batch)
            return node_classification_loss(logits, batch["labels"])

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optim_lib.apply_updates(params, updates), opt_state, loss

    return step
