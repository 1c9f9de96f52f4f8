"""The paper's own workload: CLAX click models at Baidu-ULTR scale.

2^31 query-document pairs hashed 10x down (the paper's Figure 3 setting) to a
~214.7M-row scalar-logit table, row-sharded over the ``model`` mesh axis;
sessions data-parallel. Not part of the assigned-40 grid — recorded as extra
cells in EXPERIMENTS.md because the paper technique itself is the most
representative hillclimb target.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import optim as optim_lib
from repro.configs.common import Cell, dp_axes, named, sds
from repro.core import (EmbeddingParameterConfig, Compression,
                        MODEL_REGISTRY)

POSITIONS = 10
# 2^31 ids hashed 10x, rounded to divide the model axis (16) and 512 devices.
TABLE_ROWS = 214_748_160

SHAPES = {
    "train_batch": dict(batch=65536, kind="train"),
    "serve_bulk": dict(batch=262144, kind="serve"),
}


def make_model(kind: str):
    """A ``MODEL_REGISTRY`` click model (e.g. "ubm", "dbn", "dctr") whose
    every embedding table is the hashed 2^31-id table of this workload."""
    attraction = EmbeddingParameterConfig(
        parameters=1 << 31, compression=Compression.HASH,
        compression_ratio=10.0, baseline_correction=True,
        init_logit=-2.0)
    return MODEL_REGISTRY[kind](positions=POSITIONS, attraction=attraction,
                                satisfaction=attraction)


def _param_specs(model):
    like = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))

    def rule(path, leaf):
        # huge hashed tables row-sharded; everything else replicated
        if leaf.ndim >= 1 and leaf.shape[0] >= 1_000_000:
            return P("model", *([None] * (leaf.ndim - 1)))
        return P(*([None] * leaf.ndim))

    return jax.tree_util.tree_map_with_path(rule, like), like


def build_cell(shape: str, mesh, kind: str = "ubm") -> Cell:
    info = SHAPES[shape]
    B = info["batch"]
    dp = dp_axes(mesh)
    model = make_model(kind)
    pspecs, params = _param_specs(model)

    batch = {
        "positions": sds((B, POSITIONS), jnp.int32),
        "query_doc_ids": sds((B, POSITIONS), jnp.int32),
        "clicks": sds((B, POSITIONS), jnp.float32),
        "mask": sds((B, POSITIONS), jnp.bool_),
    }
    bspecs = {k: P(dp, None) for k in batch}

    if info["kind"] == "train":
        optimizer = optim_lib.adamw(3e-3, weight_decay=1e-4)
        opt_state = jax.eval_shape(optimizer.init, params)
        from repro.optim.optimizers import ScaleByAdamState
        ospecs = (ScaleByAdamState(count=P(), mu=pspecs, nu=pspecs), (), ())

        def train_step(params, opt_state, batch):
            loss, grads = jax.value_and_grad(model.compute_loss)(params, batch)
            updates, opt_state = optimizer.update(grads, opt_state, params)
            return optim_lib.apply_updates(params, updates), opt_state, loss

        return Cell(
            arch=f"clax-{kind}-baidu", shape=shape, kind="train",
            fn=train_step, args=(params, opt_state, batch),
            in_shardings=(named(mesh, pspecs), named(mesh, ospecs),
                          named(mesh, bspecs)),
            out_shardings=(named(mesh, pspecs), named(mesh, ospecs),
                           named(mesh, P())),
            # log-space chain: ~60 flops/item fwd, 3x for bwd — gather-bound.
            model_flops=3.0 * 60 * B * POSITIONS,
            donate=(0, 1),
            notes="2^31 ids hashed 10x -> 214.7M rows P('model'); AdamW",
        )

    def serve(params, batch):
        return model.predict_clicks(params, batch)

    return Cell(
        arch=f"clax-{kind}-baidu", shape=shape, kind="serve",
        fn=serve, args=(params, batch),
        in_shardings=(named(mesh, pspecs), named(mesh, bspecs)),
        out_shardings=named(mesh, P(dp, None)),
        model_flops=1.0 * 60 * B * POSITIONS * POSITIONS,
        notes="unconditional click prediction (UBM marginalization O(K^2))",
    )
