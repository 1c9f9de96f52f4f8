"""Fused embedding-bag gather+reduce Pallas TPU kernel.

The hot path of CLAX at scale (paper §4.2: JAX has no EmbeddingBag / sparse
tables — we build it). One kernel serves three call sites:
  * CLAX per-item lookups (bag size 1) and multi-hot field bags,
  * recsys EmbeddingBag fields (DeepFM/AutoInt/BST/MIND),
  * GraphSAGE neighbor aggregation (ids = neighbor lists, weights = 1/deg).

TPU mapping: grid over blocks of ``BLOCK_B`` bags. Each step brings its
own slice of ids into SMEM (one block of the flattened (B*L,) id stream, so
SMEM use is O(BLOCK_B * L) whatever B is) and its (BLOCK_B, L) weights into
VMEM; the table stays in HBM (``memory_space=pl.ANY``). The kernel issues
one row DMA per (bag, slot) into a (L, BLOCK_B, Dp) VMEM scratch — D padded
to the 128-lane width — waits for them, and reduces over L with lane-
broadcast weights into the (BLOCK_B, Dp) output tile. No (B*L, D)
intermediate ever reaches HBM — that is the entire point vs the jnp
reference (gather then reduce), whose intermediate is L times the output.

Backward: the public wrapper (kernels/ops.py) has a custom VJP in XLA —
d(table) scatters the weighted output cotangents one bag slot at a time,
d(weights) is a row-dot.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
# Bags per grid block.
BLOCK_B = 128
# A 1-D SMEM block must hold a whole number of 1024-word tiles.
SMEM_TILE = 1024


def _bag_kernel(ids_ref, w_ref, table_hbm, o_ref, rows, sem):
    """One block of bags: out[b] = sum_l w[b, l] * table[ids[b * L + l]]."""
    n_slots, bb, _ = rows.shape

    def copy(b, l):  # ids_ref holds this block's ids, bag-major
        return pltpu.make_async_copy(
            table_hbm.at[pl.ds(ids_ref[b * n_slots + l], 1)],
            rows.at[l, pl.ds(b, 1)], sem)

    def start(b, carry):
        for l in range(n_slots):
            copy(b, l).start()
        return carry

    def wait(b, carry):
        for l in range(n_slots):
            copy(b, l).wait()
        return carry

    jax.lax.fori_loop(0, bb, start, 0)
    jax.lax.fori_loop(0, bb, wait, 0)
    w = w_ref[...]
    acc = jnp.zeros(o_ref.shape, jnp.float32)
    for l in range(n_slots):
        acc = acc + w[:, l:l + 1] * rows[l]
    o_ref[...] = acc


def embedding_bag_pallas(table: jax.Array, ids: jax.Array, weights: jax.Array,
                         *, interpret: bool = False) -> jax.Array:
    """out[b] = sum_l weights[b, l] * table[ids[b, l]]; ids < 0 are padding.

    table: (N, D); ids, weights: (B, L). Returns (B, D) float32.
    """
    B, L = ids.shape
    N, D = table.shape
    d_pad = (-D) % LANE
    if d_pad or table.dtype != jnp.float32:
        # Row DMAs need whole (1, 128) f32 rows: a packed narrower dtype
        # would slice inside a sublane tile. Upcasting first is exact.
        table = jnp.pad(table.astype(jnp.float32), ((0, 0), (0, d_pad)))
    Dp = D + d_pad
    bb = min(BLOCK_B, B + (-B) % 8)
    b_pad = (-B) % bb
    # Padding ids (and padded bags) read row 0 with weight forced to 0.
    weights = jnp.where(ids >= 0, weights, 0.0).astype(jnp.float32)
    safe_ids = jnp.maximum(ids, 0).astype(jnp.int32)
    if b_pad:
        weights = jnp.pad(weights, ((0, b_pad), (0, 0)))
        safe_ids = jnp.pad(safe_ids, ((0, b_pad), (0, 0)))
    grid = (B + b_pad) // bb
    # Each block's bb*L ids, padded up to whole SMEM tiles.
    ids_block = bb * L + (-(bb * L)) % SMEM_TILE
    safe_ids = jnp.pad(safe_ids.reshape(grid, bb * L),
                       ((0, 0), (0, ids_block - bb * L))).reshape(-1)
    out = pl.pallas_call(
        _bag_kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((ids_block,), lambda i: (i,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((bb, L), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((bb, Dp), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B + b_pad, Dp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((L, bb, Dp), jnp.float32),
                        pltpu.SemaphoreType.DMA(())],
        interpret=interpret,
        name="embedding_bag",
    )(safe_ids, weights, table)
    return out[:B, :D]
