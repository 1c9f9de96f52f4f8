"""Factorization-machine second-order interaction Pallas kernel.

out[b] = 0.5 * sum_d [(sum_f V[b,f,d])^2 - sum_f V[b,f,d]^2]   [Rendle 2010]

TPU mapping: one grid step per batch block; the (bb, F, D) tile lives in
VMEM and both reductions fuse into a single pass (VPU element-wise +
cross-lane reduce), so HBM traffic is exactly one read of V and one (bb, 1)
write — the op is bandwidth-bound and this is its floor. The jnp reference
materializes sum/square intermediates; XLA usually fuses them too, but the
kernel guarantees it and keeps the fp32 accumulation explicit for bf16 in.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 128


def _fm_kernel(v_ref, o_ref):
    v = v_ref[...].astype(jnp.float32)  # (bb, F, D)
    sum_f = jnp.sum(v, axis=1)          # (bb, D)
    sum_sq = jnp.square(sum_f)
    sq_sum = jnp.sum(jnp.square(v), axis=1)
    o_ref[...] = 0.5 * jnp.sum(sum_sq - sq_sum, axis=-1, keepdims=True)


def fm_interaction_pallas(v: jax.Array, *, block_b: int = 128,
                          interpret: bool = False) -> jax.Array:
    """v: (B, F, D) field embeddings -> (B,) fp32 FM logit term."""
    B, F, D = v.shape
    d_pad = (-D) % LANE
    b_pad = (-B) % block_b
    if d_pad or b_pad:
        v = jnp.pad(v, ((0, b_pad), (0, 0), (0, d_pad)))
    Bp, Dp = B + b_pad, D + d_pad
    out = pl.pallas_call(
        _fm_kernel,
        grid=(Bp // block_b,),
        in_specs=[pl.BlockSpec((block_b, F, Dp), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Bp, 1), jnp.float32),
        interpret=interpret,
        name="fm_interaction",
    )(v)
    return out[:B, 0]
