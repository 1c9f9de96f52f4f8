"""DCN-V2 cross-layer Pallas kernel: y = x0 * (x @ W + b) + x  [Wang 2021].

TPU mapping: grid over batch blocks; W (D, D) stays VMEM-resident across all
batch steps (D <= ~1k for recsys towers, so W is <= 4 MB — well inside the
~16 MB VMEM), the (bb, D) @ (D, D) matmul hits the MXU with fp32
accumulation, and the x0 *, + x epilogue fuses in the same tile, saving two
HBM round-trips of the (B, D) intermediate vs unfused ops.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 128


def _cross_kernel(x0_ref, x_ref, w_ref, b_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    x0 = x0_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    bias = b_ref[...].astype(jnp.float32)  # (1, D)
    xw = jnp.dot(x, w, preferred_element_type=jnp.float32)
    o_ref[...] = x0 * (xw + bias) + x


def dcn_cross_pallas(x0: jax.Array, x: jax.Array, w: jax.Array, b: jax.Array,
                     *, block_b: int = 256, interpret: bool = False) -> jax.Array:
    """x0, x: (B, D); w: (D, D); b: (D,) -> (B, D) fp32."""
    B, D = x.shape
    d_pad = (-D) % LANE
    b_pad = (-B) % block_b
    if d_pad or b_pad:
        x0 = jnp.pad(x0, ((0, b_pad), (0, d_pad)))
        x = jnp.pad(x, ((0, b_pad), (0, d_pad)))
        w = jnp.pad(w, ((0, d_pad), (0, d_pad)))
        b = jnp.pad(b, ((0, d_pad),))
    Bp, Dp = B + b_pad, D + d_pad
    out = pl.pallas_call(
        _cross_kernel,
        grid=(Bp // block_b,),
        in_specs=[
            pl.BlockSpec((block_b, Dp), lambda i: (i, 0)),
            pl.BlockSpec((block_b, Dp), lambda i: (i, 0)),
            pl.BlockSpec((Dp, Dp), lambda i: (0, 0)),
            pl.BlockSpec((1, Dp), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, Dp), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Bp, Dp), jnp.float32),
        interpret=interpret,
        name="dcn_cross",
    )(x0, x, w, b.reshape(1, -1))
    return out[:B, :D]
