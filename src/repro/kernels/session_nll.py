"""Fused session negative-log-likelihood Pallas kernel.

Computes the masked-mean Bernoulli click NLL directly from logits:

    nll[b, k] = -[c log sigmoid(x) + (1-c) log(1 - sigmoid(x))]
              = softplus(x) - c * x
    out      = sum(mask * nll) / max(sum(mask), 1)

The jnp path materializes three (B, K) intermediates (log_sigmoid, log1mexp,
BCE) before the reduction; here the whole chain runs per VMEM tile and only
per-block partial sums leave the kernel, so HBM traffic is one read of the
logits/clicks/mask and a (G, 1, 1) write (3-D so each block's (1, 1)
tile spans the whole trailing dims, as the TPU's (8, 128) tiling rule
requires). The final G-element reduction happens
outside the kernel (G = B / block_b scalars — negligible).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 128


def _session_nll_kernel(x_ref, c_ref, m_ref, sum_ref, cnt_ref):
    x = x_ref[...].astype(jnp.float32)   # (bb, Kp)
    c = c_ref[...].astype(jnp.float32)
    m = m_ref[...].astype(jnp.float32)
    # softplus(x) - c*x, the stable fused form of log_sigmoid -> log1mexp -> BCE
    nll = jnp.maximum(x, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(x))) - c * x
    sum_ref[...] = jnp.sum(nll * m, keepdims=True).reshape(1, 1, 1)
    cnt_ref[...] = jnp.sum(m, keepdims=True).reshape(1, 1, 1)


def session_nll_partials(logits: jax.Array, clicks: jax.Array,
                         mask: jax.Array, *, block_b: int = 256,
                         interpret: bool = False):
    """logits/clicks/mask: (B, K) -> per-block (masked NLL sum, mask
    count), each (G, 1, 1) for G = ceil(B / block_b)."""
    B, K = logits.shape
    k_pad = (-K) % LANE
    b_pad = (-B) % block_b
    m = mask.astype(jnp.float32)
    if k_pad or b_pad:
        logits = jnp.pad(logits, ((0, b_pad), (0, k_pad)))
        clicks = jnp.pad(clicks.astype(jnp.float32), ((0, b_pad), (0, k_pad)))
        m = jnp.pad(m, ((0, b_pad), (0, k_pad)))  # zero weight on padding
    grid = (logits.shape[0] // block_b,)
    return pl.pallas_call(
        _session_nll_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_b, logits.shape[1]), lambda i: (i, 0))] * 3,
        out_specs=[pl.BlockSpec((1, 1, 1), lambda i: (i, 0, 0))] * 2,
        out_shape=[jax.ShapeDtypeStruct((grid[0], 1, 1), jnp.float32)] * 2,
        interpret=interpret,
        name="session_nll",
    )(logits, clicks.astype(logits.dtype), m.astype(logits.dtype))
