"""FlashAttention-2-style tiled attention Pallas TPU kernel (GQA-aware).

Online-softmax attention with (q-block, kv-block) tiling: grid is
(B, Hq, nQ, nK) with the KV axis varying fastest; the output tile plus the
running (m, l, acc) statistics stay in VMEM scratch across all KV steps, so
HBM traffic is one pass over Q/K/V and one write of O — the FlashAttention
IO bound — instead of the O(S^2) score matrix XLA would materialize.

GQA is folded into the K/V BlockSpec index maps (h // group), so grouped
heads never get physically repeated in HBM (the jnp reference does repeat —
that is part of what the kernel saves).

TPU notes: all tiles are (…, 128)-lane aligned; the running max/sum ride a
(bq, 128) broadcast tile (stats live in lanes, standard TPU FA layout);
matmuls request fp32 accumulation via preferred_element_type.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANE = 128


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
               scale: float, causal: bool, block_q: int, block_k: int,
               seq_q: int, seq_k: int):
    qi, ki = pl.program_id(2), pl.program_id(3)
    n_k = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)  # (bq, Dh)
    k = k_ref[0, 0].astype(jnp.float32)  # (bk, Dh)
    v = v_ref[0, 0].astype(jnp.float32)  # (bk, Dh)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    if causal:
        # absolute positions; decode offset aligns q to the END of kv
        q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) \
            + (seq_k - seq_q)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)

    m_prev = m_scr[:, :1]                      # (bq, 1)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                     # (bq, bk)
    correction = jnp.exp(m_prev - m_new)       # (bq, 1)
    l_new = correction * l_scr[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * correction + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == n_k - 1)
    def _finish():
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[:, :1], 1e-30)
                       ).astype(o_ref.dtype)


def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = False, scale: float | None = None,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool = False) -> jax.Array:
    """q: (B, Hq, Sq, Dh); k, v: (B, Hkv, Skv, Dh); Hq % Hkv == 0."""
    B, Hq, Sq, Dh = q.shape
    _, Hkv, Sk, _ = k.shape
    group = Hq // Hkv
    scale = scale if scale is not None else 1.0 / (Dh ** 0.5)
    block_q = min(block_q, Sq)
    # shrink block_k to the largest divisor of Skv (no KV padding: padded KV
    # rows would need an extra validity mask in the non-causal path)
    block_k = min(block_k, Sk)
    while Sk % block_k:
        block_k -= 1
    q_pad = (-Sq) % block_q
    if q_pad:
        # padded q rows sit past the causal horizon (they see everything),
        # produce finite garbage, and are sliced off below.
        q = jnp.pad(q, ((0, 0), (0, 0), (0, q_pad), (0, 0)))
    nQ, nK = (Sq + q_pad) // block_q, Sk // block_k

    kernel = functools.partial(
        _fa_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, seq_q=Sq, seq_k=Sk)
    out = pl.pallas_call(
        kernel,
        grid=(B, Hq, nQ, nK),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, Dh), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, Dh),
                         lambda b, h, qi, ki, g=group: (b, h // g, ki, 0)),
            pl.BlockSpec((1, 1, block_k, Dh),
                         lambda b, h, qi, ki, g=group: (b, h // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, Dh),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq + q_pad, Dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, LANE), jnp.float32),  # running max
            pltpu.VMEM((block_q, LANE), jnp.float32),  # running sum
            pltpu.VMEM((block_q, Dh), jnp.float32),    # output accumulator
        ],
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
    return out[:, :, :Sq, :]
