"""Fused examination-chain NLL Pallas kernel (DCM / CCM / DBN / SDBN loss).

One pass from per-position probability factors to the scalar loss:

    factors -> capped affine death-odds scan -> conditional log-probs -> NLL

The unfused path (PR 1) materializes the (B, K) odds, the (B, K) conditional
log-probabilities, and the (B, K) per-element BCE before the masked mean —
three HBM round-trips of the batch. Here the whole chain runs inside one VMEM
tile: the affine recurrence z_k = a_k z_{k-1} + b_k is solved in-register with
a Hillis-Steele doubling scan along the lane axis (ceil(log2 K) capped
multiply-add rounds), and only a (G, 1, 1) partial sum / count pair per grid
block ever leaves the kernel (3-D so each block's (1, 1) tile spans the
whole trailing dims, which the TPU's (8, 128) tiling rule accepts).

Numerics follow :mod:`repro.core.recursions` exactly: the same ODDS_FLOOR on
denominators, the same ODDS_CAP saturation on the odds (finite log-probability
with zero gradient for dead chains, never inf/NaN), and the same GROWTH_CAP on
composite growth products so the capped combine stays order-insensitive for
every un-saturated span. The NLL (:func:`_click_nll`) takes the ref's own
steps, so that it stays within float rounding of the ref where the
transcendentals are approximate (a TPU's f32 log is good to about 1e-6
relative, not 1 ulp):

    log P(C=1)  = min(x, 0) - log1p(r + e + r e)              e = exp(-|x|)
    log P(C=0)  = log1mexp(log P(C=1))

A difference of two logs for the complement, which this kernel used before,
was 2.8e-5 relative off the ref for the Baidu-width DBN loss on a v5e: the
two logs are up to ten times larger than their difference.

Gradients never flow through this lowering: the public
:func:`repro.kernels.ops.examination_nll` wraps every impl in a custom VJP
whose backward pass is ``jax.vjp`` of the ref composition, so all impls share
the saturating gradient semantics of ``core/recursions`` bit-for-bit.

``examination_nll_xla`` is the fused jnp counterpart (same NLL, odds via
the associative scan) — the fast path on CPU/GPU where Pallas only
interprets.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 128
LOG2 = math.log(2.0)


def _click_nll(x, c, r):
    """Per-element click NLL from attraction logits ``x``, clicks ``c`` and
    death odds ``r``, in the ref's steps (``log_bce`` of the conditional
    log-probability). Pallas TPU has no expm1, so where p > 1/2 the
    complement 1 - p = (s + r (1 + e)) / (1 + r + e + r e), s = e if x >= 0
    else 1, is formed directly and logged; below, log1p(-exp(log p)) as in
    ``log1mexp``. Both branches are guarded so neither makes a NaN."""
    e = jnp.exp(-jnp.abs(x))
    d = r + e + r * e
    log_p = jnp.minimum(x, 0.0) - jnp.log1p(d)
    near_one = log_p > -LOG2
    s = jnp.where(x >= 0, e, 1.0)
    q = jnp.where(near_one, (s + r * (1.0 + e)) / (1.0 + d), 0.5)
    log_1mp = jnp.where(near_one, jnp.log(q), jnp.log1p(
        -jnp.exp(jnp.where(near_one, -LOG2, log_p))))
    return -(c * log_p + (1.0 - c) * log_1mp)


def _examination_nll_kernel(floor, cap, growth_cap,
                            x_ref, c_ref, m_ref, ss_ref, pd_ref, pr_ref,
                            prn_ref, sum_ref, cnt_ref):
    f32 = jnp.float32
    x = x_ref[...].astype(f32)        # (bb, Kp) attraction logits
    c = c_ref[...].astype(f32)        # clicks
    m = m_ref[...].astype(f32)        # mask weights
    pss = ss_ref[...].astype(f32)     # p_skip_survive
    pd = pd_ref[...].astype(f32)      # p_death
    pr = pr_ref[...].astype(f32)      # p_reset
    prn = prn_ref[...].astype(f32)    # p_reset_not

    clicked = (c > 0).astype(f32)
    keep = 1.0 - clicked
    a = keep / jnp.maximum(pss, floor)
    b = jnp.minimum(a * pd + clicked * (prn / jnp.maximum(pr, floor)), cap)

    # Hillis-Steele inclusive scan of z_k = a_k z_{k-1} + b_k along lanes.
    # Each round folds the prefix `off` positions back: identity fill
    # (a=1, b=0) below the offset. b must update before a (the combine uses
    # the pre-round a as the right factor). Caps mirror _affine_scan_impl.
    kp = x.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    off = 1
    while off < kp:
        a_sh = jnp.where(lane >= off, jnp.roll(a, off, axis=1), 1.0)
        b_sh = jnp.where(lane >= off, jnp.roll(b, off, axis=1), 0.0)
        b = jnp.minimum(a * b_sh + b, cap)
        a = jnp.minimum(a * a_sh, growth_cap)
        off *= 2

    # r_k = z_{k-1} (virtual sure-reset start: r_0 = 0).
    r = jnp.where(lane >= 1, jnp.roll(b, 1, axis=1), 0.0)

    nll = _click_nll(x, c, r)
    sum_ref[...] = jnp.sum(nll * m, keepdims=True).reshape(1, 1, 1)
    cnt_ref[...] = jnp.sum(m, keepdims=True).reshape(1, 1, 1)


def examination_nll_partials(attr_logits: jax.Array, clicks: jax.Array,
                             mask: jax.Array, p_skip_survive: jax.Array,
                             p_death: jax.Array, p_reset: jax.Array,
                             p_reset_not: jax.Array, *, block_b: int = 256,
                             interpret: bool = False):
    """All inputs (B, K) -> per-block (masked NLL sum, mask count), each
    (G, 1, 1) for G = ceil(B / block_b)."""
    from repro.core.recursions import GROWTH_CAP, ODDS_CAP, ODDS_FLOOR

    B, K = attr_logits.shape
    k_pad = (-K) % LANE
    b_pad = (-B) % block_b
    m = mask.astype(jnp.float32)
    inputs = [attr_logits.astype(jnp.float32), clicks.astype(jnp.float32), m,
              p_skip_survive.astype(jnp.float32), p_death.astype(jnp.float32),
              p_reset.astype(jnp.float32), p_reset_not.astype(jnp.float32)]
    if k_pad or b_pad:
        # Identity padding: no click, unit survive, sure reset, zero weight —
        # padded positions are scan no-ops and drop out of the masked sum.
        fills = (0.0, 0.0, 0.0, 1.0, 0.0, 1.0, 0.0)
        inputs = [jnp.pad(arr, ((0, b_pad), (0, k_pad)), constant_values=f)
                  for arr, f in zip(inputs, fills)]
    grid = (inputs[0].shape[0] // block_b,)
    kernel = functools.partial(_examination_nll_kernel,
                               ODDS_FLOOR, ODDS_CAP, GROWTH_CAP)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_b, inputs[0].shape[1]),
                               lambda i: (i, 0))] * 7,
        out_specs=[pl.BlockSpec((1, 1, 1), lambda i: (i, 0, 0))] * 2,
        out_shape=[jax.ShapeDtypeStruct((grid[0], 1, 1), jnp.float32)] * 2,
        interpret=interpret,
        name="examination_nll",
    )(*inputs)


def examination_nll_xla(attr_logits: jax.Array, clicks: jax.Array,
                        mask: jax.Array, p_skip_survive: jax.Array,
                        p_death: jax.Array, p_reset: jax.Array,
                        p_reset_not: jax.Array) -> jax.Array:
    """Fused jnp form: associative-scan odds + the kernel's NLL."""
    from repro.core.recursions import conditional_examination_odds

    x = attr_logits.astype(jnp.float32)
    c = clicks.astype(jnp.float32)
    m = mask.astype(jnp.float32)
    r = conditional_examination_odds(c, p_skip_survive, p_death, p_reset,
                                     p_reset_not)
    nll = _click_nll(x, c, r)
    return jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)
