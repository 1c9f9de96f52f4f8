"""Plain float math shared by the configurations' references.

Nothing here imports the program: the hashing trick and AdamW are written
out from their definitions, as a configuration states them.
"""
from __future__ import annotations

import math
from typing import Dict, Mapping

import jax.numpy as jnp


def hashed_rows(ids: int, ratio: float, multiple: int) -> int:
    """Rows of a table that hashes ``ids`` ids ``ratio``-fold, rounded up to
    a multiple of ``multiple``."""
    n = max(int(ids / max(ratio, 1.0)), 2)
    return -(-n // multiple) * multiple


def lowbias32(ids, xor: int):
    """The 32-bit integer hash of the hashing trick: ``x ^ xor`` through
    the lowbias32 finalizer (xorshift 16, multiply 0x7FEB352D, xorshift 15,
    multiply 0x846CA68B, xorshift 16), wrapping in uint32."""
    x = ids.astype(jnp.uint32) ^ jnp.uint32(xor)
    x = (x ^ (x >> 16)) * jnp.uint32(0x7FEB352D)
    x = (x ^ (x >> 15)) * jnp.uint32(0x846CA68B)
    return x ^ (x >> 16)


def hash_rows(ids, rows: int, xor: int):
    return (lowbias32(ids, xor) % jnp.uint32(rows)).astype(jnp.int32)


def logit(p: float) -> float:
    return math.log(p) - math.log1p(-p)


def adamw(params: Mapping, grads: Mapping, mu: Mapping, nu: Mapping,
          count: int, opt: Mapping):
    """One AdamW step (decoupled weight decay) on dicts of leaves. ``count``
    is the step's number, from 1. Returns (params, mu, nu)."""
    b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
    lr, wd = opt["lr"], opt["weight_decay"]
    c1, c2 = 1.0 - b1 ** count, 1.0 - b2 ** count
    new_p: Dict = {}
    new_mu: Dict = {}
    new_nu: Dict = {}
    for k, p in params.items():
        g = grads[k]
        m = b1 * mu[k] + (1.0 - b1) * g
        v = b2 * nu[k] + (1.0 - b2) * g * g
        step = (m / c1) / (jnp.sqrt(v / c2) + eps) + wd * p
        new_p[k], new_mu[k], new_nu[k] = p - lr * step, m, v
    return new_p, new_mu, new_nu
