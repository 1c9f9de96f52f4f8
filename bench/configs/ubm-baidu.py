"""Plain reference of ``ubm-baidu``: the user browsing model (Dupret and
Piwowarski 2008; CLAX eq. 25-26) over one hashed table.

Written from the model's definition in ``jax.numpy``, with nothing of the
program:

- attraction gamma_k = sigmoid(A[h(d_k)] + a0); examination
  theta_{k,k'} = sigmoid(T[k - 1, k']), k' the 1-based rank of the last
  click above rank k, 0 where there is none;
- training loss (eq. 25): the masked mean over items of the Bernoulli NLL
  of the click given the clicks above it, P(C_k = 1 | c_<k) =
  gamma_k theta_{k,k'}, with k' found by a plain loop over the ranks;
- served click probability (eq. 26): the marginal P(C_k = 1), the sum over
  every last click k' < k of P(click at k', none from k' + 1 to k - 1)
  theta_{k,k'} gamma_k, each path's probability carried rank by rank.

Every function computes in the dtype of the parameters it is given, so the
same code is the control when handed bfloat16 parameters.
"""
from __future__ import annotations

import jax.numpy as jnp

from harness import plain

LEAVES = ("attraction/table", "attraction/baseline", "examination/table")

# Every theta_{k,k'} starts at this logit (the configuration's "assumed").
EXAMINATION_INIT_LOGIT = 2.0


def rows(cfg) -> int:
    t = cfg["tables"]["attraction"]
    return plain.hashed_rows(t["parameters"], t["compression_ratio"],
                             cfg["table_row_multiple"])


def init(cfg, dtype=jnp.float32):
    """Zero rows under a baseline (baseline correction), and the (K, K)
    examination logits at their start."""
    t = cfg["tables"]["attraction"]
    k = int(cfg["positions"])
    return {"attraction/table": jnp.zeros((rows(cfg), 1), dtype),
            "attraction/baseline": jnp.full((1,), t["init_logit"], dtype),
            "examination/table": jnp.full((k, k), EXAMINATION_INIT_LOGIT,
                                          dtype)}


def _sigmoid(x):
    one = jnp.asarray(1.0, x.dtype)
    return one / (one + jnp.exp(-x))


def _gamma(params, batch, cfg):
    h = plain.hash_rows(jnp.asarray(batch["query_doc_ids"]), rows(cfg),
                        cfg["hash"]["xor"])
    return _sigmoid(jnp.take(params["attraction/table"][:, 0], h, axis=0)
                    + params["attraction/baseline"][0])


def loss(params, batch, cfg):
    gamma = _gamma(params, batch, cfg)
    dtype = gamma.dtype
    theta = _sigmoid(params["examination/table"])
    clicks = jnp.asarray(batch["clicks"]).astype(dtype)
    mask = jnp.asarray(batch["mask"]).astype(dtype)
    last = jnp.zeros(gamma.shape[:1], jnp.int32)   # k' of rank 1: none
    nll = []
    for k in range(gamma.shape[1]):
        p = gamma[:, k] * theta[k, last]
        c = clicks[:, k]
        nll.append(-(c * jnp.log(p) + (1 - c) * jnp.log1p(-p)))
        last = jnp.where(c > 0, k + 1, last)
    nll = jnp.stack(nll, axis=1)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1)


def click_log_probs(params, batch, cfg):
    gamma = _gamma(params, batch, cfg)
    theta = _sigmoid(params["examination/table"])
    # paths[kp]: P(last click at 1-based rank kp, 0 for none, and no click
    # from kp + 1 up to the rank before the current one)
    paths, marginal = [], []
    for k in range(gamma.shape[1]):
        paths.append(jnp.ones_like(gamma[:, 0]) if k == 0 else marginal[-1])
        clicked = [w * theta[k, kp] * gamma[:, k]
                   for kp, w in enumerate(paths)]
        marginal.append(sum(clicked))
        paths = [w - c for w, c in zip(paths, clicked)]
    return jnp.log(jnp.stack(marginal, axis=1))
