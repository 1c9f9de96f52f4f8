"""Plain reference of ``dbn-baidu``: the dynamic Bayesian network click
model (Chapelle and Zhang 2009; CLAX eq. 31-32) over two hashed tables.

Written from the model's definition in ``jax.numpy``, with no kernel, no
odds-space scan and nothing of the program:

- attraction gamma_k = sigmoid(A[h(d_k)] + a0), satisfaction
  sigma_k = sigmoid(S[h(d_k)] + s0), continuation lambda = sigmoid(l);
- examination eps_1 = 1; after a click eps_{k+1} = lambda (1 - sigma_k);
  after a skip eps_{k+1} = lambda eps_k (1 - gamma_k) / (1 - eps_k gamma_k);
- training loss: the masked mean over items of the Bernoulli NLL of the
  click given the clicks above it, P(C_k = 1 | c_<k) = eps_k gamma_k;
- served click probability: the marginal P(C_k = 1) = e_k gamma_k with
  e_1 = 1, e_{k+1} = e_k lambda (1 - gamma_k sigma_k).

Every function computes in the dtype of the parameters it is given, so the
same code is the control when handed bfloat16 parameters.
"""
from __future__ import annotations

import jax.numpy as jnp

from harness import plain

LEAVES = ("attraction/table", "attraction/baseline", "satisfaction/table",
          "satisfaction/baseline", "continuation/value")


def rows(cfg) -> int:
    t = cfg["tables"]["attraction"]
    return plain.hashed_rows(t["parameters"], t["compression_ratio"],
                             cfg["table_row_multiple"])


def init(cfg, dtype=jnp.float32):
    """The start of training: zero rows under a baseline (baseline
    correction), and the configured continuation."""
    n = rows(cfg)
    out = {}
    for name in ("attraction", "satisfaction"):
        t = cfg["tables"][name]
        out[f"{name}/table"] = jnp.zeros((n, 1), dtype)
        out[f"{name}/baseline"] = jnp.full((1,), t["init_logit"], dtype)
    out["continuation/value"] = jnp.asarray(
        plain.logit(cfg["model_kwargs"]["lambda_init"]), dtype)
    return out


def _probs(params, batch, cfg):
    dtype = params["attraction/table"].dtype
    h = plain.hash_rows(jnp.asarray(batch["query_doc_ids"]), rows(cfg),
                        cfg["hash"]["xor"])

    def table(name):
        return (jnp.take(params[f"{name}/table"][:, 0], h, axis=0)
                + params[f"{name}/baseline"][0])

    one = jnp.asarray(1.0, dtype)
    gamma = one / (one + jnp.exp(-table("attraction")))
    sigma = one / (one + jnp.exp(-table("satisfaction")))
    lam = one / (one + jnp.exp(-params["continuation/value"]))
    return gamma, sigma, lam


def loss(params, batch, cfg):
    gamma, sigma, lam = _probs(params, batch, cfg)
    dtype = gamma.dtype
    clicks = jnp.asarray(batch["clicks"]).astype(dtype)
    mask = jnp.asarray(batch["mask"]).astype(dtype)
    eps = jnp.ones(gamma.shape[:1], dtype)
    nll = []
    for k in range(gamma.shape[1]):
        p = eps * gamma[:, k]
        c = clicks[:, k]
        nll.append(-(c * jnp.log(p) + (1 - c) * jnp.log1p(-p)))
        eps = jnp.where(c > 0, lam * (1 - sigma[:, k]),
                        lam * eps * (1 - gamma[:, k]) / (1 - p))
    nll = jnp.stack(nll, axis=1)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1)


def click_log_probs(params, batch, cfg):
    gamma, sigma, lam = _probs(params, batch, cfg)
    e = jnp.ones(gamma.shape[:1], gamma.dtype)
    out = []
    for k in range(gamma.shape[1]):
        out.append(e * gamma[:, k])
        e = e * lam * (1 - gamma[:, k] * sigma[:, k])
    return jnp.log(jnp.stack(out, axis=1))
