"""Plain reference of ``dctr-baidu``: the document click-through-rate model
(CLAX eq. 21) over one hashed table.

P(C_k = 1) = gamma_k = sigmoid(A[h(d_k)] + a0), the same for the click
given the clicks above it; the training loss is the masked mean Bernoulli
NLL. Written from the definition, with nothing of the program; every
function computes in the dtype of the parameters it is given.
"""
from __future__ import annotations

import jax.numpy as jnp

from harness import plain

LEAVES = ("attraction/table", "attraction/baseline")


def rows(cfg) -> int:
    t = cfg["tables"]["attraction"]
    return plain.hashed_rows(t["parameters"], t["compression_ratio"],
                             cfg["table_row_multiple"])


def init(cfg, dtype=jnp.float32):
    t = cfg["tables"]["attraction"]
    return {"attraction/table": jnp.zeros((rows(cfg), 1), dtype),
            "attraction/baseline": jnp.full((1,), t["init_logit"], dtype)}


def _gamma(params, batch, cfg):
    h = plain.hash_rows(jnp.asarray(batch["query_doc_ids"]), rows(cfg),
                        cfg["hash"]["xor"])
    x = (jnp.take(params["attraction/table"][:, 0], h, axis=0)
         + params["attraction/baseline"][0])
    one = jnp.asarray(1.0, x.dtype)
    return one / (one + jnp.exp(-x))


def loss(params, batch, cfg):
    p = _gamma(params, batch, cfg)
    c = jnp.asarray(batch["clicks"]).astype(p.dtype)
    m = jnp.asarray(batch["mask"]).astype(p.dtype)
    nll = -(c * jnp.log(p) + (1 - c) * jnp.log1p(-p))
    return jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1)


def click_log_probs(params, batch, cfg):
    return jnp.log(_gamma(params, batch, cfg))
