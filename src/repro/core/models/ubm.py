"""User browsing model (paper A.6): examination depends on (rank, last click).

Conditional prediction is a table lookup (Eq. 25); unconditional prediction
marginalizes over all possible last-click positions (Eq. 26) with an O(K^2)
log-space recursion.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.core.base import last_click_positions
from repro.core.models.ctr import _PartsModel
from repro.core.parameterization import (
    EmbeddingParameterConfig,
    UBMExaminationParameter,
    build_parameter,
)
from repro.core.recursions import ubm_marginal_clicks
from repro.stable import log1mexp, log_sigmoid, logsumexp


class UserBrowsingModel(_PartsModel):
    def __init__(self, query_doc_pairs: int = None, positions: int = 10,
                 attraction=None, examination=None, init_prob: float = 0.5, **_):
        self.positions = positions
        logit = math.log(init_prob) - math.log1p(-init_prob)
        if attraction is None:
            attraction = EmbeddingParameterConfig(parameters=query_doc_pairs,
                                                  init_logit=logit)
        if examination is None:
            examination = UBMExaminationParameter(positions, init_logit=2.0)
        self.parts = {
            "attraction": build_parameter(attraction),
            "examination": examination,
        }

    # -- helpers ---------------------------------------------------------------
    def _log_attr(self, params, batch):
        return log_sigmoid(self.parts["attraction"](params["attraction"], batch))

    def _log_exam_table(self, params, batch):
        """lt[b, k_idx, kp] = log theta at 0-based rank k_idx given last click
        at 1-based rank kp (kp = 0 encodes no previous click)."""
        table = params["examination"]["table"]  # (K, K) logits
        lt = log_sigmoid(table)
        b = batch["positions"].shape[0]
        return jnp.broadcast_to(lt, (b,) + lt.shape)

    # -- API -------------------------------------------------------------------
    def predict_conditional_clicks(self, params, batch):
        """Eq. 25: log theta_{k,k'} + log gamma_d with observed last click k'."""
        la = self._log_attr(params, batch)
        return self._log_exam_given_clicks(params, batch) + la

    def _log_exam_given_clicks(self, params, batch):
        """log theta_{k,k'}: the last-click scan and the (K, K) lookup, in
        scope ``examination`` inside ``loss``, apart from the attraction
        table's ``embed``. The scopes name the operations in a trace and
        nothing else; other callers of ``predict_conditional_clicks``
        (sampling, conditional serving) carry the same names."""
        from repro.kernels.ops import LOSS_SCOPE

        # Nested in `loss` because the benchmark's trace reader
        # (bench/harness/scopes.py) counts an operation under its outermost
        # scope: the examination and its backward then add to the loss.
        with jax.named_scope(LOSS_SCOPE), jax.named_scope("examination"):
            k_prime = last_click_positions(batch["clicks"], batch["positions"])
            return log_sigmoid(self.parts["examination"].logit(
                params["examination"], batch["positions"], k_prime))

    def predict_clicks(self, params, batch):
        """Eq. 26: marginalize over last-click paths — masked (B, K, K)
        cumulative sums + one batched triangular solve (repro.core.recursions),
        O(1) graph ops instead of the former O(K^2) unrolled double loop."""
        attr_logits = self.parts["attraction"](params["attraction"], batch)
        return ubm_marginal_clicks(attr_logits, params["examination"]["table"])

    def predict_clicks_loop(self, params, batch):
        """Former unrolled O(K^2) log-space recursion; the test oracle for
        ``predict_clicks`` (tests/test_recursions.py)."""
        la = self._log_attr(params, batch)  # (B, K)
        lt = self._log_exam_table(params, batch)  # (B, K, K) [rank, last_click]
        K = la.shape[1]
        # log(1 - theta_{j,i} gamma_j) for every (rank j, last-click i) pair
        lg_no_click = log1mexp(lt + la[:, :, None])  # (B, K, K)
        # cumulative over rank j (inclusive): cs[b, j, i] = sum_{m<=j} lg[b, m, i]
        cs = jnp.cumsum(lg_no_click, axis=1)

        lu = []  # lu[r] = log P(C_r = 1), unconditional
        for r in range(K):
            terms = []
            # path i = 0: no click before r -> skip-run from rank 0..r-1 at kp=0
            run0 = cs[:, r - 1, 0] if r > 0 else jnp.zeros_like(la[:, 0])
            terms.append(run0 + lt[:, r, 0] + la[:, r])
            # paths: last click at 0-based rank q (kp = q + 1)
            for q in range(r):
                kp = q + 1
                run = cs[:, r - 1, kp] - cs[:, q, kp]  # ranks q+1 .. r-1
                terms.append(lu[q] + run + lt[:, r, kp] + la[:, r])
            lu.append(logsumexp(jnp.stack(terms, axis=-1), axis=-1))
        return jnp.stack(lu, axis=1)

    def predict_relevance(self, params, batch):
        return self.parts["attraction"](params["attraction"], batch)

    def sample(self, params, batch, rng):
        la = self._log_attr(params, batch)
        table_logp = log_sigmoid(params["examination"]["table"])  # (K, K)
        ka, ke = jax.random.split(rng)
        attracted = (jax.random.uniform(ka, la.shape) < jnp.exp(la)).astype(jnp.float32)
        exam_u = jax.random.uniform(ke, la.shape)

        def step(last_click, xs):
            r, a_k, u_k = xs
            lt_k = table_logp[r][last_click.astype(jnp.int32)]  # (B,)
            examined = (u_k < jnp.exp(lt_k)).astype(jnp.float32)
            click = examined * a_k
            new_last = jnp.where(click > 0, jnp.float32(r + 1), last_click)
            return new_last, (click, examined)

        B, K = la.shape
        xs = (jnp.arange(K), jnp.moveaxis(attracted, 1, 0), jnp.moveaxis(exam_u, 1, 0))
        _, (clicks, examined) = jax.lax.scan(step, jnp.zeros(B), xs)
        clicks = jnp.moveaxis(clicks, 0, 1) * batch["mask"].astype(jnp.float32)
        return {"clicks": clicks, "attraction": attracted,
                "examination": jnp.moveaxis(examined, 0, 1)}
