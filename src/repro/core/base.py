"""ClickModel base API (paper §4.1, Listing 2).

Every model implements five methods over a padded batch dict:

  * ``compute_loss(params, batch)``     — masked mean NLL of observed clicks
    under the session marginal likelihood (chain-rule factorized:
    sum_k log P(c_k | c_<k)). For position-independent models conditional and
    unconditional click probabilities coincide.
  * ``predict_clicks(params, batch)``   — log P(C=1 | d, k).
  * ``predict_conditional_clicks(...)`` — log P(C=1 | d, k, c_<k).
  * ``predict_relevance(params, batch)``— ranking scores (log-space).
  * ``sample(params, batch, rng)``      — click sequences + latent draws.

Batch layout (all (batch, K)):
  positions: int32 starting at 1; query_doc_ids: int32; clicks: float;
  mask: bool (True = real item); optional feature arrays (batch, K, F).
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.nn.module import Module
from repro.stable import log_bce

Batch = Dict[str, jax.Array]

REQUIRED_KEYS = ("positions", "clicks", "mask")


def validate_batch(batch: Batch) -> None:
    for key in REQUIRED_KEYS:
        if key not in batch:
            raise ValueError(f"batch missing required key {key!r}")
    shape = batch["positions"].shape
    if len(shape) != 2:
        raise ValueError(f"batch arrays must be 2D (batch, positions), got {shape}")
    for key, arr in batch.items():
        if arr.shape[:2] != shape:
            raise ValueError(f"batch[{key!r}] leading shape {arr.shape[:2]} != {shape}")


def masked_mean(values: jax.Array, mask: jax.Array) -> jax.Array:
    mask = mask.astype(values.dtype)
    return jnp.sum(values * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def last_click_positions(clicks: jax.Array, positions: jax.Array) -> jax.Array:
    """Rank (1-based) of the most recent click strictly before each position.

    Returns 0 where no click occurred before. Assumes positions are sorted
    ascending within a session (top-down browsing).
    """
    clicked_rank = jnp.where(clicks > 0, positions, 0)
    # exclusive cumulative max over the position axis
    cummax = jax.lax.associative_scan(jnp.maximum, clicked_rank, axis=1)
    exclusive = jnp.concatenate(
        [jnp.zeros_like(cummax[:, :1]), cummax[:, :-1]], axis=1)
    return exclusive


def clicks_before(clicks: jax.Array) -> jax.Array:
    """Number of clicks strictly before each position."""
    csum = jnp.cumsum(clicks, axis=1)
    return csum - clicks


class ClickModel(Module):
    """Base class: loss defaults to BCE over conditional click log-probs."""

    positions: int = 10

    # -- API -----------------------------------------------------------------
    def compute_loss(self, params, batch: Batch) -> jax.Array:
        logits = self.predict_conditional_logits(params, batch)
        if logits is not None:
            # CTR-family fast path: one fused kernel from raw logits to the
            # scalar loss, no (B, K) log-probability intermediates.
            from repro.kernels import session_nll

            return session_nll(logits, batch["clicks"], batch["mask"])
        from repro.kernels.ops import LOSS_SCOPE

        # The unfused path. Its NLL carries the `loss` scope the fused
        # kernels carry; the lookups run before it, in their own scopes.
        log_probs = self.predict_conditional_clicks(params, batch)
        with jax.named_scope(LOSS_SCOPE):
            nll = log_bce(log_probs, batch["clicks"])
            return masked_mean(nll, batch["mask"])

    def predict_conditional_logits(self, params, batch: Batch):
        """Raw logits x with log P(C=1 | d, k, c_<k) = log sigmoid(x), or None.

        Models whose conditional click probability is a single sigmoid (the
        CTR family) override this; ``compute_loss`` then routes through the
        fused ``session_nll`` kernel instead of log-space BCE.
        """
        del params, batch
        return None

    def predict_clicks(self, params, batch: Batch) -> jax.Array:
        raise NotImplementedError

    def predict_conditional_clicks(self, params, batch: Batch) -> jax.Array:
        # default: position-independent model
        return self.predict_clicks(params, batch)

    def predict_relevance(self, params, batch: Batch) -> jax.Array:
        raise NotImplementedError

    def sample(self, params, batch: Batch, rng: jax.Array) -> Dict[str, jax.Array]:
        raise NotImplementedError

    # -- conveniences ----------------------------------------------------------
    def init(self, rng: jax.Array):
        raise NotImplementedError

    def loss_and_grad(self, params, batch: Batch):
        return jax.value_and_grad(self.compute_loss)(params, batch)
