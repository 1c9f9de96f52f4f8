"""Public jit'd kernel API on top of the dispatch registry.

Every kernel registers three implementations with
:mod:`repro.kernels.dispatch`:

  ============  ==========================================================
  ``pallas``    the Pallas lowering (compiled on TPU, interpret-mode
                elsewhere — a conformance tool off-TPU, not a fast path)
  ``xla``       the best XLA-fusable jnp expression (CPU/GPU fast path)
  ``ref``       the pure-jnp oracle from :mod:`repro.kernels.ref`
  ============  ==========================================================

``impl=None`` resolves per backend at trace time (pallas on TPU, xla
elsewhere), overridable programmatically (``override_impl``) or via the
``CLAX_KERNEL_IMPL[_<NAME>]`` environment variables for drills. Passing an
explicit ``impl`` always wins.

Gradient semantics are impl-independent: ``embedding_bag``, ``session_nll``
and ``examination_nll`` carry custom VJPs, so every impl trains with the same
backward pass — a segment scatter that never materializes a (B*L, D)
intermediate, the closed-form sigmoid delta, and ``jax.vjp`` of the ref
examination composition (inheriting ``core/recursions``' saturating VJP).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import dispatch as _dispatch
from repro.kernels import ref as _ref
from repro.kernels.dcn_cross import dcn_cross_pallas
from repro.kernels.embedding_bag import embedding_bag_pallas
from repro.kernels.examination_nll import (examination_nll_partials,
                                           examination_nll_xla)
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.fm_interaction import fm_interaction_pallas
from repro.kernels.session_nll import session_nll_partials

#: ``jax.named_scope`` of the fused losses, forward and backward: the trace
#: attributes their device time by it (``op_name`` metadata).
LOSS_SCOPE = "loss"

# Re-exported so callers can flip impls without importing the registry module.
override_impl = _dispatch.override_impl
set_impl_override = _dispatch.set_impl_override
resolve_impl = _dispatch.resolve_impl


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _nll_pallas(partials, *args):
    """Masked-mean NLL from a kernel's per-block (sums, counts).

    Under a mesh (``jax.set_mesh``, as the data-parallel engine traces), a
    Mosaic kernel cannot be partitioned by XLA: it runs per data shard in a
    ``shard_map`` and only the (G, 1, 1) partials cross devices.
    """
    from jax.sharding import PartitionSpec as P

    from repro.distrib.shardings import DATA_AXES

    fn = functools.partial(partials, interpret=_interpret())
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.empty and DATA_AXES(mesh):
        data = DATA_AXES(mesh)
        fn = jax.shard_map(fn, mesh=mesh, in_specs=P(data, None),
                           out_specs=P(data), check_vma=False)
    sums, counts = fn(*args)
    return jnp.sum(sums) / jnp.maximum(jnp.sum(counts), 1.0)


# ---------------------------------------------------------------------------
# XLA implementations (fused jnp forms; oracles live in ref.py)
# ---------------------------------------------------------------------------

def _embedding_bag_xla(table, ids, weights):
    safe = jnp.maximum(ids, 0)
    w = jnp.where(ids >= 0, weights, 0.0).astype(jnp.float32)
    gathered = jnp.take(table, safe, axis=0).astype(jnp.float32)  # (B, L, D)
    return jnp.sum(gathered * w[..., None], axis=1)


def _session_nll_xla(logits, clicks, mask):
    x = logits.astype(jnp.float32)
    c = clicks.astype(jnp.float32)
    m = mask.astype(jnp.float32)
    # softplus(x) - c*x: the single-transcendental form of the BCE chain.
    nll = jnp.maximum(x, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(x))) - c * x
    return jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)


def _fm_interaction_xla(v):
    vf = v.astype(jnp.float32)
    s = jnp.sum(vf, axis=1)
    # Subtract per-d before the lane reduction (as the ref does): the two
    # totals are large and nearly equal, so subtracting them last loses
    # most of the result's relative precision to cancellation.
    return 0.5 * jnp.sum(jnp.square(s) - jnp.sum(jnp.square(vf), axis=1),
                         axis=-1)


def _flash_attention_xla(q, k, v, causal=False, scale=None):
    """Grouped softmax attention: GQA via a reshape, never a repeated K/V."""
    B, Hq, Sq, Dh = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    group = Hq // Hkv
    scale = scale if scale is not None else 1.0 / (Dh ** 0.5)
    qg = q.reshape(B, Hkv, group, Sq, Dh).astype(jnp.float32)
    logits = jnp.einsum("bhgqd,bhkd->bhgqk", qg,
                        k.astype(jnp.float32)) * scale
    if causal:
        mask = jnp.tril(jnp.ones((Sq, Skv), bool), k=Skv - Sq)
        logits = jnp.where(mask, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", probs, v.astype(jnp.float32))
    return out.reshape(B, Hq, Sq, Dh).astype(q.dtype)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

_dispatch.register("embedding_bag", "pallas",
                   lambda t, i, w: embedding_bag_pallas(
                       t, i, w, interpret=_interpret()))
_dispatch.register("embedding_bag", "ref", _ref.embedding_bag_ref)
_dispatch.register("embedding_bag", "xla", _embedding_bag_xla)

_dispatch.register("session_nll", "pallas",
                   functools.partial(_nll_pallas, session_nll_partials))
_dispatch.register("session_nll", "ref", _ref.session_nll_ref)
_dispatch.register("session_nll", "xla", _session_nll_xla)

_dispatch.register("fm_interaction", "pallas",
                   lambda v: fm_interaction_pallas(v, interpret=_interpret()))
_dispatch.register("fm_interaction", "ref", _ref.fm_interaction_ref)
_dispatch.register("fm_interaction", "xla", _fm_interaction_xla)

_dispatch.register("dcn_cross", "pallas",
                   lambda x0, x, w, b: dcn_cross_pallas(
                       x0, x, w, b, interpret=_interpret()))
_dispatch.register("dcn_cross", "ref", _ref.dcn_cross_ref)
# The ref expression (one GEMM + elementwise) is already the optimal XLA form.
_dispatch.register("dcn_cross", "xla", _ref.dcn_cross_ref)

_dispatch.register("flash_attention", "pallas",
                   lambda q, k, v, **kw: flash_attention_pallas(
                       q, k, v, interpret=_interpret(), **kw))
_dispatch.register("flash_attention", "ref", _ref.flash_attention_ref)
_dispatch.register("flash_attention", "xla", _flash_attention_xla)

_dispatch.register("examination_nll", "pallas",
                   functools.partial(_nll_pallas, examination_nll_partials))
_dispatch.register("examination_nll", "ref", _ref.examination_nll_ref)
_dispatch.register("examination_nll", "xla", examination_nll_xla)


# ---------------------------------------------------------------------------
# embedding_bag with custom VJP
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _embedding_bag(table, ids, weights, impl):
    return _dispatch.dispatch("embedding_bag", impl, table, ids, weights)


def _bag_fwd(table, ids, weights, impl):
    return _embedding_bag(table, ids, weights, impl), (table, ids, weights)


def _bag_bwd(impl, res, g):
    table, ids, weights = res
    N, D = table.shape
    g = g.astype(jnp.float32)  # (B, D)
    w = jnp.where(ids >= 0, weights, 0.0).astype(jnp.float32)
    safe = jnp.maximum(ids, 0)

    # d_table[r] = sum_{(b,l): ids=r} w[b,l] * g[b], scattered one bag slot
    # at a time: the carry is the (N, D) output itself (donated through the
    # scan) and each step touches only a (B, D) slice — peak footprint
    # O(N*D + B*D), vs the former (B*L, D) contrib + segment_sum. d_w rides
    # the same scan: d_w[b,l] = <table[ids[b,l]], g[b]> from a (B, D) gather.
    def step(d_table, xs):
        ids_l, w_l = xs  # (B,), (B,)
        rows = jnp.take(table, ids_l, axis=0).astype(jnp.float32)
        d_w_l = jnp.sum(rows * g, axis=-1)
        return d_table.at[ids_l].add(w_l[:, None] * g), d_w_l

    d_table, d_w_cols = jax.lax.scan(
        step, jnp.zeros((N, D), jnp.float32), (safe.T, w.T))
    d_table = d_table.astype(table.dtype)
    d_w = jnp.where(ids >= 0, d_w_cols.T, 0.0).astype(weights.dtype)
    return d_table, None, d_w


_embedding_bag.defvjp(_bag_fwd, _bag_bwd)


def embedding_bag(table: jax.Array, ids: jax.Array,
                  weights: Optional[jax.Array] = None, combiner: str = "sum",
                  impl: Optional[str] = None) -> jax.Array:
    """out[b] = reduce_l table[ids[b, l]]; ids < 0 = padding.

    combiner: "sum" | "mean" (mean over non-padding entries).
    """
    impl = _dispatch.resolve_impl("embedding_bag", impl)
    if weights is None:
        weights = jnp.ones(ids.shape, jnp.float32)
    if combiner == "mean":
        count = jnp.sum((ids >= 0).astype(jnp.float32), axis=1, keepdims=True)
        weights = weights / jnp.maximum(count, 1.0)
    elif combiner != "sum":
        raise ValueError(f"unknown combiner {combiner!r}")
    return _embedding_bag(table, ids, weights, impl)


# ---------------------------------------------------------------------------
# session_nll with custom VJP
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _session_nll(logits, clicks, mask, impl):
    return _dispatch.dispatch("session_nll", impl, logits, clicks, mask)


def _nll_fwd(logits, clicks, mask, impl):
    return _session_nll(logits, clicks, mask, impl), (logits, clicks, mask)


def _nll_bwd(impl, res, g):
    logits, clicks, mask = res
    # A custom_vjp backward is traced outside the forward's scope.
    with jax.named_scope(LOSS_SCOPE):
        x = logits.astype(jnp.float32)
        c = clicks.astype(jnp.float32)
        m = mask.astype(jnp.float32)
        inv_count = 1.0 / jnp.maximum(jnp.sum(m), 1.0)
        # d nll/dx = sigmoid(x) - c; d nll/dc = -x; both masked-mean weighted.
        d_logits = (g * (jax.nn.sigmoid(x) - c) * m
                    * inv_count).astype(logits.dtype)
        d_clicks = (g * (-x) * m * inv_count).astype(clicks.dtype)
    return d_logits, d_clicks, None


_session_nll.defvjp(_nll_fwd, _nll_bwd)


def session_nll(logits: jax.Array, clicks: jax.Array, mask: jax.Array,
                impl: Optional[str] = None) -> jax.Array:
    """Masked-mean Bernoulli click NLL straight from logits.

    Fuses log_sigmoid -> log1mexp -> BCE -> masked mean in one pass over the
    (B, K) tile; the scalar loss (and its closed-form VJP) never materializes
    the per-element log-probability intermediates.
    """
    impl = _dispatch.resolve_impl("session_nll", impl)
    with jax.named_scope(LOSS_SCOPE):
        return _session_nll(logits, clicks, mask, impl)


# ---------------------------------------------------------------------------
# examination_nll with custom VJP (backward = jax.vjp of the ref composition)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def _examination_nll(x, clicks, mask, pss, pd, pr, prn, impl):
    return _dispatch.dispatch("examination_nll", impl,
                              x, clicks, mask, pss, pd, pr, prn)


def _exam_fwd(x, clicks, mask, pss, pd, pr, prn, impl):
    out = _examination_nll(x, clicks, mask, pss, pd, pr, prn, impl)
    return out, (x, clicks, mask, pss, pd, pr, prn)


def _exam_bwd(impl, res, g):
    x, clicks, mask, pss, pd, pr, prn = res

    # Differentiate the ref composition regardless of the forward impl: every
    # impl then shares the exact pre-dispatch gradient, including the
    # saturating zero-cotangent semantics of core/recursions' _affine_scan.
    def composed(x_, c_, ss_, d_, r_, rn_):
        return _ref.examination_nll_ref(x_, c_, mask, ss_, d_, r_, rn_)

    with jax.named_scope(LOSS_SCOPE):
        _, vjp = jax.vjp(composed, x, clicks, pss, pd, pr, prn)
        dx, dc, dss, dd, dr, drn = vjp(g)
    return dx, dc, None, dss, dd, dr, drn


_examination_nll.defvjp(_exam_fwd, _exam_bwd)


def examination_nll(attr_logits: jax.Array, clicks: jax.Array,
                    mask: jax.Array, p_skip_survive: jax.Array,
                    p_death: jax.Array, p_reset: jax.Array,
                    p_reset_not: jax.Array,
                    impl: Optional[str] = None) -> jax.Array:
    """Fused conditional click NLL of the examination-chain models.

    Inputs are the raw attraction logits plus the four probability-space
    factors of ``core.recursions.conditional_examination_odds`` (all (B, K));
    the output is the scalar masked-mean NLL that
    ``_ChainModel.compute_loss`` minimizes. The factor -> odds-scan -> NLL
    chain runs in one pass with no (B, K) log-probability intermediates; see
    kernels/examination_nll.py for the lowering and the numerics contract.
    """
    impl = _dispatch.resolve_impl("examination_nll", impl)
    with jax.named_scope(LOSS_SCOPE):
        return _examination_nll(attr_logits, clicks, mask, p_skip_survive,
                                p_death, p_reset, p_reset_not, impl)


# ---------------------------------------------------------------------------
# fm_interaction / dcn_cross / flash_attention
# ---------------------------------------------------------------------------

def fm_interaction(v: jax.Array, impl: Optional[str] = None) -> jax.Array:
    return _dispatch.dispatch("fm_interaction", impl, v)


def dcn_cross(x0: jax.Array, x: jax.Array, w: jax.Array, b: jax.Array,
              impl: Optional[str] = None) -> jax.Array:
    return _dispatch.dispatch("dcn_cross", impl, x0, x, w, b)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = False, scale: Optional[float] = None,
                    impl: Optional[str] = None, **block_kwargs) -> jax.Array:
    impl = _dispatch.resolve_impl("flash_attention", impl)
    if impl == "pallas":
        return _dispatch.dispatch("flash_attention", impl, q, k, v,
                                  causal=causal, scale=scale, **block_kwargs)
    return _dispatch.dispatch("flash_attention", impl, q, k, v,
                              causal=causal, scale=scale)
