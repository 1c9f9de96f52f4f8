"""The user browsing model's training path against a plain float32 UBM
written here from eq. 25 (CLAX Appendix A.6): the loss, the gradient of
every leaf and one AdamW step, at a small size with seeded random weights,
sessions of 0 to K clicks and masked tails; also on the flat tables that
training keeps, and with the loss and examination scopes in the trace."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim
from repro.core import MODEL_REGISTRY, EmbeddingParameterConfig
from repro.core.parameterization import relayout_scalar_tables

K, ROWS, B = 10, 97, 48


def make_model():
    attraction = EmbeddingParameterConfig(parameters=ROWS,
                                          baseline_correction=True,
                                          init_logit=-1.5)
    return MODEL_REGISTRY["ubm"](positions=K, attraction=attraction)


def random_params(model, seed):
    rng = np.random.default_rng(seed)
    params = model.init(jax.random.PRNGKey(seed))
    params["attraction"]["table"] = jnp.asarray(
        rng.normal(0.0, 1.5, (ROWS, 1)), jnp.float32)
    params["attraction"]["baseline"] = jnp.asarray([-0.7], jnp.float32)
    params["examination"]["table"] = jnp.asarray(
        rng.normal(1.0, 1.5, (K, K)), jnp.float32)
    return params


def random_batch(seed):
    """Sessions of every click count from 0 to K, and tails masked off."""
    rng = np.random.default_rng(seed + 1)
    lengths = rng.integers(1, K + 1, size=B)
    lengths[:2] = K
    mask = np.arange(K)[None, :] < lengths[:, None]
    clicks = np.zeros((B, K), np.float32)
    for b in range(B):
        n = b % (K + 1)
        clicks[b, rng.permutation(K)[:n]] = 1.0
    clicks *= mask
    clicks[0] = 0.0               # no click at all
    clicks[1] = 1.0               # a click at every rank
    return {"positions": np.tile(np.arange(1, K + 1, dtype=np.int32), (B, 1)),
            "query_doc_ids": rng.integers(0, ROWS, (B, K)).astype(np.int32),
            "clicks": clicks, "mask": mask}


def plain_loss(leaves, batch):
    """Eq. 25: P(C_k = 1 | c_<k) = gamma_d theta_{k,k'}, k' the rank of the
    last click above k (0: none); the masked mean Bernoulli NLL."""
    table, baseline, exam = leaves
    gamma = jax.nn.sigmoid(table[batch["query_doc_ids"], 0] + baseline[0])
    theta = jax.nn.sigmoid(exam)
    clicks = jnp.asarray(batch["clicks"])
    last = jnp.zeros((B,), jnp.int32)
    nll = []
    for k in range(K):
        p = gamma[:, k] * theta[k, last]
        c = clicks[:, k]
        nll.append(-(c * jnp.log(p) + (1 - c) * jnp.log1p(-p)))
        last = jnp.where(c > 0, k + 1, last)
    mask = jnp.asarray(batch["mask"], jnp.float32)
    return jnp.sum(jnp.stack(nll, 1) * mask) / jnp.sum(mask)


def _leaves(params):
    return (params["attraction"]["table"], params["attraction"]["baseline"],
            params["examination"]["table"])


def _close(got, want, rtol=2e-5, atol=1e-7):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=rtol, atol=atol)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_loss_and_every_gradient_match_the_plain_ubm(seed):
    model = make_model()
    params = random_params(model, seed)
    batch = random_batch(seed)
    loss, grads = jax.jit(jax.value_and_grad(model.compute_loss))(params,
                                                                  batch)
    want, want_grads = jax.value_and_grad(plain_loss)(_leaves(params), batch)
    _close(loss, want)
    for got, ref in zip(_leaves(grads), want_grads):
        assert got.shape == ref.shape
        _close(got, ref, atol=1e-8)
    # every (rank, last-click) cell the batch reaches takes a gradient
    assert int(np.sum(np.asarray(grads["examination"]["table"]) != 0)) > K


@pytest.mark.parametrize("flat", [False, True])
def test_one_adamw_step_matches_plain_adamw(flat):
    model = make_model()
    params = random_params(model, 3)
    batch = random_batch(3)
    lr, b1, b2, eps, wd = 3e-3, 0.9, 0.999, 1e-8, 1e-4
    tx = optim.adamw(lr, b1=b1, b2=b2, eps=eps, weight_decay=wd)
    start = relayout_scalar_tables(model, params, flat=flat)

    @jax.jit
    def step(p):
        grads = jax.grad(model.compute_loss)(p, batch)
        updates, _ = tx.update(grads, tx.init(p), p)
        return optim.apply_updates(p, updates)

    got = relayout_scalar_tables(model, step(start), flat=False)
    leaves = _leaves(params)
    grads = jax.grad(plain_loss)(leaves, batch)
    for name, p, g, q in zip(("table", "baseline", "examination"), leaves,
                             grads, _leaves(got)):
        m, v = (1 - b1) * g, (1 - b2) * g * g
        want = p - lr * ((m / (1 - b1)) / (jnp.sqrt(v / (1 - b2)) + eps)
                         + wd * p)
        assert q.shape == p.shape, name
        _close(q, want, rtol=1e-6, atol=1e-7)


def test_examination_and_its_backward_lie_in_the_loss_scope():
    """The examination lookup and its backward carry ``examination`` inside
    ``loss``; the attraction table keeps ``embed``, outside ``loss``."""
    model = make_model()
    params = random_params(model, 4)
    text = jax.jit(jax.value_and_grad(model.compute_loss)).lower(
        params, random_batch(4)).as_text(debug_info=True)
    names = set(re.findall(r'loc\("([^"]+)"', text))

    def in_loss(n):
        return re.search(r"[/(]loss[/)]", n) is not None

    exam = [n for n in names if "/examination/" in n]
    assert exam and all(in_loss(n.split("/examination/")[0] + "/")
                        for n in exam)
    assert any("transpose(jvp(loss))/examination/" in n
               and n.endswith("dot_general") for n in exam), exam
    embed = [n for n in names if "embed" in n]
    assert embed and not any(in_loss(n) for n in embed), embed
    assert any(re.search(r"/jvp\(loss\)/log1p$", n) for n in names)


def test_examination_contraction_equals_the_gather():
    """``UBMExaminationParameter.logit`` contracts one-hots with the table:
    the same values as indexing it, and the same gradient as the gather's
    scatter-add."""
    model = make_model()
    exam = model.parts["examination"]
    table = random_params(model, 5)["examination"]["table"]
    batch = random_batch(5)
    k = jnp.asarray(batch["positions"])
    kp = jnp.asarray(np.random.default_rng(5).integers(0, K, (B, K)))
    kp = jnp.minimum(kp, k - 1)
    weights = jnp.asarray(np.random.default_rng(6).normal(size=(B, K)),
                          jnp.float32)

    def contracted(t):
        return exam.logit({"table": t}, k, kp)

    def gathered(t):
        return t[k - 1, kp]

    np.testing.assert_array_equal(contracted(table), gathered(table))
    got = jax.grad(lambda t: jnp.sum(contracted(t) * weights))(table)
    want = jax.grad(lambda t: jnp.sum(gathered(t) * weights))(table)
    _close(got, want, rtol=1e-6, atol=1e-6)
