"""Compile the main-path Pallas kernels, and the Baidu-width click-model
steps that call them, for a TPU v5e at their real widths.

Interpret mode (every other kernel test) cannot see what the chip's compiler
refuses: blocks that break the (8, 128) tiling rule, or more SMEM/VMEM than a
kernel may use. Here each kernel is lowered with ``interpret=False`` against a
described ``v5e:2x2`` topology and compiled by the TPU compiler, which runs
without an attached chip. Nothing executes.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro import kernels
from repro.kernels.embedding_bag import embedding_bag_pallas
from repro.kernels.examination_nll import examination_nll_partials
from repro.kernels.session_nll import session_nll_partials

# configs/clax_baidu.py: 65,536 sessions of 10 positions per step.
BAIDU_B, BAIDU_K = 65_536, 10
# A recsys EmbeddingBag field: 4096 bags of 10 ids over 2^20 rows of 16.
BAG_B, BAG_L, BAG_N, BAG_D = 4096, 10, 1 << 20, 16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A TPU executable written to a persistent cache cannot be read back
    # without a chip; keep these compiles out of any cache that is on.
    saved = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", saved)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    return compiled


def test_examination_nll_compiles_at_baidu_width(one_chip):
    x = jax.ShapeDtypeStruct((BAIDU_B, BAIDU_K), jnp.float32,
                             sharding=one_chip)
    _compile(lambda *a: examination_nll_partials(*a, interpret=False),
             *[x] * 7)


def test_session_nll_compiles_at_baidu_width(one_chip):
    x = jax.ShapeDtypeStruct((BAIDU_B, BAIDU_K), jnp.float32,
                             sharding=one_chip)
    _compile(lambda *a: session_nll_partials(*a, interpret=False), *[x] * 3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_embedding_bag_compiles_at_recsys_width(one_chip, dtype):
    table = jax.ShapeDtypeStruct((BAG_N, BAG_D), dtype, sharding=one_chip)
    ids = jax.ShapeDtypeStruct((BAG_B, BAG_L), jnp.int32, sharding=one_chip)
    weights = jax.ShapeDtypeStruct((BAG_B, BAG_L), jnp.float32,
                                   sharding=one_chip)
    _compile(lambda t, i, w: embedding_bag_pallas(t, i, w, interpret=False),
             table, ids, weights)


@pytest.mark.parametrize("name,n_args", [("examination_nll", 7),
                                         ("session_nll", 3)])
def test_nll_kernels_compile_data_parallel_on_four_chips(topo, monkeypatch,
                                                          name, n_args):
    """Under the data-parallel engine's mesh a Mosaic kernel must run per
    data shard: XLA refuses to partition it."""
    from repro.kernels import ops

    monkeypatch.setattr(ops, "_interpret", lambda: False)  # this host is CPU
    mesh = Mesh(np.array(topo.devices).reshape(4, 1), ("data", "model"))
    x = jax.ShapeDtypeStruct((BAIDU_B, BAIDU_K), jnp.float32,
                             sharding=NamedSharding(mesh, P("data", None)))
    loss = getattr(kernels, name)
    with jax.set_mesh(mesh):
        compiled = _compile(lambda *a: loss(*a, impl="pallas"),
                            *[x] * n_args)
    assert "all-gather" not in compiled.as_text()


# configs/clax_baidu.py: 2^31 ids hashed 10x into tables of this many rows.
BAIDU_ROWS = 214_748_672


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _closing(s):
    """Index of the parenthesis that closes the one ``s`` starts with."""
    depth = 0
    for i, ch in enumerate(s):
        depth += {"(": 1, ")": -1}.get(ch, 0)
        if depth == 0:
            return i
    return len(s)


def _entry(text):
    """``{name: (shape, op, operand names, text after the shape)}`` of the
    entry computation of an HLO module's text."""
    body = text[text.index("\nENTRY "):]
    body = body[body.index("\n", 1) + 1:body.index("\n}")]
    out = {}
    for line in body.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (.*)$", line)
        if not m:
            continue
        name, rest = m.groups()
        if rest.startswith("("):  # a tuple shape
            i = _closing(rest)
            shape, rest = rest[:i + 1], rest[i + 2:]
        else:
            shape, rest = rest.split(" ", 1)
        op = rest[:rest.index("(")]
        args = rest[len(op):]
        operands = re.findall(r"%([\w.\-]+)", args[:_closing(args)])
        out[name] = (shape, op, operands, rest)
    return out


def _full_table_ops_after(entry, start):
    """Every full-table operation downstream of ``start`` (tuple reads and
    the output tuple aside)."""
    users = {}
    for name, (_, _, operands, _) in entry.items():
        for o in operands:
            users.setdefault(o, []).append(name)
    found, seen, todo = [], set(), list(users.get(start, []))
    while todo:
        name = todo.pop()
        if name in seen:
            continue
        seen.add(name)
        shape, op, _, _ = entry[name]
        if op not in ("get-tuple-element", "tuple") \
                and str(BAIDU_ROWS) in shape:
            found.append((name, op))
        todo.extend(users.get(name, []))
    return found


@pytest.mark.parametrize("kind,tables", [("dbn", 2), ("dctr", 1)])
def test_baidu_train_step_keeps_scalar_tables_in_one_layout(
        one_chip, monkeypatch, kind, tables):
    """The whole Baidu-width step (model, dense AdamW, donated state) on the
    flat tables ``TrainEngine.place`` gives it reads them where they lie: no
    (rows, 1) buffer, no full-table relayout before the gather, and the
    gradient's scatter-add feeds one AdamW fusion, with no full-table pass
    between them."""
    from repro import optim
    from repro.configs import clax_baidu
    from repro.core.parameterization import relayout_scalar_tables
    from repro.kernels import dispatch, ops

    monkeypatch.setattr(ops, "_interpret", lambda: False)  # this host is CPU
    model = clax_baidu.make_model(kind)
    tx = optim.adamw(3e-3, weight_decay=1e-4)
    like = jax.eval_shape(lambda: relayout_scalar_tables(
        model, model.init(jax.random.PRNGKey(0)), flat=True))
    batch = _on(one_chip, {
        "positions": jax.ShapeDtypeStruct((BAIDU_B, BAIDU_K), jnp.int32),
        "query_doc_ids": jax.ShapeDtypeStruct((BAIDU_B, BAIDU_K), jnp.int32),
        "clicks": jax.ShapeDtypeStruct((BAIDU_B, BAIDU_K), jnp.float32),
        "mask": jax.ShapeDtypeStruct((BAIDU_B, BAIDU_K), jnp.bool_)})

    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(model.compute_loss)(params, batch)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optim.apply_updates(params, updates), opt_state, loss

    with dispatch.override_impl("pallas"):
        text = jax.jit(step, donate_argnums=(0, 1)).lower(
            _on(one_chip, like), _on(one_chip, jax.eval_shape(tx.init, like)),
            batch).compile().as_text()

    assert f"f32[{BAIDU_ROWS},1]" not in text
    assert not re.search(rf"= f32\[{BAIDU_ROWS}\]\S* reduce\(", text)
    entry = _entry(text)
    scatters = [name for name, (shape, op, _, rest) in entry.items()
                if shape.startswith(f"f32[{BAIDU_ROWS}]")
                and (op == "scatter" or "scatter-add" in rest)]
    assert len(scatters) == tables, scatters
    for name in scatters:
        after = _full_table_ops_after(entry, name)
        assert len(after) == 1 and after[0][1] == "fusion", (name, after)


def test_baidu_serving_program_gathers_from_the_table_in_place(one_chip):
    """The serving ``primary`` program (``predict_clicks``) over the
    Baidu-width DBN, on the flat tables ``ModelRegistry`` keeps, copies no
    table into another layout."""
    from repro.configs import clax_baidu
    from repro.core.parameterization import relayout_scalar_tables

    model = clax_baidu.make_model("dbn")
    params = _on(one_chip, jax.eval_shape(lambda: relayout_scalar_tables(
        model, model.init(jax.random.PRNGKey(0)), flat=True)))
    ids = jax.ShapeDtypeStruct((512, BAIDU_K), jnp.int32)
    batch = _on(one_chip, {"positions": ids, "query_doc_ids": ids,
                           "mask": jax.ShapeDtypeStruct((512, BAIDU_K),
                                                        jnp.bool_)})
    text = jax.jit(model.predict_clicks).lower(params, batch).compile().as_text()
    assert f"f32[{BAIDU_ROWS},1]" not in text
    full = [(name, op) for name, (shape, op, _, _) in _entry(text).items()
            if str(BAIDU_ROWS) in shape and op != "parameter"]
    assert not full, full


def test_baidu_dbn_chunk_compiles_data_parallel_on_flat_tables(topo,
                                                               monkeypatch):
    """The four-chip data-parallel DBN chunk, as ``TrainEngine`` compiles
    it over ``clax_param_rule`` shardings of the flat state ``place`` gives
    it: no (rows, 1) buffer, no full-table relayout, and only the
    gradient's all-reduce between chips."""
    from repro import optim
    from repro.configs import clax_baidu
    from repro.core.parameterization import relayout_scalar_tables
    from repro.distrib.shardings import (chunked_batch_spec, clax_param_rule,
                                         make_shardings)
    from repro.kernels import dispatch, ops
    from repro.train import TrainEngine

    monkeypatch.setattr(ops, "_interpret", lambda: False)  # this host is CPU
    mesh = Mesh(np.array(topo.devices).reshape(4, 1), ("data", "model"))
    model = clax_baidu.make_model("dbn")
    tx = optim.adamw(3e-3, weight_decay=1e-4)
    engine = TrainEngine(model, tx, chunk_batches=8, mesh=mesh)
    params = jax.eval_shape(lambda: relayout_scalar_tables(
        model, model.init(jax.random.PRNGKey(0)), flat=True))
    rule = clax_param_rule(mesh)

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            tree, make_shardings(mesh, tree, rule))

    rows = NamedSharding(mesh, chunked_batch_spec(mesh))
    chunk = {k: jax.ShapeDtypeStruct((8, BAIDU_B, BAIDU_K), dtype,
                                     sharding=rows)
             for k, dtype in (("positions", jnp.int32),
                              ("query_doc_ids", jnp.int32),
                              ("clicks", jnp.float32), ("mask", jnp.bool_))}
    with dispatch.override_impl("pallas"):
        text = engine.compiled(placed(params),
                               placed(jax.eval_shape(tx.init, params)),
                               chunk).as_text()
    assert "tpu_custom_call" in text
    assert f"f32[{BAIDU_ROWS},1]" not in text
    assert not re.search(rf"= f32\[{BAIDU_ROWS}\]\S* reduce\(", text)
    collectives = set(re.findall(r"\b(all-reduce|all-gather|reduce-scatter|"
                                 r"all-to-all|collective-permute)\b", text))
    assert collectives == {"all-reduce"}, collectives
