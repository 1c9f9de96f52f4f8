"""Compile the main-path Pallas kernels for a TPU v5e at their real widths.

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
