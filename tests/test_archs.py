"""Per-architecture smoke tests: reduced same-family configs, one
forward/train step on CPU, asserting output shapes + no NaNs (brief f)."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim
from repro.configs import registry
from repro.configs.lm_common import lm_smoke_batch
from repro.models.gnn import (NeighborSampler, SAGEConfig, init_params as sage_init,
                              make_full_graph_train_step, make_sampled_train_step,
                              random_graph)
from repro.models.gnn.graphsage import full_graph_forward, sampled_forward
from repro.models.lm import (forward, init_cache, init_params, lm_loss,
                             make_decode_step, make_train_step)
from repro.models.recsys import AutoInt, BST, DeepFM, MIND

LM_ARCHS = list(registry.LM_ARCHS)


def _finite(tree):
    return all(bool(jnp.all(jnp.isfinite(x.astype(jnp.float32))))
               for x in jax.tree_util.tree_leaves(tree))


@pytest.mark.parametrize("arch", LM_ARCHS)
def test_lm_arch_smoke(arch):
    cfg = registry.get_arch(arch).reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = lm_smoke_batch(cfg, batch=2, seq=16)
    logits = forward(cfg, params, batch["tokens"])
    assert logits.shape == (2, 16, cfg.padded_vocab)
    assert _finite(logits)
    # one train step reduces nothing but must run and stay finite
    optzr = optim.adamw(1e-3)
    step = jax.jit(make_train_step(cfg, optzr))
    p2, o2, loss = step(params, optzr.init(params), batch)
    assert np.isfinite(float(loss))
    assert _finite(p2)
    # decode one token
    cache = init_cache(cfg, batch=2, max_seq=16)
    dec = make_decode_step(cfg)
    lg, cache = dec(p2, cache, batch["tokens"][:, :1], jnp.int32(0))
    assert lg.shape == (2, 1, cfg.padded_vocab)
    # padded columns are -inf, real columns finite
    assert _finite(lg[..., :cfg.vocab])


@pytest.mark.parametrize("arch", LM_ARCHS)
def test_lm_full_config_dims_match_assignment(arch):
    """The FULL configs must carry the exact published dimensions."""
    cfg = registry.get_arch(arch).FULL
    expected = {
        "llama3-405b": (126, 16384, 128, 8, 53248, 128256),
        "phi3-mini-3.8b": (32, 3072, 32, 32, 8192, 32064),
        "llama3.2-1b": (16, 2048, 32, 8, 8192, 128256),
        "granite-moe-1b-a400m": (24, 1024, 16, 8, 512, 49155),
        "llama4-maverick-400b-a17b": (48, 5120, 40, 8, 8192, 202048),
    }[arch]
    got = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_ff,
           cfg.vocab)
    assert got == expected
    if arch == "granite-moe-1b-a400m":
        assert (cfg.n_experts, cfg.top_k) == (32, 8)
    if arch == "llama4-maverick-400b-a17b":
        assert (cfg.n_experts, cfg.top_k, cfg.moe_layer_step) == (128, 1, 2)
        # total/active ballpark: 400B total, 17B active
        assert 3.5e11 < cfg.param_count() < 4.6e11
        assert 1.2e10 < cfg.active_param_count() < 2.2e10
    if arch == "llama3-405b":
        assert 3.9e11 < cfg.param_count() < 4.2e11


def test_graphsage_smoke():
    cfg = registry.get_arch("graphsage-reddit").reduced()
    g = random_graph(150, 600, cfg.d_in, cfg.n_classes, seed=3)
    graph = {k: jnp.asarray(v) for k, v in g.items()}
    params = sage_init(cfg, jax.random.PRNGKey(0))
    logits = full_graph_forward(cfg, params, graph)
    assert logits.shape == (150, cfg.n_classes) and _finite(logits)
    step = jax.jit(make_full_graph_train_step(cfg))
    opt = optim.adam(1e-2).init(params)
    p2, o2, loss = step(params, opt, graph)
    assert np.isfinite(float(loss))
    # sampled path
    sampler = NeighborSampler(g["src"], g["dst"], 150, seed=0)
    batch = sampler.sample_batch(np.arange(16), cfg.sample_sizes,
                                 g["features"], g["labels"])
    batch = {k: jnp.asarray(v) for k, v in batch.items()}
    out = sampled_forward(cfg, params, batch)
    assert out.shape == (16, cfg.n_classes) and _finite(out)
    sstep = jax.jit(make_sampled_train_step(cfg))
    p3, o3, loss2 = sstep(params, optim.adam(1e-2).init(params), batch)
    assert np.isfinite(float(loss2))


RECSYS = {
    "deepfm": (DeepFM, "field_ids"),
    "autoint": (AutoInt, "field_ids"),
    "bst": (BST, "sequence"),
    "mind": (MIND, "sequence"),
}


@pytest.mark.parametrize("arch", sorted(RECSYS))
def test_recsys_smoke(arch):
    rng = np.random.default_rng(0)
    model_cls, style = RECSYS[arch]
    cfg = registry.get_arch(arch).reduced()
    model = model_cls(cfg)
    B = 32
    if style == "field_ids":
        batch = {"field_ids": jnp.asarray(rng.integers(0, 500, (B, cfg.n_sparse))),
                 "labels": jnp.asarray(rng.integers(0, 2, B).astype(np.float32))}
    else:
        hist_len = cfg.seq_len if arch == "bst" else cfg.history_len
        batch = {"history_ids": jnp.asarray(rng.integers(0, 400, (B, hist_len))),
                 "target_ids": jnp.asarray(rng.integers(0, 400, B)),
                 "labels": jnp.asarray(rng.integers(0, 2, B).astype(np.float32))}
    params = model.init(jax.random.PRNGKey(0))
    logits = model.forward(params, batch)
    assert logits.shape == (B,) and _finite(logits)
    step = jax.jit(model.make_train_step())
    opt = optim.adamw(1e-3).init(params)
    p2, o2, loss = step(params, opt, batch)
    assert np.isfinite(float(loss)) and _finite(p2)
    # training actually reduces loss on a learnable target
    for _ in range(30):
        p2, o2, loss2 = step(p2, o2, batch)
    assert float(loss2) < float(loss)


def test_recsys_compression_variants():
    """Paper tech on recsys tables: hash + QR compressions stay finite."""
    from repro.models.recsys import DeepFMConfig
    rng = np.random.default_rng(1)
    batch = {"field_ids": jnp.asarray(rng.integers(0, 100_000, (16, 8))),
             "labels": jnp.asarray(rng.integers(0, 2, 16).astype(np.float32))}
    for compression in ("hash", "qr"):
        cfg = DeepFMConfig(name="c", n_sparse=8, embed_dim=4, mlp=(8,),
                           table_rows=100_000, compression=compression,
                           compression_ratio=50.0)
        model = DeepFM(cfg)
        params = model.init(jax.random.PRNGKey(0))
        n_rows = sum(x.shape[0] for x in
                     jax.tree_util.tree_leaves(params["embedding"]))
        assert n_rows < 100_000 / 10  # actually compressed
        assert np.isfinite(float(model.loss(params, batch)))


MOE_ORACLE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.mesh import make_auto_mesh
from repro.models.lm import LMConfig, init_params, forward

# capacity_factor >= n_experts => lossless routing => shard_map == dense oracle
cfg = LMConfig(name="m", n_layers=2, d_model=32, n_heads=2, n_kv_heads=2,
               d_ff=64, vocab=64, head_dim=16, moe=True, n_experts=8, top_k=2,
               d_ff_moe=32, moe_layer_step=1, attn_chunk=8,
               capacity_factor=64.0)
params = init_params(cfg, jax.random.PRNGKey(0))
toks = jax.random.randint(jax.random.PRNGKey(1), (4, 8), 0, 64)
dense = forward(cfg, params, toks, mesh=None)

mesh = make_auto_mesh((2, 4), ("data", "model"))
with jax.set_mesh(mesh):
    sharded = jax.jit(lambda p, t: forward(cfg, p, t, mesh=mesh))(params, toks)
err = float(jnp.max(jnp.abs(dense.astype(jnp.float32) - sharded.astype(jnp.float32))))
assert err < 2e-2, err
print("MOE_ORACLE_OK", err)
"""


def test_moe_shard_map_matches_dense_oracle():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    # Pin the subprocess to CPU: probing other platform plugins (e.g. the
    # baked-in TPU runtime on dev images) can stall minutes in metadata
    # retries. --xla_force_host_platform_device_count still applies on cpu.
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", MOE_ORACLE_SCRIPT],
                          capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "MOE_ORACLE_OK" in proc.stdout


FLASH_DECODE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, dataclasses
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.mesh import make_auto_mesh
from repro.models.lm import LMConfig, init_params, init_cache, make_decode_step, forward

cfg0 = LMConfig(name="m", n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                d_ff=128, vocab=64, head_dim=16, attn_chunk=8, max_seq=16)
cfg1 = dataclasses.replace(cfg0, flash_decode=True, decode_seq_axes=("model",))
params = init_params(cfg0, jax.random.PRNGKey(0))
toks = jax.random.randint(jax.random.PRNGKey(1), (4, 8), 0, 64)
nxt = jax.random.randint(jax.random.PRNGKey(3), (4, 1), 0, 64)
ref = forward(cfg0, params, jnp.concatenate([toks, nxt], 1))[:, -1]
mesh = make_auto_mesh((2, 4), ("data", "model"))
with jax.set_mesh(mesh):
    cache = init_cache(cfg0, batch=4, max_seq=16)
    dec_dense = make_decode_step(cfg0, mesh=mesh)
    for i in range(8):
        _, cache = jax.jit(dec_dense)(params, cache, toks[:, i:i+1], jnp.int32(i))
    cache = jax.tree_util.tree_map(
        lambda x: jax.device_put(x, NamedSharding(
            mesh, P(None, None, "data", "model", None, None))), cache)
    dec_flash = make_decode_step(cfg1, mesh=mesh)
    lg, cache2 = jax.jit(dec_flash)(params, cache, nxt, jnp.int32(8))
err = float(jnp.max(jnp.abs(lg[:, 0].astype(jnp.float32) - ref.astype(jnp.float32))))
assert err < 5e-2, err
assert float(jnp.abs(jax.device_get(cache2["k"])[:, :, :, 8]).sum()) > 0
print("FLASH_DECODE_OK", err)
"""


def test_flash_decode_matches_dense():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    # Pin the subprocess to CPU: probing other platform plugins (e.g. the
    # baked-in TPU runtime on dev images) can stall minutes in metadata
    # retries. --xla_force_host_platform_device_count still applies on cpu.
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", FLASH_DECODE_SCRIPT],
                          capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "FLASH_DECODE_OK" in proc.stdout


DST_PARTITIONED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np, dataclasses
from repro.launch.mesh import make_auto_mesh
from repro.models.gnn import SAGEConfig, init_params, random_graph
from repro.models.gnn.graphsage import full_graph_forward

N, E, SHARDS = 160, 800, 8
g = random_graph(N, E, 12, 4, seed=0)
n_local = N // SHARDS
buckets = [[] for _ in range(SHARDS)]
for e in range(E):
    buckets[g["dst"][e] // n_local].append(e)
cap = max(len(b) for b in buckets)
src, dst, w = [], [], []
for i, b in enumerate(buckets):
    idx = np.asarray(b, np.int64)
    src.extend(g["src"][idx]); dst.extend(g["dst"][idx]); w.extend([1.0] * len(b))
    for _ in range(cap - len(b)):
        src.append(0); dst.append(i * n_local); w.append(0.0)
gp = {"features": g["features"], "degree_inv": g["degree_inv"],
      "labels": g["labels"], "src": np.asarray(src, np.int32),
      "dst": np.asarray(dst, np.int32),
      "edge_weight": np.asarray(w, np.float32)}
cfg0 = SAGEConfig(n_layers=2, d_in=12, d_hidden=16, n_classes=4)
cfg1 = dataclasses.replace(cfg0, partitioned_edges=True)
params = init_params(cfg0, jax.random.PRNGKey(0))
dense = full_graph_forward(cfg0, params, {k: jnp.asarray(v) for k, v in g.items()})
mesh = make_auto_mesh((2, 4), ("data", "model"))
with jax.set_mesh(mesh):
    out = jax.jit(lambda p, gr: full_graph_forward(cfg1, p, gr, mesh))(
        params, {k: jnp.asarray(v) for k, v in gp.items()})
err = float(jnp.max(jnp.abs(out - dense)))
assert err < 1e-5, err
print("DST_PARTITIONED_OK", err)
"""


def test_gnn_dst_partitioned_matches_dense():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    # Pin the subprocess to CPU: probing other platform plugins (e.g. the
    # baked-in TPU runtime on dev images) can stall minutes in metadata
    # retries. --xla_force_host_platform_device_count still applies on cpu.
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", DST_PARTITIONED_SCRIPT],
                          capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "DST_PARTITIONED_OK" in proc.stdout
