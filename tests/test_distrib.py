"""Distribution-layer tests: compression math + multi-device shard_map paths.

Multi-device cases run in a subprocess with XLA_FLAGS forcing 8 host devices
(the main test process stays single-device; see conftest note and the dry-run
contract in the brief).
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.distrib import quantize_int8, dequantize_int8, CompressedAllReduce


def test_int8_quantization_roundtrip_error_bound():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(256,)).astype(np.float32)) * 3.0
    q, scale = quantize_int8(x)
    assert q.dtype == jnp.int8
    err = np.abs(np.asarray(dequantize_int8(q, scale)) - np.asarray(x))
    assert err.max() <= float(scale) / 2 + 1e-6  # half-ULP of the grid


def test_error_feedback_converges_on_quadratic():
    """EF-compressed GD matches uncompressed GD's optimum on a quadratic."""
    rng = np.random.default_rng(1)
    A = jnp.asarray(rng.normal(size=(16, 16)).astype(np.float32))
    A = A @ A.T / 16 + jnp.eye(16)
    b = jnp.asarray(rng.normal(size=(16,)).astype(np.float32))
    x_star = jnp.linalg.solve(A, b)

    def grad(x):
        return A @ x - b

    x = jnp.zeros(16)
    state = CompressedAllReduce.init(x)
    lr = 0.1
    for _ in range(400):
        payload, state = state.compress_correct(grad(x))
        g = dequantize_int8(*payload)
        x = x - lr * g
    np.testing.assert_allclose(np.asarray(x), np.asarray(x_star), atol=1e-2)


def test_compression_without_error_feedback_is_worse():
    rng = np.random.default_rng(2)
    A = jnp.asarray(rng.normal(size=(16, 16)).astype(np.float32))
    A = A @ A.T / 16 + jnp.eye(16)
    b = jnp.asarray(rng.normal(size=(16,)).astype(np.float32))
    x_star = jnp.linalg.solve(A, b)

    def run(use_ef):
        x = jnp.zeros(16)
        state = CompressedAllReduce.init(x)
        for _ in range(200):
            g = A @ x - b
            if use_ef:
                payload, state = state.compress_correct(g)
            else:
                payload = quantize_int8(g)
            x = x - 0.1 * dequantize_int8(*payload) if not use_ef else \
                x - 0.1 * dequantize_int8(*payload)
            if use_ef:
                pass
        return float(jnp.linalg.norm(x - x_star))

    # plain quantization stalls at a grid-limited error; EF does not
    assert run(True) <= run(False) + 1e-6


MULTIDEV_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.launch.mesh import make_auto_mesh
from repro.distrib import masked_psum_lookup
from repro.distrib.compression import compressed_psum, CompressedAllReduce

mesh = make_auto_mesh((2, 4), ("data", "model"))

# --- masked psum lookup == dense take -----------------------------------------
N, D, B, K = 64, 4, 8, 5
rng = np.random.default_rng(0)
table = jnp.asarray(rng.normal(size=(N, D)).astype(np.float32))
ids = jnp.asarray(rng.integers(0, N, size=(B, K)))
with jax.set_mesh(mesh):
    lookup = masked_psum_lookup(mesh, batch_dims=2)
    got = jax.jit(lookup)(
        jax.device_put(table, NamedSharding(mesh, P("model", None))),
        jax.device_put(ids, NamedSharding(mesh, P("data", None))))
want = np.asarray(table)[np.asarray(ids)]
np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6)

# gradient flows back into the sharded table
def loss(t):
    return jnp.sum(lookup(t, ids) ** 2)
g = jax.jit(jax.grad(loss))(
    jax.device_put(table, NamedSharding(mesh, P("model", None))))
# reference grad
import numpy as onp
ref = onp.zeros((N, D), onp.float32)
e = onp.asarray(table)[onp.asarray(ids)]
for bi in range(B):
    for ki in range(K):
        ref[int(ids[bi, ki])] += 2 * e[bi, ki]
np.testing.assert_allclose(np.asarray(g), ref, rtol=1e-5)

# --- compressed psum across 'data' --------------------------------------------
grads = jnp.asarray(rng.normal(size=(8, 16)).astype(np.float32))

def body(g):
    state = CompressedAllReduce.init(g)
    out, _ = compressed_psum(g, "data", state)
    return out

f = jax.shard_map(body, mesh=mesh, in_specs=P("data", None), out_specs=P("data", None))
out = jax.jit(f)(grads)
# each data shard holds 4 rows; result = mean across the 2 data shards
want = (np.asarray(grads[:4]) + np.asarray(grads[4:])) / 2
got = np.asarray(out)
np.testing.assert_allclose(got[:4], want, atol=0.05)
np.testing.assert_allclose(got[4:], want, atol=0.05)
print("MULTIDEV_OK")
"""


def test_shard_map_paths_on_8_fake_devices():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    # Pin the subprocess to CPU: probing other platform plugins (e.g. the
    # baked-in TPU runtime on dev images) can stall minutes in metadata
    # retries. --xla_force_host_platform_device_count still applies on cpu.
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", MULTIDEV_SCRIPT],
                          capture_output=True, text=True, env=env, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "MULTIDEV_OK" in proc.stdout


def test_hlo_cost_walker_on_synthetic_module():
    """While-aware walker: trip counts multiply flops/bytes/wire."""
    from repro.launch.hlo_cost import analyze_hlo

    hlo = """
HloModule test

%body (p: (s32[], f32[8,16])) -> (s32[], f32[8,16]) {
  %p = (s32[], f32[8,16]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[8,16] get-tuple-element(%p), index=1
  %w = f32[16,16] constant(0)
  %dot.1 = f32[8,16]{1,0} dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[8,16]{1,0} all-reduce(%dot.1), replica_groups=[2,4]<=[8]
  ROOT %t = (s32[], f32[8,16]) tuple(%i, %ar)
}

%cond (p: (s32[], f32[8,16])) -> pred[] {
  %p = (s32[], f32[8,16]) parameter(0)
  ROOT %lt = pred[] constant(true)
}

ENTRY %main (a: f32[8,16]) -> f32[8,16] {
  %a = f32[8,16] parameter(0)
  %zero = s32[] constant(0)
  %init = (s32[], f32[8,16]) tuple(%zero, %a)
  %loop = (s32[], f32[8,16]) while(%init), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"5"}}
  ROOT %out = f32[8,16] get-tuple-element(%loop), index=1
}
"""
    out = analyze_hlo(hlo)
    # dot flops: 2 * 8*16 * 16 = 4096 per iteration, x5 trips
    np.testing.assert_allclose(out["flops"], 5 * 4096)
    # all-reduce wire: ring 2*(4-1)/4 * 8*16*4 bytes = 768, x5
    np.testing.assert_allclose(out["collective_ops"]["all-reduce"], 5 * 768)
    assert out["unknown_trip_loops"] == 0
