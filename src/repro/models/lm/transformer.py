"""Decoder-only transformer family (llama3 / phi3 / granite-MoE / llama4).

Production posture:
  * **scan-over-layers** with stacked parameters — HLO size (and compile time)
    independent of depth; remat policy on the layer body.
  * **3D sharding**: TP over ``model`` (heads / ffn hidden / vocab / experts),
    FSDP over the data axes (``pod`` + ``data``) on the d_model dim of every
    weight, DP over (pod, data) for activations. GSPMD inserts the FSDP
    all-gathers; the MoE block does its gather explicitly inside shard_map.
  * **MoE**: expert-parallel over ``model`` with capacity-bounded top-k
    routing. Activations stay replicated across ``model`` (Megatron-style),
    each expert shard processes the tokens routed to its local experts and
    one psum merges expert outputs — the same collective a dense TP FFN
    needs, so EP costs no extra wire vs dense. ``capacity_factor >= n_experts``
    makes routing lossless (used by tests to compare against the dense oracle).
  * **memory-efficient attention**: q-block-chunked softmax(QK^T)V (lax.scan)
    so the (S, S) score matrix never materializes for long prefill; the
    Pallas flash kernel is the TPU fast path (kernels/flash_attention.py),
    this jnp chunked path is the portable/compile-analysis path.
  * **decode**: KV cache stacked over layers, batch-sharded over DP and
    seq-sharded over ``model`` (flash-decoding style partial-softmax psum is
    exercised in the perf pass).
  * **microbatching**: train_step accumulates grads over ``microbatches``
    splits in fp32, keeping the global-batch interface while bounding live
    activation memory.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro import optim as optim_lib


# ---------------------------------------------------------------------------
# Config
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class LMConfig:
    name: str = "lm"
    n_layers: int = 4
    d_model: int = 256
    n_heads: int = 4
    n_kv_heads: int = 4
    d_ff: int = 1024
    vocab: int = 1024
    head_dim: Optional[int] = None
    rope_theta: float = 500_000.0
    # MoE
    moe: bool = False
    n_experts: int = 0
    top_k: int = 1
    d_ff_moe: int = 0
    moe_layer_step: int = 1          # 1 = every layer MoE, 2 = alternate
    n_shared_experts: int = 0        # llama4-style always-on shared expert
    capacity_factor: float = 1.25
    # numerics / memory
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    opt_dtype: Any = jnp.float32      # AdamW moments (bf16 for 400B-class)
    remat: bool = True
    attn_chunk: int = 1024            # q-block size for chunked attention
    scan_chunks: Optional[int] = None  # two-level remat scan factor (U1)
    grad_accum_dtype: Any = jnp.float32  # microbatch grad accumulator
    explicit_row_parallel: bool = False  # shard_map bf16 psum for wo/w_down
    flash_decode: bool = False        # shard_map partial-softmax decode attn
    decode_seq_axes: Tuple[str, ...] = ("model",)  # KV cache seq sharding
    microbatches: int = 1
    max_seq: int = 8192               # decode cache capacity

    def __post_init__(self):
        if self.head_dim is None:
            self.head_dim = self.d_model // self.n_heads
        if self.moe and self.d_ff_moe == 0:
            self.d_ff_moe = self.d_ff

    @property
    def padded_vocab(self) -> int:
        """Vocab padded to a TP-shardable multiple (Megatron-style). The
        logical vocab is unchanged; padded logit columns are masked to -inf
        in the loss and in decode outputs."""
        return -(-self.vocab // 16) * 16

    @property
    def n_units(self) -> int:
        return self.n_layers // self.moe_layer_step if self.moe else self.n_layers

    @property
    def layers_per_unit(self) -> int:
        return self.moe_layer_step if self.moe else 1

    def param_count(self) -> int:
        D, Dh = self.d_model, self.head_dim
        attn = D * self.n_heads * Dh * 2 + D * self.n_kv_heads * Dh * 2
        dense_ffn = 3 * D * self.d_ff
        total = 2 * self.vocab * D + self.n_layers * (attn + 2 * D) + D
        if not self.moe:
            return total + self.n_layers * dense_ffn
        n_moe = self.n_layers // self.moe_layer_step
        n_dense = self.n_layers - n_moe
        total += n_dense * dense_ffn
        total += n_moe * (self.n_experts * 3 * D * self.d_ff_moe + D * self.n_experts)
        total += n_moe * self.n_shared_experts * 3 * D * self.d_ff_moe
        return total

    def active_param_count(self) -> int:
        if not self.moe:
            return self.param_count()
        D = self.d_model
        n_moe = self.n_layers // self.moe_layer_step
        routed = self.n_experts * 3 * D * self.d_ff_moe
        active_routed = self.top_k * 3 * D * self.d_ff_moe
        return self.param_count() - n_moe * (routed - active_routed)


# ---------------------------------------------------------------------------
# Parameter init + sharding specs
# ---------------------------------------------------------------------------

def _dense_layer_shapes(cfg: LMConfig) -> Dict[str, tuple]:
    D, Dh = cfg.d_model, cfg.head_dim
    return {
        "ln1": (D,), "ln2": (D,),
        "wq": (D, cfg.n_heads * Dh), "wk": (D, cfg.n_kv_heads * Dh),
        "wv": (D, cfg.n_kv_heads * Dh), "wo": (cfg.n_heads * Dh, D),
        "w_gate": (D, cfg.d_ff), "w_up": (D, cfg.d_ff), "w_down": (cfg.d_ff, D),
    }


def _moe_layer_shapes(cfg: LMConfig) -> Dict[str, tuple]:
    D, Dh, E, F = cfg.d_model, cfg.head_dim, cfg.n_experts, cfg.d_ff_moe
    shapes = {
        "ln1": (D,), "ln2": (D,),
        "wq": (D, cfg.n_heads * Dh), "wk": (D, cfg.n_kv_heads * Dh),
        "wv": (D, cfg.n_kv_heads * Dh), "wo": (cfg.n_heads * Dh, D),
        "router": (D, E),
        "we_gate": (E, D, F), "we_up": (E, D, F), "we_down": (E, F, D),
    }
    if cfg.n_shared_experts:
        Fs = cfg.n_shared_experts * F
        shapes.update({"ws_gate": (D, Fs), "ws_up": (D, Fs), "ws_down": (Fs, D)})
    return shapes


def _stack_shapes(cfg: LMConfig) -> Dict[str, Dict[str, tuple]]:
    out = {}
    if cfg.moe:
        out["moe"] = _moe_layer_shapes(cfg)
        if cfg.moe_layer_step == 2:
            out["dense"] = _dense_layer_shapes(cfg)
    else:
        out["dense"] = _dense_layer_shapes(cfg)
    return out


def init_params(cfg: LMConfig, rng: jax.Array):
    """Real initialization (smoke tests); the dry-run uses eval_shape."""
    U = cfg.n_units
    keys = iter(jax.random.split(rng, 64))

    def init_one(shape, scale=None):
        fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
        scale = scale if scale is not None else (1.0 / max(fan_in, 1)) ** 0.5
        return (jax.random.normal(next(keys), shape) * scale).astype(cfg.param_dtype)

    params = {
        "embed": init_one((cfg.padded_vocab, cfg.d_model), scale=0.02),
        "ln_f": jnp.ones((cfg.d_model,), cfg.param_dtype),
        "lm_head": init_one((cfg.d_model, cfg.padded_vocab)),
    }
    for stack, shapes in _stack_shapes(cfg).items():
        params[stack] = {}
        for name, shape in shapes.items():
            full = (U,) + shape
            if name.startswith("ln"):
                params[stack][name] = jnp.ones(full, cfg.param_dtype)
            else:
                params[stack][name] = init_one(full)
    return params


def param_specs(cfg: LMConfig, mesh) -> Any:
    """FSDP over data axes on d_model dims + TP over 'model'.

    Any axis assignment whose mesh size does not divide the dimension is
    dropped to replication (e.g. granite's vocab 49155 is not 16-divisible,
    so its embed/lm_head stay vocab-replicated).
    """
    fsdp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    tp = "model"

    def axes_size(entry) -> int:
        if entry is None:
            return 1
        names = entry if isinstance(entry, tuple) else (entry,)
        out = 1
        for n in names:
            out *= mesh.shape[n]
        return out

    def guard(spec: P, shape: tuple) -> P:
        entries = []
        for dim, entry in zip(shape, tuple(spec) + (None,) * len(shape)):
            entries.append(entry if dim % axes_size(entry) == 0 else None)
        return P(*entries)

    def spec_for(name: str, shape: tuple) -> P:
        if name.startswith("ln"):
            return P(*([None] * len(shape)))
        table = {
            "wq": P(None, fsdp, tp), "wk": P(None, fsdp, tp),
            "wv": P(None, fsdp, tp), "wo": P(None, tp, fsdp),
            "w_gate": P(None, fsdp, tp), "w_up": P(None, fsdp, tp),
            "w_down": P(None, tp, fsdp),
            "ws_gate": P(None, fsdp, tp), "ws_up": P(None, fsdp, tp),
            "ws_down": P(None, tp, fsdp),
            "router": P(None, None, None),
            # experts: EP over model, FSDP on d_model dim
            "we_gate": P(None, tp, fsdp, None), "we_up": P(None, tp, fsdp, None),
            "we_down": P(None, tp, None, fsdp),
        }
        return guard(table[name], shape)

    D, V = cfg.d_model, cfg.padded_vocab
    specs = {
        "embed": guard(P(tp, fsdp), (V, D)),
        "ln_f": P(None),
        "lm_head": guard(P(fsdp, tp), (D, V)),
    }
    for stack, shapes in _stack_shapes(cfg).items():
        U = cfg.n_units
        specs[stack] = {name: spec_for(name, (U,) + shape)
                        for name, shape in shapes.items()}
    return specs


# ---------------------------------------------------------------------------
# Building blocks
# ---------------------------------------------------------------------------

def _wsc(x: jax.Array, spec: P) -> jax.Array:
    """with_sharding_constraint that no-ops outside a mesh context."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty:
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def _flash_decode_attention(cfg: LMConfig, mesh, dp_axes, seq_axes,
                            q, k_cache, v_cache, new_k, new_v, cache_index):
    """Flash-decoding over a seq-sharded KV cache (beyond-paper serving opt).

    Each seq shard computes a PARTIAL softmax over its local KV block
    (running max m, exp-sum l, weighted value o) and a 3-tensor psum
    combines them — wire is O(B * H * Dh) per layer instead of the
    multi-GB all-gathers XLA-auto emits for softmax over a sharded axis.
    The new token's KV is scattered into whichever shard owns position
    ``cache_index`` inside the same region.

    q: (B, 1, Hq, Dh); caches: (B, S, Hkv, Dh) sharded on S over seq_axes.
    Returns (attn_out (B, 1, Hq, Dh), new_k_cache, new_v_cache).
    """
    group = cfg.n_heads // cfg.n_kv_heads
    n_seq_shards = 1
    for a in seq_axes:
        n_seq_shards *= mesh.shape[a]

    def body(q, k_loc, v_loc, new_k, new_v, index):
        B, S_loc, Hkv, Dh = k_loc.shape
        shard = jnp.zeros((), jnp.int32)
        for a in seq_axes:
            shard = shard * mesh.shape[a] + jax.lax.axis_index(a)
        offset = shard * S_loc
        # scatter the new token's kv into the owning shard
        local_pos = jnp.clip(index - offset, 0, S_loc - 1)
        owns = (index >= offset) & (index < offset + S_loc)
        k_upd = jax.lax.dynamic_update_slice(
            k_loc, new_k.astype(k_loc.dtype), (0, local_pos, 0, 0))
        v_upd = jax.lax.dynamic_update_slice(
            v_loc, new_v.astype(v_loc.dtype), (0, local_pos, 0, 0))
        k_loc = jnp.where(owns, k_upd, k_loc)
        v_loc = jnp.where(owns, v_upd, v_loc)
        # local partial attention
        kq = jnp.repeat(k_loc, group, axis=2) if group > 1 else k_loc
        vq = jnp.repeat(v_loc, group, axis=2) if group > 1 else v_loc
        s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                       kq.astype(jnp.float32)) * (Dh ** -0.5)
        valid = (offset + jnp.arange(S_loc)) <= index
        s = jnp.where(valid[None, None, None, :], s, -jnp.inf)
        m_loc = jnp.max(s, axis=-1)                       # (B, H, 1)
        # all-masked shards contribute zero weight
        m_safe = jnp.where(jnp.isfinite(m_loc), m_loc, 0.0)
        p = jnp.exp(jnp.where(jnp.isfinite(s), s - m_safe[..., None], -jnp.inf))
        l_loc = jnp.sum(p, axis=-1)                       # (B, H, 1)
        o_loc = jnp.einsum("bhqk,bkhd->bhqd", p, vq.astype(jnp.float32))
        m_g = jax.lax.pmax(m_safe, seq_axes)
        scale = jnp.where(l_loc > 0, jnp.exp(m_safe - m_g), 0.0)
        l_g = jax.lax.psum(l_loc * scale, seq_axes)
        o_g = jax.lax.psum(o_loc * scale[..., None], seq_axes)
        out = (o_g / jnp.maximum(l_g[..., None], 1e-30)).astype(cfg.dtype)
        return out.transpose(0, 2, 1, 3), k_loc, v_loc    # (B,1,H,Dh)

    dp = _dp(dp_axes)
    cache_spec = P(dp, seq_axes, None, None)
    out = shard_map(
        body, mesh=mesh,
        in_specs=(P(dp, None, None, None), cache_spec, cache_spec,
                  P(dp, None, None, None), P(dp, None, None, None), P()),
        out_specs=(P(dp, None, None, None), cache_spec, cache_spec),
        check_vma=False,
    )(q, k_cache, v_cache, new_k, new_v, cache_index)
    return out


def _dp(dp_axes: tuple):
    """PartitionSpec entry for the batch dim ('' tuple -> replicated)."""
    return dp_axes if dp_axes else None


def _rmsnorm(x: jax.Array, scale: jax.Array) -> jax.Array:
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + 1e-6)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, Dh); positions: (S,) or (B, S)."""
    Dh = x.shape[-1]
    half = Dh // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    if positions.ndim == 1:
        angles = positions.astype(jnp.float32)[:, None] * freqs[None, :]  # (S, half)
        angles = angles[None, :, None, :]
    else:
        angles = positions.astype(jnp.float32)[..., None] * freqs  # (B, S, half)
        angles = angles[:, :, None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def _chunked_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                       causal: bool, chunk: int, kv_offset: int = 0) -> jax.Array:
    """softmax(QK^T)V without materializing (Sq, Skv) — scan over q blocks.

    q, k, v: (B, H, S, Dh) with MATCHING head counts (GQA KV are repeated to
    Hq by the caller *after* the TP sharding constraint, so the repeat stays
    shard-local — reshaping a head-sharded tensor into (Hkv, group) instead
    triggers a GSPMD full-rematerialization with wrong numerics on CPU).
    kv_offset: absolute position of q[0] minus kv[0] (decode alignment).
    """
    B, Hq, Sq, Dh = q.shape
    Skv = k.shape[2]
    scale = Dh ** -0.5
    chunk = min(chunk, Sq)
    pad = (-Sq) % chunk
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0))) if pad else q
    nq = (Sq + pad) // chunk
    qp = qp.reshape(B, Hq, nq, chunk, Dh)

    k_pos = jnp.arange(Skv)

    def block(carry, inputs):
        qi, q_blk = inputs  # q_blk: (B, H, chunk, Dh)
        s = jnp.einsum("bhqd,bhkd->bhqk", q_blk.astype(jnp.float32),
                       k.astype(jnp.float32)) * scale
        if causal:
            q_pos = qi * chunk + jnp.arange(chunk) + kv_offset
            mask = q_pos[:, None] >= k_pos[None, :]
            s = jnp.where(mask[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
        return carry, o.astype(q.dtype)

    _, out = jax.lax.scan(block, None,
                          (jnp.arange(nq), jnp.moveaxis(qp, 2, 0)))
    out = jnp.moveaxis(out, 0, 2).reshape(B, Hq, Sq + pad, Dh)
    return out[:, :, :Sq]


def _attention_block(cfg: LMConfig, lp: Dict[str, jax.Array], h: jax.Array,
                     positions: jax.Array, dp_axes: tuple,
                     cache: Optional[Dict] = None,
                     cache_index: Optional[jax.Array] = None,
                     mesh=None):
    """Self-attention sublayer. Returns (out, new_cache_entry)."""
    B, S, D = h.shape
    Dh = cfg.head_dim
    x = _rmsnorm(h, lp["ln1"])
    q = (x @ lp["wq"].astype(cfg.dtype)).reshape(B, S, cfg.n_heads, Dh)
    k = (x @ lp["wk"].astype(cfg.dtype)).reshape(B, S, cfg.n_kv_heads, Dh)
    v = (x @ lp["wv"].astype(cfg.dtype)).reshape(B, S, cfg.n_kv_heads, Dh)
    # NOTE: no explicit head-dim constraint on q/k — the TP ('model') head
    # sharding propagates naturally from wq/wk's output dim, and forcing it
    # with with_sharding_constraint miscompiles under CPU GSPMD (verified by
    # bisect: constrained head-sharded q + repeated kv give wrong numerics;
    # tests/test_archs.py::test_moe_shard_map_matches_dense_oracle guards it).
    k = _wsc(k, P(_dp(dp_axes), None, None, None))
    q = _rope(q, positions, cfg.rope_theta)
    k = _rope(k, positions, cfg.rope_theta)
    group = cfg.n_heads // cfg.n_kv_heads

    def rep(x, constrain=True):
        """Broadcast KV heads to Hq. In the prefill/train path the result is
        constrained to the q sharding so each TP shard materializes only its
        local repeated heads; in decode the cache stays seq-sharded and the
        repeat must follow it (constrain=False) — flash-decoding style."""
        if group == 1:
            return x
        del constrain  # sharding left to propagation (see note above)
        return jnp.repeat(x, group, axis=2)

    if cache is None:
        out = _chunked_attention(q.transpose(0, 2, 1, 3),
                                 rep(k).transpose(0, 2, 1, 3),
                                 rep(v).transpose(0, 2, 1, 3), causal=True,
                                 chunk=cfg.attn_chunk)
        new_entry = {"k": k, "v": v}  # (B, S, Hkv, Dh) — prefill cache entry
    elif cfg.flash_decode and mesh is not None:
        seq_axes = tuple(cfg.decode_seq_axes)
        attn, k_new, v_new = _flash_decode_attention(
            cfg, mesh, dp_axes, seq_axes, q, cache["k"], cache["v"],
            k, v, cache_index)
        out = attn.transpose(0, 2, 1, 3)   # (B, H, 1, Dh)
        new_entry = {"k": k_new, "v": v_new}
    else:
        # decode: append S (=1) new kv at cache_index, attend over prefix
        k_cache = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, cache_index, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, cache_index, 0, 0))
        S_max = k_cache.shape[1]
        qt = q.transpose(0, 2, 1, 3)                       # (B, H, 1, Dh)
        kt = rep(k_cache, constrain=False).transpose(0, 2, 1, 3)  # (B, H, S, Dh)
        vt = rep(v_cache, constrain=False).transpose(0, 2, 1, 3)
        s = jnp.einsum("bhqd,bhkd->bhqk", qt.astype(jnp.float32),
                       kt.astype(jnp.float32)) * (Dh ** -0.5)
        valid = jnp.arange(S_max)[None, :] <= (cache_index + jnp.arange(S)[:, None])
        s = jnp.where(valid[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bhqk,bhkd->bhqd", p, vt.astype(jnp.float32))
        out = out.astype(cfg.dtype)
        new_entry = {"k": k_cache, "v": v_cache}

    out = out.transpose(0, 2, 1, 3).reshape(B, S, cfg.n_heads * Dh)
    if cfg.explicit_row_parallel and mesh is not None and cache is None:
        attn_out = _row_parallel_matmul(cfg, mesh, dp_axes, out, lp["wo"])
    else:
        attn_out = out @ lp["wo"].astype(cfg.dtype)
        attn_out = _wsc(attn_out, P(_dp(dp_axes), None, None))
    return h + attn_out, new_entry


def _row_parallel_matmul(cfg: LMConfig, mesh, dp_axes, x, w):
    """Megatron row-parallel matmul as an explicit shard_map:
    x (B,S,K) sharded on K over 'model'; w (K,D) sharded (model, fsdp).
    The partial products psum over 'model' IN BF16 — GSPMD's auto placement
    reduces the pre-downcast f32 dot output (2x wire; on CPU backends the
    f32 convert is unavoidable in auto mode). The fsdp weight shard is
    all-gathered in bf16 inside the region for the same reason."""
    fsdp = dp_axes

    def body(x_l, w_l):
        if fsdp:
            w_l = jax.lax.all_gather(w_l, fsdp, axis=1, tiled=True)
        y = (x_l.astype(cfg.dtype) @ w_l.astype(cfg.dtype)).astype(cfg.dtype)
        return jax.lax.psum(y, "model")

    return shard_map(
        body, mesh=mesh,
        in_specs=(P(_dp(dp_axes), None, "model"), P("model", _dp(fsdp))),
        out_specs=P(_dp(dp_axes), None, None), check_vma=False)(x, w)


def _dense_ffn(cfg: LMConfig, lp, h, dp_axes, mesh=None):
    x = _rmsnorm(h, lp["ln2"])
    gate = jax.nn.silu(x @ lp["w_gate"].astype(cfg.dtype))
    up = x @ lp["w_up"].astype(cfg.dtype)
    hidden = _wsc(gate * up, P(_dp(dp_axes), None, "model"))
    if cfg.explicit_row_parallel and mesh is not None:
        return h + _row_parallel_matmul(cfg, mesh, dp_axes, hidden,
                                        lp["w_down"])
    return h + hidden @ lp["w_down"].astype(cfg.dtype)


# ---------------------------------------------------------------------------
# MoE block (shard_map expert parallelism over 'model')
# ---------------------------------------------------------------------------

def _moe_ffn(cfg: LMConfig, lp, h, mesh, dp_axes):
    B, S, D = h.shape
    if mesh is None:
        return _moe_ffn_dense(cfg, lp, h)
    fsdp = dp_axes  # FSDP axes for the stored expert weights

    def body(x, router_w, wg, wu, wd):
        # x: (B_loc, S, D) local tokens (replicated across 'model')
        # wg/wu: (E_loc, D/fsdp, F); wd: (E_loc, F, D/fsdp)
        if fsdp:
            wg = jax.lax.all_gather(wg, fsdp, axis=1, tiled=True)
            wu = jax.lax.all_gather(wu, fsdp, axis=1, tiled=True)
            wd = jax.lax.all_gather(wd, fsdp, axis=2, tiled=True)
        Bl, Sl, Dl = x.shape
        T = Bl * Sl
        E_loc = wg.shape[0]
        E = cfg.n_experts
        m = jax.lax.axis_index("model")
        xt = x.reshape(T, Dl)
        logits = (xt.astype(jnp.float32) @ router_w.astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)            # (T, E)
        top_p, top_i = jax.lax.top_k(probs, cfg.top_k)     # (T, k)
        top_p = top_p / jnp.maximum(jnp.sum(top_p, -1, keepdims=True), 1e-9)
        capacity = max(int(T * cfg.top_k / E * cfg.capacity_factor), 1)
        capacity = min(capacity, T)

        out = jnp.zeros((T, Dl), jnp.float32)
        for e in range(E_loc):
            eid = m * E_loc + e
            gate = jnp.sum(jnp.where(top_i == eid, top_p, 0.0), axis=-1)  # (T,)
            sel_gate, sel_idx = jax.lax.top_k(gate, capacity)
            xe = jnp.take(xt, sel_idx, axis=0).astype(cfg.dtype)  # (C, D)
            hid = jax.nn.silu(xe @ wg[e].astype(cfg.dtype)) * (xe @ wu[e].astype(cfg.dtype))
            ye = (hid @ wd[e].astype(cfg.dtype)).astype(jnp.float32)
            out = out.at[sel_idx].add(ye * sel_gate[:, None])
        out = jax.lax.psum(out, "model")
        return out.reshape(Bl, Sl, Dl).astype(cfg.dtype)

    moe = shard_map(
        body, mesh=mesh,
        in_specs=(P(_dp(dp_axes), None, None), P(None, None),
                  P("model", _dp(fsdp), None), P("model", _dp(fsdp), None),
                  P("model", None, _dp(fsdp))),
        out_specs=P(_dp(dp_axes), None, None),
        check_vma=False,
    )
    x = _rmsnorm(h, lp["ln2"])
    y = moe(x, lp["router"], lp["we_gate"], lp["we_up"], lp["we_down"])
    if cfg.n_shared_experts:
        gate = jax.nn.silu(x @ lp["ws_gate"].astype(cfg.dtype))
        up = x @ lp["ws_up"].astype(cfg.dtype)
        y = y + (gate * up) @ lp["ws_down"].astype(cfg.dtype)
    return h + y


def _moe_ffn_dense(cfg: LMConfig, lp, h):
    """Exact (lossless) MoE oracle: every expert over every token, one-hot
    combined. Used on single-device smoke tests and as the routing oracle
    for the shard_map path (with capacity_factor >= n_experts they agree)."""
    B, S, D = h.shape
    x = _rmsnorm(h, lp["ln2"])
    xt = x.reshape(B * S, D)
    logits = xt.astype(jnp.float32) @ lp["router"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, cfg.top_k)
    top_p = top_p / jnp.maximum(jnp.sum(top_p, -1, keepdims=True), 1e-9)
    combine = jnp.zeros_like(probs)
    for k in range(cfg.top_k):
        combine = combine + jax.nn.one_hot(top_i[:, k], cfg.n_experts) * top_p[:, k:k+1]
    y = jnp.zeros((B * S, D), jnp.float32)
    for e in range(cfg.n_experts):
        hid = jax.nn.silu(xt @ lp["we_gate"][e].astype(cfg.dtype)) * (
            xt @ lp["we_up"][e].astype(cfg.dtype))
        ye = (hid @ lp["we_down"][e].astype(cfg.dtype)).astype(jnp.float32)
        y = y + ye * combine[:, e:e+1]
    y = y.reshape(B, S, D).astype(cfg.dtype)
    if cfg.n_shared_experts:
        gate = jax.nn.silu(x @ lp["ws_gate"].astype(cfg.dtype))
        up = x @ lp["ws_up"].astype(cfg.dtype)
        y = y + (gate * up) @ lp["ws_down"].astype(cfg.dtype)
    return h + y


# ---------------------------------------------------------------------------
# Forward / loss / train step
# ---------------------------------------------------------------------------

def _unit_body(cfg: LMConfig, mesh, dp_axes, h, positions, unit_params,
               collect_kv: bool = False):
    """One scan unit: (step-1) dense layers then the MoE/dense layer."""
    entries = []
    if cfg.moe and cfg.moe_layer_step == 2:
        h, e = _attention_block(cfg, unit_params["dense"], h, positions,
                                dp_axes, mesh=mesh)
        entries.append(e)
        h = _dense_ffn(cfg, unit_params["dense"], h, dp_axes, mesh=mesh)
        h, e = _attention_block(cfg, unit_params["moe"], h, positions,
                                dp_axes, mesh=mesh)
        entries.append(e)
        h = _moe_ffn(cfg, unit_params["moe"], h, mesh, dp_axes)
    elif cfg.moe:
        h, e = _attention_block(cfg, unit_params["moe"], h, positions,
                                dp_axes, mesh=mesh)
        entries.append(e)
        h = _moe_ffn(cfg, unit_params["moe"], h, mesh, dp_axes)
    else:
        h, e = _attention_block(cfg, unit_params["dense"], h, positions,
                                dp_axes, mesh=mesh)
        entries.append(e)
        h = _dense_ffn(cfg, unit_params["dense"], h, dp_axes, mesh=mesh)
    if not collect_kv:
        return h
    unit_cache = {"k": jnp.stack([e["k"] for e in entries]),
                  "v": jnp.stack([e["v"] for e in entries])}
    return h, unit_cache


def forward(cfg: LMConfig, params, tokens: jax.Array, mesh=None) -> jax.Array:
    """tokens (B, S) -> logits (B, S, V)."""
    dp_axes = _dp_axes(mesh)
    B, S = tokens.shape
    h = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
    h = _wsc(h, P(_dp(dp_axes), None, None))
    positions = jnp.arange(S)

    stacks = {k: v for k, v in params.items()
              if k in ("dense", "moe")}

    def body(h, unit_params):
        h = _unit_body(cfg, mesh, dp_axes, h, positions, unit_params)
        return h, None

    if cfg.remat:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable)

    if cfg.scan_chunks and cfg.scan_chunks > 1 and cfg.n_units % cfg.scan_chunks == 0:
        # two-level (sqrt) remat scan: backward keeps U1 outer + U2 inner
        # carries live instead of all n_units — the 126-layer memory fix.
        u1 = cfg.scan_chunks
        u2 = cfg.n_units // u1
        stacks2 = jax.tree_util.tree_map(
            lambda x: x.reshape((u1, u2) + x.shape[1:]), stacks)

        def outer(h, chunk_params):
            h, _ = jax.lax.scan(body, h, chunk_params)
            return h, None

        if cfg.remat:
            outer = jax.checkpoint(
                outer, policy=jax.checkpoint_policies.nothing_saveable)
        h, _ = jax.lax.scan(outer, h, stacks2)
    else:
        h, _ = jax.lax.scan(body, h, stacks)
    h = _rmsnorm(h, params["ln_f"])
    logits = h @ params["lm_head"].astype(cfg.dtype)
    logits = _wsc(logits, P(_dp(dp_axes), None, "model"))
    return logits


def _mask_padded_vocab(cfg: LMConfig, logits: jax.Array) -> jax.Array:
    if cfg.padded_vocab == cfg.vocab:
        return logits
    valid = jnp.arange(cfg.padded_vocab) < cfg.vocab
    return jnp.where(valid, logits, -jnp.inf)


def lm_loss(cfg: LMConfig, params, batch, mesh=None) -> jax.Array:
    logits = forward(cfg, params, batch["tokens"], mesh)
    logits = _mask_padded_vocab(cfg, logits.astype(jnp.float32))
    targets = batch["targets"]
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, jnp.maximum(targets, 0)[..., None],
                               axis=-1)[..., 0]
    nll = logz - gold
    mask = (targets >= 0).astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def make_train_step(cfg: LMConfig, optimizer=None, mesh=None):
    """Returns train_step(params, opt_state, batch) -> (params, opt, loss).

    Accumulates over cfg.microbatches in fp32 (sequential lax.scan), so the
    global-batch interface holds while live activations stay bounded.
    """
    optimizer = optimizer or optim_lib.adamw(3e-4)

    def train_step(params, opt_state, batch):
        M = cfg.microbatches

        if M == 1:
            loss, grads = jax.value_and_grad(
                lambda p: lm_loss(cfg, p, batch, mesh))(params)
        else:
            def split(x):
                return x.reshape(M, x.shape[0] // M, *x.shape[1:])

            micro = jax.tree_util.tree_map(split, batch)

            def acc_body(carry, mb):
                loss_acc, g_acc = carry
                loss, g = jax.value_and_grad(
                    lambda p: lm_loss(cfg, p, mb, mesh))(params)
                g_acc = jax.tree_util.tree_map(
                    lambda a, b: (a.astype(jnp.float32)
                                  + b.astype(jnp.float32)
                                  ).astype(cfg.grad_accum_dtype), g_acc, g)
                return (loss_acc + loss, g_acc), None

            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, cfg.grad_accum_dtype), params)
            (loss, grads), _ = jax.lax.scan(acc_body, (0.0, zeros), micro)
            loss = loss / M
            grads = jax.tree_util.tree_map(lambda g: g / M, grads)

        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optim_lib.apply_updates(params, updates)
        return params, opt_state, loss

    return train_step


# ---------------------------------------------------------------------------
# Decode (serving)
# ---------------------------------------------------------------------------

def init_cache(cfg: LMConfig, batch: int, max_seq: Optional[int] = None,
               dtype=None):
    S = max_seq or cfg.max_seq
    dtype = dtype or cfg.dtype
    U, n_sub = cfg.n_units, cfg.layers_per_unit
    shape = (U, n_sub, batch, S, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def cache_specs(cfg: LMConfig, mesh, *, shard_seq: bool = True) -> Dict[str, P]:
    """Cache (U, sub, B, S, Hkv, Dh): batch over DP, seq over model."""
    dp_axes = _dp_axes(mesh)
    if shard_seq:
        spec = P(None, None, dp_axes, "model", None, None)
    else:
        spec = P(None, None, dp_axes, None, None, None)
    return {"k": spec, "v": spec}


def make_prefill_step(cfg: LMConfig, mesh=None, dp_axes=None):
    """prefill(params, tokens (B, S)) -> (last-token logits (B,1,V), cache).

    Runs the full layer stack once over the prompt, emitting the KV cache
    (stacked (U, sub, B, S, Hkv, Dh)) and only the final-position logits —
    the (B, S, V) logits tensor never materializes.
    """
    dp = _dp_axes(mesh) if dp_axes is None else dp_axes

    def prefill(params, tokens):
        B, S = tokens.shape
        h = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
        h = _wsc(h, P(_dp(dp), None, None))
        positions = jnp.arange(S)
        stacks = {k: v for k, v in params.items() if k in ("dense", "moe")}

        def body(h, unit_params):
            return _unit_body(cfg, mesh, dp, h, positions, unit_params,
                              collect_kv=True)

        if cfg.remat:
            body = jax.checkpoint(
                body, policy=jax.checkpoint_policies.nothing_saveable)
        h, cache = jax.lax.scan(body, h, stacks)
        h_last = _rmsnorm(h[:, -1:], params["ln_f"])
        logits = h_last @ params["lm_head"].astype(cfg.dtype)
        return _mask_padded_vocab(cfg, logits), cache

    return prefill


def make_decode_step(cfg: LMConfig, mesh=None, dp_axes=None):
    """decode_step(params, cache, tokens (B,1), index) -> (logits, cache)."""
    dp_axes = _dp_axes(mesh) if dp_axes is None else dp_axes

    def decode_step(params, cache, tokens, index):
        B = tokens.shape[0]
        h = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
        positions = jnp.full((B, 1), index, jnp.int32)

        stacks = {k: v for k, v in params.items() if k in ("dense", "moe")}

        def body(h, xs):
            unit_params, unit_cache = xs
            new_entries = []
            for sub in range(cfg.layers_per_unit):
                kind = ("dense" if (cfg.moe and cfg.moe_layer_step == 2
                                    and sub == 0) else
                        ("moe" if cfg.moe else "dense"))
                lp = unit_params[kind]
                entry = {"k": unit_cache["k"][sub], "v": unit_cache["v"][sub]}
                h, new_entry = _attention_block(
                    cfg, lp, h, positions, dp_axes,
                    cache=entry,
                    cache_index=index, mesh=mesh)
                if kind == "moe":
                    h = _moe_ffn(cfg, lp, h, mesh, dp_axes)
                else:
                    h = _dense_ffn(cfg, lp, h, dp_axes)
                new_entries.append(new_entry)
            new_unit_cache = {
                "k": jnp.stack([e["k"] for e in new_entries]),
                "v": jnp.stack([e["v"] for e in new_entries]),
            }
            return h, new_unit_cache

        h, new_cache = jax.lax.scan(body, h, (stacks, cache))
        h = _rmsnorm(h, params["ln_f"])
        logits = h @ params["lm_head"].astype(cfg.dtype)
        return _mask_padded_vocab(cfg, logits), new_cache

    return decode_step


def _dp_axes(mesh) -> tuple:
    if mesh is None:
        return ()
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
