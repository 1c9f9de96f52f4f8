"""Parameterizations: the "how" of each click-model variable (paper §4.2).

A parameterization maps a batch to per-item *logits*; the click model turns
logits into log-probabilities via the stable log-sigmoid (paper Eq. 17).
Decoupling structure from parameterization is the paper's flexibility story:
the same PBM can be a classic embedding-table model or a DeepCrossV2 two-tower.

Supported:
  * EmbeddingParameter — classic table, optional baseline correction,
    hashing-trick [Weinberger 2009] or quotient-remainder [Shi 2020]
    compression.
  * PositionParameter — rank-indexed table (θ_k).
  * UBMExaminationParameter — (rank, last-click-rank) table θ_{k,k'}.
  * ScalarParameter — single shared logit (GCTR ρ, CCM τ, DBN λ).
  * FeatureParameter — Linear / MLP / DeepCrossV2 towers over feature vectors.
"""
from __future__ import annotations

import dataclasses
import enum
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.nn import Dense, DeepCrossV2, MLP, init as initializers
from repro.nn.module import Module, split_rngs


# Compressed tables round up to a multiple of this so row-sharding over any
# production mesh axis (16 / 512) divides evenly. Harmless for hashing (the
# modulus just grows) and for QR (quotient table padding rows are unused).
SHARD_MULTIPLE = 512


def _round_up(n: int, multiple: int = SHARD_MULTIPLE) -> int:
    return -(-n // multiple) * multiple


class Compression(str, enum.Enum):
    NONE = "none"
    HASH = "hash"
    QR = "quotient_remainder"


class Combination(str, enum.Enum):
    STACKED = "stacked"
    PARALLEL = "parallel"


# ---------------------------------------------------------------------------
# Config dataclasses (mirror the paper's Listing 3/4 API).
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class EmbeddingParameterConfig:
    parameters: int
    use_feature: str = "query_doc_ids"
    compression: Compression = Compression.NONE
    compression_ratio: float = 1.0
    baseline_correction: bool = False
    features: int = 1  # output logits per item (1 for classic scalar models)
    init_logit: float = 0.0


@dataclasses.dataclass
class ScalarParameterConfig:
    init_prob: float = 0.5
    features: int = 1


@dataclasses.dataclass
class LinearParameterConfig:
    features: int
    use_feature: str = "query_doc_features"
    out_features: int = 1


@dataclasses.dataclass
class MLPParameterConfig:
    features: int
    hidden: Sequence[int] = (64, 64)
    use_feature: str = "query_doc_features"
    out_features: int = 1


@dataclasses.dataclass
class DeepCrossParameterConfig:
    features: int
    cross_layers: int = 2
    deep_layers: int = 2
    use_feature: str = "query_doc_features"
    combination: Combination = Combination.STACKED
    out_features: int = 1


# ---------------------------------------------------------------------------
# Integer hashing (multiply-xorshift, SplitMix64 finalizer) for the
# hashing-trick. Works on int32/int64 ids, vectorized, jit-safe.
# ---------------------------------------------------------------------------

def _splitmix(ids: jax.Array, salt: int = 0) -> jax.Array:
    """64-bit avalanche hash of integer ids (jnp, overflow wraps as intended)."""
    x = ids.astype(jnp.uint32)
    salt_arr = jnp.uint32(salt * 0x9E3779B9 + 0x85EBCA6B)
    x = x ^ salt_arr
    x = (x ^ (x >> 16)) * jnp.uint32(0x7FEB352D)
    x = (x ^ (x >> 15)) * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return x


def hash_ids(ids: jax.Array, table_size: int, salt: int = 0) -> jax.Array:
    return (_splitmix(ids, salt) % jnp.uint32(table_size)).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Parameter modules. Each returns logits of shape ids.shape (+ trailing
# features dim squeezed when features == 1).
# ---------------------------------------------------------------------------

class EmbeddingParameter(Module):
    """Table-based parameter with optional compression + baseline correction.

    Baseline correction (paper §4.2): a shared scalar is added to every row's
    logit; rows init at zero so unseen/rare ids start at the global baseline.
    """

    def __init__(self, config: EmbeddingParameterConfig, name: str = "embedding"):
        self.config = config
        self.name = name
        c = config
        self.features = c.features
        if c.compression == Compression.NONE:
            self.table_rows = c.parameters
        elif c.compression == Compression.HASH:
            self.table_rows = _round_up(
                max(int(c.parameters / max(c.compression_ratio, 1.0)), 2))
        elif c.compression == Compression.QR:
            # Two tables of ~sqrt-scaled sizes: remainder table of size m,
            # quotient table of ceil(N/m). Choose m so total rows shrink by
            # ~compression_ratio: m + N/m = 2N/ratio at m = N/ratio... we pick
            # m = max(parameters / ratio / 2, 2) and q_rows = ceil(N/m).
            m = _round_up(max(int(c.parameters / max(c.compression_ratio, 1.0) / 2), 2))
            self.rem_rows = m
            self.quot_rows = _round_up(int(-(-c.parameters // m)))  # ceil div
        else:
            raise ValueError(f"unknown compression {c.compression}")

    def init(self, rng):
        c = self.config
        k1, k2, k3 = jax.random.split(rng, 3)
        if c.baseline_correction:
            row_init = initializers.zeros
        else:
            row_init = initializers.constant(c.init_logit)
        params = {}
        if c.compression == Compression.QR:
            params["quotient"] = row_init(k1, (self.quot_rows, c.features), jnp.float32)
            params["remainder"] = initializers.ones(k2, (self.rem_rows, c.features), jnp.float32)
        else:
            params["table"] = row_init(k1, (self.table_rows, c.features), jnp.float32)
        if c.baseline_correction:
            params["baseline"] = jnp.full((c.features,), c.init_logit, jnp.float32)
        return params

    def row_ids(self, batch):
        """Table rows the batch gathers — the single home of this
        parameterization's index math (forward lookup and the sparse-optimizer
        row stream must agree row-for-row). QR has no single row-id table
        (each logical row is a product of two table rows)."""
        c = self.config
        ids = batch[c.use_feature]
        if c.compression == Compression.NONE:
            return jnp.clip(ids, 0, self.table_rows - 1)
        if c.compression == Compression.HASH:
            return hash_ids(ids, self.table_rows)
        raise NotImplementedError(
            "quotient-remainder compression has no single row-id stream")

    def __call__(self, params, batch):
        # The scope names the hash and gather in the trace; autodiff names
        # their backward (the gradient scatter) transpose(jvp(embed)).
        with jax.named_scope("embed"):
            return self._lookup(params, batch)

    def _lookup(self, params, batch):
        c = self.config
        ids = batch[c.use_feature]
        if c.compression in (Compression.NONE, Compression.HASH):
            logits = jnp.take(params["table"], self.row_ids(batch), axis=0)
        else:  # QR: element-wise product of quotient and remainder rows
            q = jnp.take(params["quotient"], (ids // self.rem_rows) % self.quot_rows, axis=0)
            r = jnp.take(params["remainder"], ids % self.rem_rows, axis=0)
            logits = q * r
        if c.baseline_correction:
            logits = logits + params["baseline"]
        if c.features == 1 and logits.ndim > ids.ndim:  # a (rows, 1) table
            logits = jnp.squeeze(logits, axis=-1)
        return logits

    def scalar_tables(self) -> Dict[str, int]:
        """``{leaf: rows}`` of the tables this part gathers single logits
        from (none when ``features > 1``)."""
        if self.features != 1:
            return {}
        if self.config.compression == Compression.QR:
            return {"quotient": self.quot_rows, "remainder": self.rem_rows}
        return {"table": self.table_rows}


def _path_names(path) -> List[str]:
    """The names along a pytree path; a key that joins names with ``/``
    (the sparse optimizer's ``"attraction/table"``) counts as each name."""
    out: List[str] = []
    for k in path:
        for attr in ("key", "name", "idx"):
            if hasattr(k, attr):
                out.extend(str(getattr(k, attr)).split("/"))
                break
    return out


def relayout_scalar_tables(model, tree, *, flat: bool, consume: bool = False):
    """``tree`` with each scalar table of ``model``'s embedding parts, and
    every leaf made from one under the same names (its gradient, optimizer
    moments, replica stacks), as ``(..., rows)`` when ``flat`` and as the
    ``(..., rows, 1)`` that :meth:`EmbeddingParameter.init` gives otherwise.

    The lookup reads either. Training keeps them flat: the TPU lays out a
    ``(rows, 1)`` array apart from the ``(rows,)`` that its gather and
    scatter-add work in, so a step on ``(rows, 1)`` tables copies each table
    into the gather's layout and its gradient out of the scatter's, in full.

    With ``consume``, each leaf that moves is made and waited for before its
    old buffer is deleted, one at a time, so that a state of table-sized
    leaves is never held twice; ``tree``'s arrays are then spent, as a
    donated argument's are.
    """
    tables = {(name, leaf): rows
              for name, part in (getattr(model, "parts", None) or {}).items()
              if isinstance(part, EmbeddingParameter)
              for leaf, rows in part.scalar_tables().items()}
    if not tables:
        return tree
    moved: Dict[int, object] = {}

    def move(path, x):
        if id(x) in moved:  # one array under two names
            return moved[id(x)]
        names = _path_names(path)
        rows = next((r for (part, leaf), r in tables.items()
                     if any(names[i:i + 2] == [part, leaf]
                            for i in range(len(names) - 1))), None)
        if rows is None:
            return x
        shape = tuple(np.shape(x))
        if flat and shape[-2:] == (rows, 1):
            new = x.reshape(shape[:-1])
        elif not flat and shape[-1:] == (rows,) and shape[-2:] != (rows, 1):
            new = x.reshape(shape + (1,))
        else:
            return x
        if consume and isinstance(x, jax.Array):
            new.block_until_ready()
            x.delete()
        moved[id(x)] = new
        return new

    return jax.tree_util.tree_map_with_path(move, tree)


class PositionParameter(Module):
    """Rank-indexed logit table θ_k. Positions in batches are 1-based."""

    def __init__(self, positions: int, init_logit: float = 0.0,
                 use_feature: str = "positions"):
        self.positions = positions
        self.init_logit = init_logit
        self.use_feature = use_feature

    def init(self, rng):
        del rng
        return {"table": jnp.full((self.positions,), self.init_logit, jnp.float32)}

    def gather(self, values, batch):
        """Index per-rank ``values`` (the logit table or any array derived
        from it row-for-row) by the batch's 1-based positions. The single
        home of this parameterization's index math — vectorized model paths
        that transform the table before gathering must use it too."""
        pos = batch[self.use_feature] - 1  # 1-based -> 0-based
        return jnp.take(values, jnp.clip(pos, 0, self.positions - 1), axis=0)

    def __call__(self, params, batch):
        return self.gather(params["table"], batch)


class UBMExaminationParameter(Module):
    """θ_{k,k'} table: examination at rank k given last click at rank k'.

    k' == 0 encodes "no previous click". Table shape (K, K): entry
    [k-1, k'] for k in 1..K, k' in 0..K-1 (k' < k always).
    """

    def __init__(self, positions: int, init_logit: float = 0.0):
        self.positions = positions
        self.init_logit = init_logit

    def init(self, rng):
        del rng
        return {"table": jnp.full((self.positions, self.positions), self.init_logit,
                                  jnp.float32)}

    def logit(self, params, k, k_prime):
        """k: 1-based rank array; k_prime: 0-based last-click rank (0=none)."""
        k_idx = jnp.clip(k - 1, 0, self.positions - 1)
        kp_idx = jnp.clip(k_prime, 0, self.positions - 1)
        return params["table"][k_idx, kp_idx]

    def __call__(self, params, batch):  # pragma: no cover - UBM calls .logit
        raise NotImplementedError("UBMExaminationParameter is indexed via .logit")


class ScalarParameter(Module):
    """Single shared logit, broadcast to the batch shape."""

    def __init__(self, config: ScalarParameterConfig = None, name: str = "scalar"):
        self.config = config or ScalarParameterConfig()
        self.name = name

    def init(self, rng):
        import math

        p = min(max(self.config.init_prob, 1e-6), 1 - 1e-6)
        v = math.log(p) - math.log1p(-p)
        return {"value": jnp.full((), v, jnp.float32)}

    def __call__(self, params, batch):
        ref = batch["positions"]
        return jnp.broadcast_to(params["value"], ref.shape)


class FeatureParameter(Module):
    """Feature-vector tower: Linear / MLP / DeepCrossV2 -> logit per item."""

    def __init__(self, config):
        self.config = config
        if isinstance(config, LinearParameterConfig):
            self.net = Dense(config.features, config.out_features)
        elif isinstance(config, MLPParameterConfig):
            self.net = MLP(config.features, list(config.hidden), config.out_features)
        elif isinstance(config, DeepCrossParameterConfig):
            self.net = DeepCrossV2(config.features, config.cross_layers,
                                   config.deep_layers,
                                   out_features=config.out_features,
                                   combination=config.combination.value)
        else:
            raise ValueError(f"unsupported feature config {config}")

    def init(self, rng):
        return self.net.init(rng)

    def __call__(self, params, batch):
        feats = batch[self.config.use_feature]
        logits = self.net(params, feats)
        if self.config.out_features == 1:
            logits = jnp.squeeze(logits, axis=-1)
        return logits


def build_parameter(config, positions: Optional[int] = None):
    """Factory: config dataclass -> parameter module."""
    if isinstance(config, EmbeddingParameterConfig):
        return EmbeddingParameter(config)
    if isinstance(config, ScalarParameterConfig):
        return ScalarParameter(config)
    if isinstance(config, (LinearParameterConfig, MLPParameterConfig,
                           DeepCrossParameterConfig)):
        return FeatureParameter(config)
    if isinstance(config, Module):
        return config
    raise ValueError(f"cannot build parameter from {config!r}")
