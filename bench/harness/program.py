"""The system under test, built from a configuration file.

Only the program's public surface is used: ``MODEL_REGISTRY`` with the
configuration's tables, ``optim.adamw``, and the trees the program trains.
"""
from __future__ import annotations

from typing import Dict


def build_model(cfg):
    from repro.core import (MODEL_REGISTRY, Compression,
                            EmbeddingParameterConfig)

    tables = {name: EmbeddingParameterConfig(
        parameters=int(t["parameters"]),
        compression=Compression(t["compression"]),
        compression_ratio=float(t["compression_ratio"]),
        baseline_correction=bool(t["baseline_correction"]),
        init_logit=float(t["init_logit"]))
        for name, t in cfg["tables"].items()}
    return MODEL_REGISTRY[cfg["model"]](**cfg["model_kwargs"], **tables)


def optimizer(cfg):
    from repro import optim

    o = cfg["optimizer"]
    if o["name"] != "adamw":
        raise ValueError(f"no optimizer {o['name']!r} in the harness")
    return optim.adamw(o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                       weight_decay=o["weight_decay"])


def _key(k) -> str:
    for attr in ("key", "name", "idx"):
        if hasattr(k, attr):
            return str(getattr(k, attr))
    return str(k)


def leaves(tree) -> Dict[str, object]:
    """``{"attraction/table": leaf, ...}`` for a nested tree."""
    import jax

    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {"/".join(_key(k) for k in path): leaf for path, leaf in flat}


def nest(flat: Dict[str, object]) -> Dict:
    """The inverse of :func:`leaves` for trees of dicts."""
    out: Dict = {}
    for path, leaf in flat.items():
        node = out
        *heads, last = path.split("/")
        for h in heads:
            node = node.setdefault(h, {})
        node[last] = leaf
    return out


def moments(opt_state, name: str, paths) -> Dict[str, object]:
    """The optimizer-state leaves under a field called ``name`` (``mu``,
    ``nu``), keyed like the parameters they belong to."""
    out = {}
    for path, leaf in leaves(opt_state).items():
        parts = path.split("/")
        if name in parts:
            sub = "/".join(parts[parts.index(name) + 1:])
            if sub in paths:
                out[sub] = leaf
    if set(out) != set(paths):
        raise KeyError(f"optimizer state has no {name!r} for "
                       f"{sorted(set(paths) - set(out))}")
    return out
