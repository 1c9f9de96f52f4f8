"""The reference's training steps, run after the program's on its inputs.

``follow`` takes a configuration's plain reference from its start through
the batches the program trained on, with plain AdamW, in float32 at the
highest matmul precision (or in the dtype asked for, for the control), and
returns what the comparison reads: each step's loss, the first gradient's
per-leaf norms, and the first moment's and the parameters' change's per-leaf
norms after ``read_at`` steps (all of them unless said).
"""
from __future__ import annotations

from typing import Dict, List, Mapping, Optional

import numpy as np


def norms(tree: Mapping) -> Dict[str, object]:
    import jax.numpy as jnp

    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


def follow(ref, cfg, batches: List[Mapping[str, np.ndarray]], dtype=None,
           rows: Optional[slice] = None,
           read_at: Optional[int] = None,
           reset_at: Optional[int] = None) -> Dict[str, object]:
    """``rows`` keeps only those rows of every batch (a fault: part of the
    batch left out, the mean taken over the rest); ``reset_at`` starts the
    optimizer afresh before that step (a fault: a resumed call that loses
    the moments and the step count it was handed)."""
    import jax
    import jax.numpy as jnp

    from harness import plain

    dtype = dtype or jnp.float32
    opt = cfg["optimizer"]

    def init():
        return ref.init(cfg, dtype)

    @jax.jit
    def start():
        p = init()
        return (p, {k: jnp.zeros_like(x) for k, x in p.items()},
                {k: jnp.zeros_like(x) for k, x in p.items()})

    def step(p, m, v, count, batch):
        loss, g = jax.value_and_grad(ref.loss)(p, batch, cfg)
        p, m, v = plain.adamw(p, g, m, v, count, opt)
        return p, m, v, loss.astype(jnp.float32), norms(g)

    step = jax.jit(step, donate_argnums=(0, 1, 2))

    @jax.jit
    def change(p):
        return norms({k: p[k] - p0 for k, p0 in init().items()})

    read_at = len(batches) if read_at is None else int(read_at)
    with jax.default_matmul_precision("highest"):
        p, m, v = start()
        losses, grad1, moved, mu = [], None, None, None
        first = 0
        for i, host in enumerate(batches):
            if i == reset_at:
                m = {k: jnp.zeros_like(x) for k, x in m.items()}
                v = {k: jnp.zeros_like(x) for k, x in v.items()}
                first = i
            batch = {k: (np.asarray(a)[rows] if rows is not None
                         else np.asarray(a)) for k, a in host.items()}
            p, m, v, loss, gn = step(p, m, v, jnp.float32(i + 1 - first),
                                     jax.device_put(batch))
            losses.append(float(loss))
            if grad1 is None:
                grad1 = {k: float(x) for k, x in gn.items()}
            if i + 1 == read_at:
                moved = {k: float(x) for k, x in change(p).items()}
                mu = {k: float(x) for k, x in norms(m).items()}
    del p, m, v
    return {"losses": losses, "grad1": grad1, "mu": mu, "step": moved}
