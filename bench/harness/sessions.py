"""Seeded Baidu-ULTR-shaped search sessions, with clicks from a DBN user.

A copy of the program's synthetic log generator (``repro.data.synthetic``,
behaviour ``dbn``), kept here so that later changes to the program cannot
change the benchmark's inputs. Queries follow a Zipf law; a logging ranker
orders each query's documents by noisy attractiveness and shows the top K;
clicks follow the dynamic Bayesian network (attraction, satisfaction,
continuation). Every chunk is a pure function of ``(seed, chunk index)``.
"""
from __future__ import annotations

from typing import Dict, Mapping

import numpy as np


class SessionSource:
    """Sessions of one traffic mix (``spec``) under one seed."""

    def __init__(self, spec: Mapping, seed: int):
        self.n_queries = int(spec["n_queries"])
        self.docs_per_query = int(spec["docs_per_query"])
        self.positions = int(spec["positions"])
        self.continuation = float(spec["continuation"])
        self.ranker_noise = float(spec["ranker_noise"])
        self.seed = int(seed)
        rng = np.random.default_rng((self.seed, 0))
        shape = (self.n_queries, self.docs_per_query)
        a, b = float(spec["attr_alpha"]), float(spec["attr_beta"])
        self.gamma = rng.beta(a, b, size=shape).astype(np.float32)
        self.sigma = rng.beta(a, b, size=shape).astype(np.float32)
        ranks = np.arange(1, self.n_queries + 1, dtype=np.float64)
        q = ranks ** (-float(spec["zipf_exponent"]))
        self.query_probs = q / q.sum()

    def chunk(self, index: int, rows: int) -> Dict[str, np.ndarray]:
        """``rows`` sessions: positions, query_doc_ids, clicks and mask."""
        rng = np.random.default_rng((self.seed, 1, int(index)))
        S, K = int(rows), self.positions
        queries = rng.choice(self.n_queries, size=S, p=self.query_probs)
        noise = rng.gumbel(scale=self.ranker_noise,
                           size=(S, self.docs_per_query)).astype(np.float32)
        scores = np.log(np.maximum(self.gamma[queries], 1e-6)) + noise
        top = np.argsort(-scores, axis=1)[:, :K]
        gamma = np.take_along_axis(self.gamma[queries], top, axis=1)
        sigma = np.take_along_axis(self.sigma[queries], top, axis=1)
        attracted = rng.random((S, K)) < gamma
        satisfied_draw = rng.random((S, K)) < sigma
        cont_draw = rng.random((S, K)) < self.continuation
        clicks = np.zeros((S, K), np.float32)
        examining = np.ones(S, bool)
        for k in range(K):
            click = examining & attracted[:, k]
            clicks[:, k] = click
            examining = examining & ~(click & satisfied_draw[:, k]) \
                & cont_draw[:, k]
        ids = queries[:, None] * self.docs_per_query + top
        return {
            "positions": np.broadcast_to(np.arange(1, K + 1, dtype=np.int32),
                                         (S, K)).copy(),
            "query_doc_ids": ids.astype(np.int32),
            "clicks": clicks,
            "mask": np.ones((S, K), bool),
        }
