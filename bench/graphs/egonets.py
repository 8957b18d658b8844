"""Collaboration ego-networks sized as the TUDataset COLLAB graphs.

COLLAB (Yanardag & Vishwanathan, KDD 2015; TUDataset, Morris et al.,
arXiv:2007.08663) holds 5,000 ego-networks of researchers: the ego, its
co-authors, and the co-author links among them; 74.49 vertices and
2,457.78 edges on average, 32 to 492 vertices.  The dataset is not in
this repository, so each graph is made from a model with those means:

* vertex counts are the ``count`` evenly spaced quantiles of
  ``lo + floor(L)``, ``L`` lognormal(``mu``, ``sigma``), capped at
  ``hi`` (the same set of sizes for every seed);
* the ego (vertex 0) links to every alter; the alters are split into
  ``round((n - 1) / group)`` research groups, each a clique (papers
  with many co-authors), and each alter joins one more random group with
  probability ``overlap``.
"""
from __future__ import annotations

from statistics import NormalDist

import numpy as np


def sizes(p: dict) -> np.ndarray:
    """The vertex count of every graph, ascending."""
    q = (np.arange(p["count"]) + 0.5) / p["count"]
    z = np.array([NormalDist().inv_cdf(x) for x in q])
    lengths = np.floor(np.exp(p["mu"] + p["sigma"] * z))
    return np.minimum(p["hi"], p["lo"] + lengths).astype(np.int64)


def egonet(n: int, group: float, overlap: float, rng) -> np.ndarray:
    """One ego-network on ``n`` vertices as unique edges ``int64[m, 2]``."""
    alters = rng.permutation(np.arange(1, n))
    k = max(1, int(round((n - 1) / group)))
    groups = [list(g) for g in np.array_split(alters, k)]
    if k > 1:
        for v in alters:
            if rng.random() < overlap:
                groups[int(rng.integers(k))].append(v)
    adj = np.zeros((n, n), bool)
    adj[0, 1:] = True
    for g in groups:
        g = np.unique(g)
        adj[np.ix_(g, g)] = True
    i, j = np.nonzero(np.triu(adj | adj.T, 1))
    return np.stack([i, j], axis=1).astype(np.int64)


def generate(p: dict, seed: int) -> list[tuple[np.ndarray, int]]:
    rng = np.random.default_rng(seed)
    return [(egonet(int(n), p["group"], p["overlap"], rng), int(n))
            for n in sizes(p)]
