"""Graph generators, one module per family, found by the name that a
configuration's ``"generator"`` gives.  Each module has
``generate(params, seed) -> list[(edges int64[m, 2], n)]``.

A configuration fixes its graphs with its own ``graph_seed``, as the
GAP suite fixes its generator's seed.  The run's ``--seed`` then draws
only :func:`relabel`: a new vertex numbering, edge order and edge
direction.  Every seed thus carries the same work in another order,
and the reference is recomputed on the relabelled graph.
"""
from __future__ import annotations

import importlib

import numpy as np


def load(name: str):
    """The generator module ``bench/graphs/<name>.py``."""
    return importlib.import_module(f"{__name__}.{name}")


def relabel(edges, n: int, rng) -> np.ndarray:
    """``edges`` under a random permutation of the vertex ids that keeps
    vertex 0 (the engine's BFS root) in place, in a random row order,
    with a random half of the rows reversed."""
    perm = np.concatenate([[0], 1 + rng.permutation(max(n - 1, 0))])[:n]
    out = perm[np.asarray(edges, np.int64)]
    out = out[rng.permutation(len(out))]
    flip = rng.random(len(out)) < 0.5
    out[flip] = out[flip][:, ::-1]
    return out
