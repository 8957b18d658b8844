"""Graph500 Kronecker (R-MAT) graph, as the GAP suite's "Kron" input.

R-MAT (Chakrabarti, Zhan, Faloutsos, SDM 2004) with the Graph500
quadrant probabilities a=.57, b=.19, c=.19 (d=.05): ``2**scale``
vertices and ``edge_factor * 2**scale`` edge rows, each endpoint built
bit by bit, then the vertex labels permuted to break degree locality.
Duplicates and self-loops are left in, as Graph500 leaves them.
"""
from __future__ import annotations

import numpy as np


def rmat(scale: int, edge_factor: int, a: float, b: float, c: float,
         seed: int) -> tuple[np.ndarray, int]:
    if min(a, b, c) < 0 or a + b + c > 1 + 1e-9:
        raise ValueError(f"R-MAT needs a, b, c >= 0 summing to <= 1; "
                         f"got {a}, {b}, {c}")
    n = 1 << scale
    m = edge_factor * n
    rng = np.random.default_rng(seed)
    src = np.zeros(m, np.int64)
    dst = np.zeros(m, np.int64)
    ab = a + b
    c_norm = c / (1.0 - ab) if ab < 1.0 else 0.0
    a_norm = a / ab if ab > 0.0 else 0.0
    for bit in range(scale):
        r1 = rng.random(m)
        r2 = rng.random(m)
        src_bit = r1 > ab
        dst_bit = np.where(src_bit, r2 > c_norm, r2 > a_norm)
        src |= src_bit.astype(np.int64) << bit
        dst |= dst_bit.astype(np.int64) << bit
    perm = rng.permutation(n)
    return np.stack([perm[src], perm[dst]], axis=1), n


def generate(p: dict, seed: int) -> list[tuple[np.ndarray, int]]:
    return [rmat(p["scale"], p["edge_factor"], p["a"], p["b"], p["c"], seed)]
