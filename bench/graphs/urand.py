"""Uniform random graph, as the GAP suite's "Urand" input: ``2**scale``
vertices and ``edge_factor * 2**scale`` edge rows whose endpoints are
drawn uniformly and independently (Erdős–Rényi in the G(n, m) form, with
duplicates and self-loops left in).  O(m) time and memory."""
from __future__ import annotations

import numpy as np


def generate(p: dict, seed: int) -> list[tuple[np.ndarray, int]]:
    n = 1 << p["scale"]
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, n, size=(p["edge_factor"] * n, 2)), n)]
