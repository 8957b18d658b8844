"""The benchmark's SciPy reference equals a brute-force count."""
from __future__ import annotations

import itertools
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import reference  # noqa: E402


def brute(edges, n):
    adj = np.zeros((n, n), bool)
    for u, v in edges:
        if u != v:
            adj[u, v] = adj[v, u] = True
    return sum(adj[a, b] and adj[b, c] and adj[a, c]
               for a, b, c in itertools.combinations(range(n), 3))


@pytest.mark.parametrize("seed", range(6))
def test_reference_equals_brute_force(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(5, 30))
    edges = rng.integers(0, n, size=(int(rng.integers(0, 4 * n)), 2))
    assert reference.triangles(edges, n) == brute(edges, n)


def test_known_counts():
    k5 = np.array(list(itertools.combinations(range(5), 2)))
    assert reference.triangles(k5, 5) == 10
    assert reference.triangles(np.concatenate([k5, k5[:, ::-1], k5]), 5) == 10
    assert reference.triangles(np.zeros((0, 2), np.int64), 3) == 0
