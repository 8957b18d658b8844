"""The plain reference: a graph's triangle count by sparse matrix
products, independent of the code under test (it imports nothing from
``src/``).

Vertices are ranked by degree and every edge points from the lower rank
to the higher (``U = triu`` of the ranked adjacency), which keeps the
products small around hubs; ``(U @ U) * U`` then counts each triangle
once, at its (lowest, highest) corner.  Self-loops are dropped and
duplicate or reversed edges merged, as a simple undirected graph has
them.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp


def triangles(edges, n: int) -> int:
    """Triangles of the simple undirected graph on ``edges`` (int[m, 2])
    over ``n`` vertices."""
    e = np.asarray(edges, np.int64).reshape(-1, 2)
    e = e[e[:, 0] != e[:, 1]]
    a = sp.coo_matrix(
        (np.ones(len(e), np.int64), (e[:, 0], e[:, 1])), shape=(n, n)
    ).tocsr()
    a = ((a + a.T) > 0).astype(np.int64)
    order = np.argsort(np.asarray(a.sum(axis=1)).ravel(), kind="stable")
    u = sp.triu(a[order][:, order], k=1, format="csr")
    return int((u @ u).multiply(u).sum())
