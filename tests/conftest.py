"""Shared fixtures. NOTE: no XLA_FLAGS manipulation here — smoke tests and
benches must see the real single CPU device; only the subprocess-based
multi-device tests request placeholder devices."""
from __future__ import annotations

import numpy as np
import pytest

from repro.graph import generators as gen
from repro.graph.csr import from_edges


def optional_hypothesis():
    """``(given, settings, st)`` — real hypothesis if installed, otherwise
    no-op stand-ins that mark the decorated property tests as skipped.

    Keeps every non-property test collectable on a clean environment
    (equivalent to a per-test ``pytest.importorskip("hypothesis")`` without
    skipping the whole module).  ``requirements-dev.txt`` installs the real
    thing for CI.
    """
    try:
        from hypothesis import given, settings, strategies as st

        return given, settings, st
    except ModuleNotFoundError:
        def given(*_a, **_k):
            return lambda f: pytest.mark.skip(
                reason="hypothesis not installed (see requirements-dev.txt)"
            )(f)

        def settings(*_a, **_k):
            return lambda f: f

        class _Strategies:  # strategy stubs; only evaluated at decoration time
            def __getattr__(self, _name):
                return lambda *_a, **_k: None

        return given, settings, _Strategies()


def nx_triangles(edges: np.ndarray, n: int) -> int:
    import networkx as nx

    G = nx.Graph()
    G.add_nodes_from(range(n))
    G.add_edges_from(np.asarray(edges))
    G.remove_edges_from(nx.selfloop_edges(G))
    return sum(nx.triangles(G).values()) // 3


def hub_graph(clique: int = 10, hubs=(600, 2500)) -> tuple[np.ndarray, int]:
    """A ``clique`` (the BFS root is vertex 0) with one hub per entry of
    ``hubs``, each joined to every clique vertex and to that many leaves
    of its own.  The horizontal edges of BFS level 1 are the clique's
    and the hubs' edges to it, so one candidate bucket holds larger
    degrees on both sides of 512 — the exact plan's target bands."""
    edges = [(i, j) for i in range(clique) for j in range(i + 1, clique)]
    nxt = clique
    for leaves in hubs:
        hub, nxt = nxt, nxt + 1
        edges += [(hub, i) for i in range(clique)]
        edges += [(hub, nxt + k) for k in range(leaves)]
        nxt += leaves
    return np.asarray(edges, dtype=np.int64), nxt


def random_geometric(n: int, radius: float, *, seed: int = 0):
    """Points in the unit cube joined under ``radius``: dense local
    clusters, many triangles, no hubs."""
    rng = np.random.default_rng(seed)
    pos = rng.random((n, 3))
    d2 = ((pos[:, None, :] - pos[None, :, :]) ** 2).sum(-1)
    i, j = np.nonzero(np.triu(d2 < radius * radius, k=1))
    return np.stack([i, j], axis=1).astype(np.int64), n


FIXTURES = {
    "karate": gen.karate(),
    "ring_of_cliques": gen.ring_of_cliques(5, 6),
    "er200": gen.erdos_renyi(200, 0.05, seed=3),
    "rmat8": gen.rmat(8, 8, seed=1),
    "complete9": gen.complete(9),
    "dolphins_like": gen.dolphins_like(),
    "geometric": random_geometric(80, 0.25, seed=2),
}


@pytest.fixture(params=sorted(FIXTURES))
def named_graph(request):
    edges, n = FIXTURES[request.param]
    return request.param, edges, n, from_edges(edges, n)


#: test sizes of the benchmark drivers that ``tests/bench``'s table of
#: sizes (``test_bench_control.SMALL``) does not list: the cells over a
#: mesh run there too, at this size, on the devices the process has
BENCH_DRIVER_SIZES = {"count_mesh": {"scale": 9}}


@pytest.fixture(autouse=True)
def _bench_driver_sizes(request):
    small = getattr(request.module, "SMALL", None)
    if isinstance(small, dict):
        for driver, size in BENCH_DRIVER_SIZES.items():
            small.setdefault(driver, size)
