"""Graph container, partitioner, binary search."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest
from conftest import optional_hypothesis

given, settings, st = optional_hypothesis()

from repro.graph import generators as gen
from repro.graph.csr import bounded_binary_search, from_edges, max_degree
from repro.graph.partition import shard_edges, vertex_partition


def test_csr_roundtrip_karate():
    edges, n = gen.karate()
    g = from_edges(edges, n)
    assert g.n_nodes == 34
    assert int(g.n_edges_dir) == 2 * 78
    assert int(jnp.sum(g.deg)) == 2 * 78
    # CSR slices are sorted and match adjacency
    src, dst = np.asarray(g.src), np.asarray(g.dst)
    row = np.asarray(g.row_offsets)
    adj = {i: set() for i in range(n)}
    for a, b in edges:
        adj[a].add(b), adj[b].add(a)
    for v in range(n):
        sl = dst[row[v]: row[v + 1]]
        assert list(sl) == sorted(adj[v])
        assert (src[row[v]: row[v + 1]] == v).all()


def test_padding_and_dedup():
    edges = np.array([[0, 1], [1, 0], [0, 1], [2, 2], [1, 2]])
    g = from_edges(edges, 3, num_slots=16)
    assert g.num_slots == 16
    assert int(g.n_edges_dir) == 4  # {0-1, 1-2} symmetrized
    assert int(jnp.sum(g.src == 3)) == 12  # sentinel padding


def test_empty_graph_n_zero():
    """n_nodes=0 used to crash on the packed-key division; it must build
    a consistent (if degenerate) container."""
    for edges in (np.zeros((0, 2), np.int64), np.array([[0, 0]])):
        g = from_edges(edges, 0)
        assert g.n_nodes == 0
        assert g.num_slots == 0
        assert int(g.n_edges_dir) == 0
        assert g.deg.shape == (0,)
        assert np.asarray(g.row_offsets).tolist() == [0, 0]
    g = from_edges(np.zeros((0, 2)), 0, num_slots=8)
    assert g.num_slots == 8
    assert int(jnp.sum(g.src == 0)) == 8  # sentinel == n_nodes == 0


def test_zero_edge_graph_counts_zero():
    """Vertices but no edges (also: self-loops only) — the whole
    pipeline must run and count zero."""
    from repro.core.sequential import triangle_count

    for edges in (np.zeros((0, 2), np.int64),
                  np.array([[1, 1], [3, 3]])):
        g = from_edges(edges, 5)
        assert int(g.n_edges_dir) == 0
        assert int(jnp.sum(g.deg)) == 0
        res = triangle_count(g)
        assert int(res.triangles) == 0
        assert int(res.num_horizontal) == 0
        assert float(res.k) == 0.0
        assert not bool(res.h_overflow)


@settings(max_examples=25, deadline=None)
@given(st.lists(st.integers(0, 49), min_size=0, max_size=60), st.integers(0, 60))
def test_bounded_binary_search_matches_numpy(vals, q):
    arr = np.sort(np.asarray(vals + [10 ** 6], dtype=np.int32))  # non-empty
    found = bool(
        bounded_binary_search(
            jnp.asarray(arr),
            jnp.asarray([0]),
            jnp.asarray([len(vals)]),
            jnp.asarray([q]),
            num_steps=8,
        )[0]
    )
    assert found == (q in vals)


def test_vertex_partition_balance():
    edges, n = gen.rmat(9, 8, seed=0)
    g = from_edges(edges, n)
    for p in (2, 4, 8):
        bounds = vertex_partition(np.asarray(g.row_offsets), p)
        assert bounds[0] == 0 and bounds[-1] == n
        row = np.asarray(g.row_offsets)
        sizes = row[bounds[1:]] - row[bounds[:-1]]
        m2 = int(g.n_edges_dir)
        assert sizes.sum() == m2
        assert sizes.max() <= 2 * m2 / p + max_degree(g)  # paper's ~2m/p


def test_shard_edges_covers_all_edges():
    edges, n = gen.erdos_renyi(100, 0.08, seed=5)
    g = from_edges(edges, n)
    s_sh, d_sh, counts, _ = shard_edges(g, 4)
    got = set()
    for i in range(4):
        for j in range(int(counts[i])):
            got.add((int(s_sh[i, j]), int(d_sh[i, j])))
    want = set(zip(np.asarray(g.src)[: int(g.n_edges_dir)],
                   np.asarray(g.dst)[: int(g.n_edges_dir)]))
    assert got == want


def test_rmat_rejects_invalid_probabilities():
    """Regression: a=0.9, b=0.3, c=0.3 (sum 1.5) used to silently
    generate a graph from a nonsense distribution (c_norm > 1)."""
    with pytest.raises(ValueError, match="rmat probabilities"):
        gen.rmat(5, 4, a=0.9, b=0.3, c=0.3)
    for bad in (dict(a=-0.1), dict(b=-0.2), dict(c=1.01),
                dict(a=0.5, b=0.5, c=0.1)):
        with pytest.raises(ValueError):
            gen.rmat(5, 4, **bad)
    # the Graph500 defaults and valid corners still generate
    edges, n = gen.rmat(5, 4, seed=1)
    assert n == 32 and edges.shape == (128, 2)
    for corner in (dict(a=1.0, b=0.0, c=0.0), dict(a=0.0, b=0.0, c=0.0),
                   dict(a=0.0, b=0.0, c=1.0)):
        edges, n = gen.rmat(4, 2, **corner)
        assert edges.shape == (32, 2) and edges.max() < n


def _assert_roundtrip_and_parity(edges: np.ndarray, n: int) -> None:
    """One case of the from_edges/to_batch round-trip property: the B=1
    batch view must reproduce the packed graph exactly, and the engine
    must agree bit-for-bit with the legacy shims on it."""
    import warnings

    from repro.api import TriangleEngine
    from repro.core.sequential import triangle_count
    from repro.graph.csr import to_batch

    g = from_edges(edges, n)
    gb = to_batch(g)
    # ---- structural round trip: the lane IS the graph -----------------
    lane = gb.lane_view()
    assert gb.batch_size == 1 and gb.n_budget == g.n_nodes
    for field in ("src", "dst", "row_offsets", "deg"):
        np.testing.assert_array_equal(
            np.asarray(getattr(lane, field))[0], np.asarray(getattr(g, field))
        )
    assert int(gb.n_nodes[0]) == g.n_nodes
    assert int(gb.n_edges_dir[0]) == int(g.n_edges_dir)
    # re-packing the round-tripped edge list is idempotent
    src, dst = np.asarray(g.src), np.asarray(g.dst)
    keep = (src < dst) & (dst < n)
    g2 = from_edges(np.stack([src[keep], dst[keep]], axis=1), n)
    np.testing.assert_array_equal(np.asarray(g2.src), src)
    np.testing.assert_array_equal(np.asarray(g2.dst), dst)
    # ---- engine vs shims, bit for bit ---------------------------------
    engine = TriangleEngine()
    rep = engine.count(g, route="local")
    if n > 0:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            legacy = triangle_count(g)
        assert rep.triangles == int(legacy.triangles)
        assert (rep.c1, rep.c2) == (int(legacy.c1), int(legacy.c2))
        assert rep.k == float(legacy.k)
        assert rep.num_horizontal == int(legacy.num_horizontal)
        assert rep.overflow.h == bool(legacy.h_overflow)
    # the batch route answers the same graph identically (budget padding
    # cannot change counts)
    rep_b = engine.count((edges, n), route="batch")
    assert (rep_b.triangles, rep_b.c1, rep_b.c2) == (
        rep.triangles, rep.c1, rep.c2)
    assert rep_b.k == rep.k


def test_roundtrip_explicit_degenerates():
    """Empty graphs, self-loop-only graphs and duplicate edges — the
    packer must normalize them all onto one canonical CSR and every
    route of the engine must agree with the shims on each."""
    cases = [
        (np.zeros((0, 2), np.int64), 0),          # truly empty
        (np.zeros((0, 2), np.int64), 7),          # vertices, no edges
        (np.array([[2, 2], [4, 4]]), 6),          # self-loops only
        (np.array([[0, 1]] * 5), 3),              # one edge, duplicated
        (np.array([[0, 1], [1, 0], [1, 2], [2, 0], [0, 0], [2, 1]]), 3),
        (np.array([[5, 1], [1, 5], [5, 5], [1, 1]]), 8),  # loops + dupes
    ]
    for edges, n in cases:
        _assert_roundtrip_and_parity(np.asarray(edges, np.int64), n)


@settings(max_examples=25, deadline=None)
@given(
    st.integers(1, 32),
    st.lists(st.tuples(st.integers(0, 31), st.integers(0, 31)),
             min_size=0, max_size=120),
    st.integers(0, 3),
)
def test_roundtrip_property_random_multigraphs(n, pairs, dup):
    """Property form: arbitrary edge lists with self-loops and
    duplicates (each list repeated ``dup`` extra times) round-trip and
    count identically through the engine and the shims."""
    pairs = [(a % n, b % n) for a, b in pairs]
    edges = np.asarray(pairs * (dup + 1), np.int64).reshape(-1, 2)
    _assert_roundtrip_and_parity(edges, n)


def test_budget_grid_top_cell():
    """A capped grid routes: cells at/below the cap fit, anything whose
    rounded cell exceeds it raises from budget_for but answers fits()."""
    from repro.graph.csr import BudgetGrid

    grid = BudgetGrid(max_nodes=256, max_slots=1024)
    assert grid.fits(256, 512)
    assert grid.budget_for(200, 300).n_budget == 256
    assert not grid.fits(257, 10)     # node cell would round to 512
    assert not grid.fits(10, 513)     # slot cell would round to 2048
    with pytest.raises(ValueError, match="top cell"):
        grid.budget_for(257, 10)
    unbounded = BudgetGrid()
    assert unbounded.fits(1 << 20, 1 << 22)
