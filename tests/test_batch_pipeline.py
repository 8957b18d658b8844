"""Batched pipeline: GraphBatch packing, budget grid, lane bit-parity
with the single-graph pipeline AND the dense seed reference (exact and
served/bounded plan modes, both backends), plan cache, serving layer."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import FIXTURES, nx_triangles, optional_hypothesis

given, settings, st = optional_hypothesis()

from repro.core.bfs import bfs_levels, bfs_levels_batch
from repro.core.sequential import (
    batch_plan_cache_stats,
    batch_plan_for,
    triangle_count,
    triangle_count_batch,
    triangle_count_dense,
)
from repro.graph import generators as gen
from repro.graph.csr import (
    BudgetGrid,
    ShapeBudget,
    from_edges,
    from_edges_batch,
    max_degree,
    to_batch,
)

BACKENDS = ("jnp", "pallas")


def _assert_lane_matches(res, i, single, dense):
    """Lane ``i`` of a batch result must bit-match the single-graph
    pipeline AND the dense seed reference on (triangles, c1, c2, k)."""
    for ref in (single, dense):
        assert int(res.triangles[i]) == int(ref.triangles)
        assert int(res.c1[i]) == int(ref.c1)
        assert int(res.c2[i]) == int(ref.c2)
        assert float(res.k[i]) == float(ref.k)
    assert int(res.num_horizontal[i]) == int(single.num_horizontal)
    assert not bool(res.h_overflow[i])


def _batch_and_refs(graphs, backend):
    gb = from_edges_batch(graphs)
    exact = triangle_count_batch(gb, intersect_backend=backend)
    plan = batch_plan_for(gb, intersect_backend=backend)
    served = triangle_count_batch(gb, plan=plan, intersect_backend=backend)
    return gb, exact, served


@pytest.mark.parametrize("backend", BACKENDS)
def test_batch_fixture_parity(backend):
    graphs = [FIXTURES["karate"], FIXTURES["complete9"], FIXTURES["er200"],
              (np.zeros((0, 2), np.int64), 0)]
    gb, exact, served = _batch_and_refs(graphs, backend)
    for i, (edges, n) in enumerate(graphs[:3]):
        g = from_edges(edges, n)
        single = triangle_count(g, intersect_backend=backend)
        dense = triangle_count_dense(g, d_max=max(1, max_degree(g)))
        _assert_lane_matches(exact, i, single, dense)
        _assert_lane_matches(served, i, single, dense)
        assert int(exact.triangles[i]) == nx_triangles(edges, n)
    # the empty padding lane is all-zero and keeps the CSR invariant
    # row_offsets[n+1] == num_slots like every real lane
    np.testing.assert_array_equal(
        np.asarray(gb.row_offsets[:, -1]),
        np.full(gb.batch_size, gb.slot_budget),
    )
    for res in (exact, served):
        assert int(res.triangles[3]) == 0
        assert int(res.num_horizontal[3]) == 0
        assert float(res.k[3]) == 0.0


@pytest.mark.slow
@settings(max_examples=12, deadline=None)
@given(
    st.lists(
        st.tuples(st.integers(2, 60), st.integers(0, 10 ** 6)),
        min_size=1, max_size=5,
    ),
    st.sampled_from(BACKENDS),
)
def test_batch_bitmatch_random_mixed_sizes(specs, backend):
    """Property (the PR's acceptance invariant): on random batches of
    mixed-size graphs, every lane of ``triangle_count_batch`` — exact
    AND served/bounded plan modes — bit-matches the per-graph pipeline
    and the dense reference on (triangles, c1, c2, k)."""
    graphs = []
    for n, seed in specs:
        rng = np.random.default_rng(seed)
        p = float(rng.uniform(0.03, 0.3))
        graphs.append(gen.erdos_renyi(n, p, seed=seed))
    _, exact, served = _batch_and_refs(graphs, backend)
    for i, (edges, n) in enumerate(graphs):
        g = from_edges(edges, n)
        single = triangle_count(g, intersect_backend=backend)
        dense = triangle_count_dense(g, d_max=max(1, max_degree(g)))
        _assert_lane_matches(exact, i, single, dense)
        _assert_lane_matches(served, i, single, dense)


def test_batch_lane_levels_match_single_bfs():
    graphs = [FIXTURES["karate"], FIXTURES["rmat8"]]
    gb = from_edges_batch(graphs)
    for ro in (None, gb.row_offsets):  # scatter sweep and CSR sweep
        levels = bfs_levels_batch(
            gb.src, gb.dst, gb.n_budget, root=0, row_offsets=ro
        )
        for i, (edges, n) in enumerate(graphs):
            g = from_edges(edges, n)
            want = np.asarray(bfs_levels(g.src, g.dst, n, root=0))
            np.testing.assert_array_equal(np.asarray(levels[i])[:n], want)


def test_bfs_csr_path_bit_identical():
    """The scatter-free CSR sweep must produce the exact level array the
    seed ``segment_max`` sweep does (it feeds bit-parity claims)."""
    for edges, n in (gen.rmat(8, 8, seed=3), gen.karate(),
                     gen.erdos_renyi(80, 0.04, seed=9)):
        g = from_edges(edges, n)
        a = np.asarray(bfs_levels(g.src, g.dst, n, root=0))
        b = np.asarray(
            bfs_levels(g.src, g.dst, n, root=0, row_offsets=g.row_offsets)
        )
        np.testing.assert_array_equal(a, b)


def test_budget_grid_is_geometric_and_monotone():
    grid = BudgetGrid(min_nodes=64, min_slots=256, factor=2.0)
    assert grid.budget_for(10, 5) == ShapeBudget(64, 256)
    assert grid.budget_for(64, 128) == ShapeBudget(64, 256)
    assert grid.budget_for(65, 129) == ShapeBudget(128, 512)
    cells = {grid.budget_for(n, 4 * n) for n in range(1, 3000)}
    assert len(cells) <= 8  # log-many cells over a 3000x size range
    for n in (1, 63, 64, 65, 1000):
        b = grid.budget_for(n, 4 * n)
        assert b.n_budget >= n and b.slot_budget >= 8 * n


def test_to_batch_roundtrip_wrapper():
    edges, n = gen.karate()
    g = from_edges(edges, n)
    gb = to_batch(g)
    assert gb.batch_size == 1 and gb.n_budget == n and gb.meta is None
    res = triangle_count_batch(gb)
    assert int(res.triangles[0]) == 45
    # and the public wrapper is exactly the squeezed lane
    single = triangle_count(g)
    assert int(single.triangles) == 45
    assert single.levels.shape == (n,)


def test_plan_cache_hits_and_meta_quantization():
    from repro.core import sequential

    # the cache is process-global: another test in this worker may have
    # planned the same batch profile already
    sequential._BATCH_PLAN_CACHE.clear()
    batch_plan_cache_stats(reset=True)
    before = batch_plan_cache_stats()["size"]
    graphs_a = [gen.erdos_renyi(50, 0.1, seed=1), gen.erdos_renyi(48, 0.1, seed=2)]
    graphs_b = [gen.erdos_renyi(47, 0.1, seed=3), gen.erdos_renyi(52, 0.1, seed=4)]
    gba = from_edges_batch(graphs_a)
    gbb = from_edges_batch(graphs_b)
    pa = batch_plan_for(gba, intersect_backend="jnp")
    if gba.meta == gbb.meta:  # same quantized profile -> cache hit
        s0 = batch_plan_cache_stats()
        pb = batch_plan_for(gbb, intersect_backend="jnp")
        s1 = batch_plan_cache_stats()
        assert s1["hits"] == s0["hits"] + 1
        assert pb is pa
    assert batch_plan_cache_stats()["size"] >= before + 1
    # batches without metadata must refuse the bounded path loudly
    with pytest.raises(ValueError):
        batch_plan_for(to_batch(from_edges(*gen.karate())))


def test_foreign_plan_undercoverage_is_flagged():
    """A reused plan that probes fewer rows than a lane's horizontal
    count must set h_overflow, never silently undercount."""
    path = np.stack([np.arange(15), np.arange(1, 16)], 1)
    sparse = from_edges_batch([(path, 16)])  # h_rows bound = 64
    dense = from_edges_batch([gen.complete(16)])  # n_h = C(15,2) = 105
    assert sparse.budget == dense.budget
    plan = batch_plan_for(sparse, intersect_backend="jnp")
    res = triangle_count_batch(dense, plan=plan, intersect_backend="jnp")
    assert bool(res.h_overflow[0])
    ok = triangle_count_batch(
        dense, plan=batch_plan_for(dense, intersect_backend="jnp"),
        intersect_backend="jnp",
    )
    assert not bool(ok.h_overflow[0])
    assert int(ok.triangles[0]) == 560  # C(16,3)


def test_batch_rejects_oversized_and_plan_kwarg_conflicts():
    edges, n = gen.karate()
    with pytest.raises(ValueError):
        from_edges_batch([(edges, n)], budget=ShapeBudget(16, 256))
    with pytest.raises(ValueError):
        from_edges_batch([(edges, n)], budget=ShapeBudget(64, 8))
    gb = from_edges_batch([(edges, n)])
    plan = batch_plan_for(gb)
    with pytest.raises(ValueError):
        triangle_count_batch(gb, plan=plan, cap_h=4)


def test_serving_layer_smoke():
    """End-to-end server: mixed stream, partial drain, results agree
    with the per-graph pipeline, latencies recorded."""
    from repro.launch.serve_tc import TriangleServer

    graphs = [gen.karate(), gen.complete(9), gen.erdos_renyi(40, 0.2, seed=7),
              gen.erdos_renyi(150, 0.05, seed=8), gen.complete(6)]
    server = TriangleServer(batch_size=2, intersect_backend="jnp")
    for e, n in graphs:
        server.submit(e, n)
    results = server.drain()
    assert len(results) == len(graphs)
    by_id = {r.request_id: r for r in results}
    for rid, (e, n) in enumerate(graphs):
        want = nx_triangles(e, n)
        assert by_id[rid].triangles == want
        assert by_id[rid].latency_s >= 0.0
        assert not by_id[rid].overflow
    assert server.batches_run >= 2
    assert server.summary()["requests"] == len(graphs)
    # malformed requests (aliasing / negative node ids): answered with a
    # structured rejection by default, raised only under strict=True
    for bad in (np.array([[0, 7]]), np.array([[-1, 3]])):
        s = TriangleServer()
        rid = s.submit(bad, 5)
        (res,) = s.drain()
        assert res.request_id == rid and res.route == "rejected"
        assert res.reason == "malformed"
        with pytest.raises(ValueError):
            TriangleServer(strict=True).submit(bad, 5)
