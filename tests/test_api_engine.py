"""The public front door (repro.api): TriangleEngine routing parity,
the unified TriangleReport contract, TCOptions validation and cache-key
semantics, the legacy deprecation shims, and the §V-B wedge-baseline
cross-check the cover-edge counts previously had no test against."""
from __future__ import annotations

import warnings

import numpy as np
import pytest

from repro.api import (
    ROUTES,
    Overflow,
    TCOptions,
    TriangleEngine,
    default_engine,
)
from repro.graph import generators as gen
from repro.graph.csr import BudgetGrid, from_edges, max_degree

from conftest import nx_triangles
from tests.test_parallel_tc import run_multidevice


def _fixtures():
    return {
        "karate": gen.karate(),
        "path17": gen.path(17),
        "star16": gen.star(16),
        "complete9": gen.complete(9),
        "er": gen.erdos_renyi(80, 0.08, seed=5),
        "rmat8": gen.rmat(8, 8, seed=1),
    }


# --------------------------------------------------------- route parity


def test_routes_bit_identical_and_match_networkx():
    """local / batch / distributed (p=1 in-process) must agree with each
    other, with the legacy entry points, and with networkx — triangles
    and k bit-for-bit (the acceptance criterion)."""
    from repro.core.sequential import triangle_count

    engine = TriangleEngine()
    for name, (edges, n) in _fixtures().items():
        g = from_edges(edges, n)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            legacy = triangle_count(g)
        want = nx_triangles(edges, n)
        reports = {
            "local": engine.count(g, route="local"),
            "batch": engine.count((edges, n), route="batch"),
            "distributed": engine.count(g, route="distributed"),
        }
        for route, rep in reports.items():
            assert rep.triangles == want, (name, route)
            assert rep.triangles == int(legacy.triangles), (name, route)
            assert rep.k == float(legacy.k), (name, route)
            assert rep.route == route
            assert not rep.overflow.any, (name, route)
            assert rep.options is not None and rep.plan_id
        # the apex-level split exists exactly off the distributed route
        for route in ("local", "batch"):
            rep = reports[route]
            assert rep.c1 == int(legacy.c1) and rep.c2 == int(legacy.c2)
            assert rep.levels is not None and rep.comm is None
        dist = reports["distributed"]
        assert dist.c1 is None and dist.c2 is None
        assert dist.comm is not None and dist.per_device is not None


def test_routes_agree_across_backends():
    """jnp and (interpreted) pallas answer every route identically."""
    engine = TriangleEngine()
    for edges, n in (gen.karate(), gen.rmat(7, 8, seed=3)):
        g = from_edges(edges, n)
        base = engine.count(g, route="local")
        for route in ("local", "batch"):
            rep = engine.count(
                (edges, n), route=route,
                options=TCOptions(backend="pallas", interpret=True),
            )
            assert rep.backend == "pallas"
            assert (rep.triangles, rep.c1, rep.c2, rep.k) == (
                base.triangles, base.c1, base.c2, base.k), route


def test_count_batch_matches_per_graph_counts():
    engine = TriangleEngine()
    graphs = [gen.karate(), gen.complete(9), gen.path(17),
              gen.erdos_renyi(60, 0.1, seed=2)]
    reports = engine.count_batch(graphs)
    assert len(reports) == len(graphs)
    for (edges, n), rep in zip(graphs, reports):
        solo = engine.count(from_edges(edges, n), route="local")
        assert (rep.triangles, rep.c1, rep.c2) == (
            solo.triangles, solo.c1, solo.c2)
        assert rep.k == solo.k
        assert rep.route == "batch"


def test_auto_route_policy_is_the_grid_top_cell():
    engine = TriangleEngine(budgets=BudgetGrid(max_nodes=128,
                                               max_slots=1024))
    assert engine.route_for(34, 78) == "local"
    assert engine.route_for(512, 4000) == "distributed"
    # explicit route overrides the policy
    assert engine.route_for(512, 4000, route="distributed") == "distributed"
    assert engine.route_for(34, 78, route="batch") == "batch"
    with pytest.raises(ValueError):
        engine.route_for(34, 78, route="bogus")
    # auto on an over-budget graph actually answers distributed
    edges, n = gen.rmat(9, 8, seed=7)
    rep = engine.count((edges, n))
    assert rep.route == "distributed" and rep.c1 is None
    assert rep.triangles == nx_triangles(edges, n)


def test_mixed_stream_serves_unified_contract():
    """Regression (the c1/c2 = -1 sentinel leak): a mixed local /
    distributed stream through the engine's server answers every request
    with the unified contract — batched lanes carry the split,
    distributed responses carry None + the full report, and counts are
    bit-identical to the local route per request."""
    engine = TriangleEngine(budgets=BudgetGrid(max_nodes=256,
                                               max_slots=2048))
    server = engine.serve(batch_size=4)
    reqs = [gen.karate(), gen.complete(9), gen.rmat(9, 8, seed=7),
            gen.erdos_renyi(60, 0.1, seed=2), gen.path(17),
            gen.rmat(9, 4, seed=8)]
    want = [engine.count(from_edges(e, n), route="local").triangles
            for e, n in reqs]
    for e, n in reqs:
        server.submit(e, n)
    res = {r.request_id: r for r in server.drain()}
    assert len(res) == len(reqs)
    for i in range(len(reqs)):
        assert res[i].triangles == want[i], i
        assert not res[i].overflow, i
    for i in (2, 5):  # the over-budget rmat9 requests
        assert res[i].route == "distributed"
        assert res[i].c1 is None and res[i].c2 is None
        assert res[i].report is not None
        assert res[i].report.route == "distributed"
        assert res[i].report.comm is not None
    for i in (0, 1, 3, 4):
        assert res[i].route == "batched"
        assert res[i].c1 is not None and res[i].c2 is not None
    assert server.summary()["distributed_requests"] == 2


def test_server_serves_over_budget_even_with_local_default_route():
    """Regression: the server's dispatch is size policy, not the
    engine's default route — an engine configured route='local' must
    still answer over-budget requests distributed, not crash on
    budget_for."""
    engine = TriangleEngine(
        TCOptions(route="local"),
        budgets=BudgetGrid(max_nodes=128, max_slots=1024),
    )
    server = engine.serve(batch_size=2)
    edges, n = gen.rmat(9, 8, seed=7)  # over the 128-node top cell
    server.submit(edges, n)
    server.submit(*gen.karate())
    res = {r.request_id: r for r in server.drain()}
    assert res[0].route == "distributed" and res[0].c1 is None
    assert res[0].triangles == nx_triangles(edges, n)
    assert res[1].route == "batched" and res[1].triangles == 45


def test_auto_route_uses_true_edge_count_not_slot_padding():
    """Regression: a small graph packed with a fat num_slots budget must
    still route local — slot padding is not graph size."""
    engine = TriangleEngine(budgets=BudgetGrid(max_nodes=128,
                                               max_slots=1024))
    edges, n = gen.karate()
    g = from_edges(edges, n, num_slots=4096)  # padded past the top cell
    rep = engine.count(g)
    assert rep.route == "local" and rep.triangles == 45


def test_empty_graph_honors_requested_route_contract():
    """Regression: the n=0 facade answer must echo the resolved route
    and its c1/c2 contract, not always claim 'local'."""
    empty = (np.zeros((0, 2), np.int64), 0)
    engine = TriangleEngine()
    loc = engine.count(empty)
    assert loc.route == "local" and (loc.c1, loc.c2) == (0, 0)
    dist = engine.count(empty, route="distributed")
    assert dist.route == "distributed"
    assert dist.c1 is None and dist.c2 is None
    assert dist.triangles == 0 and not dist.overflow.any
    bat = engine.count(empty, route="batch")
    assert bat.route == "batch" and bat.triangles == 0
    with pytest.raises(ValueError, match="batch"):
        engine.count(empty, route="batch",
                     options=TCOptions(cap_h=4))


# ------------------------------------------------- §V-B baseline parity


def test_wedge_baseline_agrees_with_engine():
    """The paper's §V-B prior-art baseline (open-wedge generation) must
    agree with the cover-edge engine on rmat and on the degenerate
    path/star fixtures (k = 0 and k -> 1 extremes)."""
    from repro.core.wedge_baseline import wedge_triangle_count

    engine = TriangleEngine()
    for name, (edges, n) in {
        "rmat8": gen.rmat(8, 8, seed=1),
        "rmat7": gen.rmat(7, 16, seed=3),
        "path17": gen.path(17),
        "star16": gen.star(16),
    }.items():
        g = from_edges(edges, n)
        rep = engine.count(g, route="local")
        wedge = int(wedge_triangle_count(g, d_max=max(1, max_degree(g))))
        assert wedge == rep.triangles == nx_triangles(edges, n), name


def test_parallel_wedge_baseline_agrees_with_engine():
    """Same cross-check against the distributed wedge-router (shard_map
    over the in-process device set)."""
    import jax
    from jax.sharding import Mesh

    from repro.core.wedge_baseline import parallel_wedge_triangle_count

    engine = TriangleEngine()
    devs = np.array(jax.devices())
    mesh = Mesh(devs.reshape(devs.size), ("p",))
    for name, (edges, n) in {
        "rmat7": gen.rmat(7, 8, seed=2),
        "path17": gen.path(17),
        "star16": gen.star(16),
    }.items():
        g = from_edges(edges, n)
        wres = parallel_wedge_triangle_count(g, mesh)
        assert not bool(wres.overflow), name
        assert int(wres.triangles) == engine.count(g).triangles, name


# ------------------------------------------------------------- TCOptions


BAD_OPTIONS = [
    (dict(backend="cuda"), ValueError),
    (dict(route="remote"), ValueError),
    (dict(mode="broadcast"), ValueError),
    (dict(frontier_dtype="int64"), ValueError),
    (dict(query_chunk=0), ValueError),
    (dict(d_max=-1), ValueError),
    (dict(cap_h=0), ValueError),
    (dict(d_pad=0), ValueError),
    (dict(hedge_chunk=-1), ValueError),
    (dict(bucket_widths=(32, 0)), ValueError),
    (dict(row_mult=0), ValueError),
    (dict(slack=0.0), ValueError),
    (dict(gather_buffer_limit_bytes=0), ValueError),
    (dict(deadline_s=0.0), ValueError),
    (dict(admission_tokens=0), ValueError),
    (dict(approx_samples=0), ValueError),
    (dict(distributed_timeout_s=-1.0), ValueError),
    (dict(grid="x"), TypeError),
]


@pytest.mark.parametrize(
    "bad,error", BAD_OPTIONS,
    ids=[",".join(f"{k}={v}" for k, v in bad.items())
         for bad, _ in BAD_OPTIONS],
)
def test_tcoptions_validates_in_one_place(bad, error):
    with pytest.raises(error):
        TCOptions(**bad)


def test_tcoptions_normalizes_widths_and_hashes():
    # widths coerced to an int tuple, options hashable
    o = TCOptions(bucket_widths=[np.int64(32), 256])
    assert o.bucket_widths == (32, 256)
    assert hash(o) == hash(TCOptions(bucket_widths=(32, 256)))
    assert "auto" in ROUTES and "approx" in ROUTES
    assert "stream" in ROUTES and len(ROUTES) == 6


def test_plan_view_is_the_plan_cache_key():
    """Options differing only in plan-irrelevant knobs must collide on
    one cache entry; plan-relevant knobs must split it."""
    base = TCOptions()
    same = TCOptions(root=3, mode="ring", slack=8.0, route="batch")
    other = TCOptions(bucket_widths=(8, 64))
    assert base.plan_view() == same.plan_view()
    assert base.plan_view() != other.plan_view()
    # chunking folds into the row quantization exactly once
    assert TCOptions(query_chunk=128).plan_view().row_mult == 128
    engine = TriangleEngine()
    from repro.graph.csr import from_edges_batch

    gb = from_edges_batch([gen.karate(), gen.complete(9)])
    p1 = engine.plan_for(gb)
    p2 = _plan_for_with(engine, gb, same)
    assert p1 is p2, "plan-irrelevant knobs must hit the same cache entry"
    stats = engine.plan_cache_stats()
    assert stats["size"] == 1 and stats["hits"] == 1


def _plan_for_with(engine, gb, options):
    from repro.core.sequential import batch_plan_for

    return batch_plan_for(gb, options=options, cache=engine._plan_cache,
                          stats=engine._plan_stats)


def test_overflow_struct_semantics():
    assert not Overflow().any and not Overflow()
    assert Overflow(h=True).any
    assert Overflow(transpose=True) and Overflow(hedge=True)


# ----------------------------------------------------- deprecation shims


def test_legacy_entry_points_warn_and_match():
    from repro.core.sequential import (
        find_triangles,
        triangle_count,
        triangle_count_batch,
    )
    from repro.graph.csr import from_edges_batch, to_batch

    edges, n = gen.karate()
    g = from_edges(edges, n)
    engine = default_engine()
    with pytest.warns(DeprecationWarning, match="triangle_count"):
        res = triangle_count(g)
    rep = engine.count(g, route="local")
    assert (int(res.triangles), int(res.c1), int(res.c2)) == (
        rep.triangles, rep.c1, rep.c2)
    gb = from_edges_batch([gen.karate(), gen.complete(9)])
    with pytest.warns(DeprecationWarning, match="triangle_count_batch"):
        bres = triangle_count_batch(gb)
    assert int(bres.triangles[0]) == rep.triangles
    with pytest.warns(DeprecationWarning, match="find_triangles"):
        tri, cnt = find_triangles(g, max_triangles=64)
    tri2, cnt2 = engine.find(g, max_triangles=64)
    assert int(cnt) == int(cnt2) == 45
    assert np.array_equal(np.asarray(tri), np.asarray(tri2))
    # B=1 batch wrapper stays bit-identical through the shim stack
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        b1 = triangle_count_batch(to_batch(g))
    assert int(b1.triangles[0]) == rep.triangles


def test_legacy_parallel_entry_point_warns_and_matches():
    import jax
    from jax.sharding import Mesh

    from repro.core.parallel_tc import parallel_triangle_count

    edges, n = gen.karate()
    g = from_edges(edges, n)
    devs = np.array(jax.devices())
    mesh = Mesh(devs.reshape(devs.size), ("p",))
    with pytest.warns(DeprecationWarning, match="parallel_triangle_count"):
        res = parallel_triangle_count(g, mesh)
    rep = default_engine().count(g, route="distributed")
    assert int(res.triangles) == rep.triangles == 45
    assert float(res.k) == rep.k


# --------------------------------------------- multi-device route parity


@pytest.mark.slow
def test_distributed_route_parity_multidevice():
    """Engine distributed route vs the local route and the legacy entry
    point: bit-identical triangles/k on p in {1, 2, 4}, both
    intersection backends (the acceptance matrix)."""
    out = run_multidevice(
        """
        import warnings
        import jax, numpy as np
        from jax.sharding import Mesh
        from repro.api import TCOptions, TriangleEngine
        from repro.core.parallel_tc import parallel_triangle_count
        from repro.graph import generators as gen
        from repro.graph.csr import from_edges

        edges, n = gen.rmat(8, 8, seed=1)
        g = from_edges(edges, n)
        devs = np.array(jax.devices())
        for backend in ('jnp', 'pallas'):
            opts = TCOptions(backend=backend, interpret=True)
            engine = TriangleEngine(opts)
            local = engine.count(g, route='local')
            for p in (1, 2, 4):
                mesh = Mesh(devs[:p].reshape(p), ('p',))
                eng_p = TriangleEngine(opts, mesh=mesh)
                rep = eng_p.count(g, route='distributed')
                assert rep.triangles == local.triangles, (backend, p)
                assert rep.k == local.k, (backend, p)
                assert rep.c1 is None and not rep.overflow.any
                assert rep.backend == backend
                with warnings.catch_warnings():
                    warnings.simplefilter('ignore', DeprecationWarning)
                    legacy = parallel_triangle_count(
                        g, mesh, intersect_backend=backend, interpret=True)
                assert int(legacy.triangles) == rep.triangles, (backend, p)
                assert float(legacy.k) == rep.k, (backend, p)
        print('DONE')
        """
    )
    assert "DONE" in out
