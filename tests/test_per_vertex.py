"""Per-vertex triangle attribution — engine vs the independent brute force.

``tests/oracle.py`` (pure NumPy, zero repro imports) is ground truth; the
engine must match it **bit-exactly** on every route (local / batch /
distributed), every backend (jnp / pallas) and every device count.  The
standing invariant ``sum(per_vertex) == 3 * triangles`` — every triangle
credited at exactly its three corners — is asserted on every comparison.
"""
from __future__ import annotations

import numpy as np
import pytest

from conftest import FIXTURES, nx_triangles, optional_hypothesis
from tests import oracle
from tests.test_parallel_tc import run_multidevice

from repro.api import TCOptions, TriangleEngine
from repro.graph import generators as gen
from repro.graph.csr import from_edges

given, settings, st = optional_hypothesis()

SHAPES = {
    "path10": gen.path(10),
    "star16": gen.star(16),
    "complete9": gen.complete(9),
}


def _assert_matches_oracle(rep, edges, n, ctx=""):
    exp = oracle.triangle_counts(edges, n)
    got = np.asarray(rep.per_vertex)
    assert got.shape == (n,), ctx
    assert np.array_equal(got, exp), f"{ctx}: per_vertex != oracle"
    assert int(got.sum()) == 3 * int(rep.triangles), f"{ctx}: sum != 3T"
    assert np.array_equal(
        np.asarray(rep.degrees), oracle.degrees(edges, n)
    ), ctx


# ------------------------------------------------------------ oracle sanity
def test_oracle_agrees_with_networkx_totals():
    """The oracle is independent of the repro code; cross-check its totals
    against networkx so a bug in it can't silently bless the engine."""
    for name, (e, n) in {**FIXTURES, **SHAPES}.items():
        assert oracle.total_triangles(e, n) == nx_triangles(e, n), name


def test_oracle_handles_duplicates_and_self_loops():
    e = np.array([[0, 1], [1, 0], [1, 2], [2, 0], [2, 2], [0, 1]])
    assert np.array_equal(oracle.triangle_counts(e, 3), [1, 1, 1])
    assert oracle.total_triangles(e, 3) == 1
    assert np.array_equal(oracle.degrees(e, 3), [2, 2, 2])


# ------------------------------------------------------------- local route
def test_local_route_matches_oracle(named_graph):
    name, edges, n, g = named_graph
    rep = TriangleEngine(TCOptions(per_vertex=True)).count(g, route="local")
    _assert_matches_oracle(rep, edges, n, f"local/{name}")


def test_local_route_shapes_match_oracle():
    eng = TriangleEngine(TCOptions(per_vertex=True))
    for name, (edges, n) in SHAPES.items():
        rep = eng.count((edges, n), route="local")
        _assert_matches_oracle(rep, edges, n, f"local/{name}")


def test_pallas_backend_matches_oracle():
    """The pallas per-vertex path probes through the hit-mask kernel; it
    must stay bit-identical to the jnp scatter path."""
    eng = TriangleEngine(TCOptions(
        per_vertex=True, backend="pallas", interpret=True,
    ))
    for name in ("karate", "ring_of_cliques", "complete9"):
        edges, n = FIXTURES[name]
        rep = eng.count((edges, n), route="local")
        _assert_matches_oracle(rep, edges, n, f"pallas/{name}")


def test_dense_reference_matches_oracle():
    from repro.core.sequential import triangle_count_dense
    from repro.graph.csr import max_degree

    for name in ("karate", "complete9", "geometric"):
        edges, n = FIXTURES[name]
        g = from_edges(edges, n)
        res = triangle_count_dense(g, d_max=max(1, max_degree(g)))
        assert np.array_equal(
            np.asarray(res.per_vertex), oracle.triangle_counts(edges, n)
        ), name


def test_flag_off_returns_none(named_graph):
    name, edges, n, g = named_graph
    rep = TriangleEngine().count(g, route="local")
    assert rep.per_vertex is None and rep.degrees is None
    with pytest.raises(ValueError, match="per-vertex"):
        rep.local_clustering()
    with pytest.raises(ValueError, match="per-vertex"):
        rep.top_k(3)


# ------------------------------------------------------------- batch route
def test_batch_route_matches_oracle(named_graph):
    name, edges, n, g = named_graph
    rep = TriangleEngine(TCOptions(per_vertex=True)).count(g, route="batch")
    _assert_matches_oracle(rep, edges, n, f"batch/{name}")


def test_count_batch_slices_per_lane():
    """Lanes of different sizes share one padded batch; each report must
    get exactly its own n_nodes rows back."""
    cases = [FIXTURES["karate"], SHAPES["star16"], SHAPES["complete9"],
             FIXTURES["ring_of_cliques"]]
    eng = TriangleEngine(TCOptions(per_vertex=True))
    reps = eng.count_batch(cases)
    assert len(reps) == len(cases)
    for (edges, n), rep in zip(cases, reps):
        _assert_matches_oracle(rep, edges, n, f"count_batch/n={n}")


# --------------------------------------------------- derived analytics
def test_complete_graph_clustering_is_one():
    rep = TriangleEngine(TCOptions(per_vertex=True)).count(gen.complete(9))
    assert np.array_equal(rep.local_clustering(), np.ones(9))
    assert rep.transitivity() == 1.0


def test_star_and_path_are_triangle_free():
    eng = TriangleEngine(TCOptions(per_vertex=True))
    for name, (edges, n) in (("star16", gen.star(16)), ("path10", gen.path(10))):
        rep = eng.count((edges, n))
        assert int(np.asarray(rep.per_vertex).sum()) == 0, name
        assert np.array_equal(rep.local_clustering(), np.zeros(n)), name
        assert rep.transitivity() == 0.0, name


def test_clustering_matches_oracle_on_fixture():
    edges, n = FIXTURES["geometric"]
    rep = TriangleEngine(TCOptions(per_vertex=True)).count((edges, n))
    np.testing.assert_allclose(
        rep.local_clustering(), oracle.local_clustering(edges, n),
        rtol=0, atol=1e-12,
    )
    assert rep.transitivity() == pytest.approx(
        oracle.transitivity(edges, n), abs=1e-12,
    )


def test_top_k_orders_by_count_then_vertex_id():
    edges, n = FIXTURES["ring_of_cliques"]
    rep = TriangleEngine(TCOptions(per_vertex=True)).count((edges, n))
    pv = np.asarray(rep.per_vertex)
    top = rep.top_k(5)
    assert len(top) == 5
    # ranked by count desc; ties broken toward the lower vertex id
    counts = pv[top]
    assert all(counts[i] >= counts[i + 1] for i in range(len(top) - 1))
    for i in range(len(top) - 1):
        if counts[i] == counts[i + 1]:
            assert top[i] < top[i + 1]
    assert counts[0] == pv.max()
    # k beyond n clamps
    assert len(rep.top_k(10 * n)) == n


def test_empty_graph_report():
    rep = TriangleEngine(TCOptions(per_vertex=True)).count(
        (np.zeros((0, 2), np.int64), 0)
    )
    assert rep.per_vertex is not None and rep.per_vertex.shape == (0,)
    assert rep.local_clustering().shape == (0,)
    assert rep.transitivity() == 0.0


# ---------------------------------------------------------------- property
@given(st.integers(min_value=0, max_value=2**31 - 1))
@settings(max_examples=15, deadline=None)
def test_property_random_graphs_match_oracle(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(2, 40))
    m = int(rng.integers(1, 4 * n))
    edges = rng.integers(0, n, size=(m, 2))
    rep = TriangleEngine(TCOptions(per_vertex=True)).count((edges, n))
    exp = oracle.triangle_counts(edges, n)
    assert np.array_equal(np.asarray(rep.per_vertex), exp)
    assert int(exp.sum()) == 3 * int(rep.triangles)


# ----------------------------------------------------------------- serving
def test_server_carries_per_vertex():
    eng = TriangleEngine(TCOptions(per_vertex=True, backend="jnp"))
    server = eng.serve(batch_size=4)
    cases = [FIXTURES["karate"], SHAPES["complete9"]]
    ids = [server.submit(e, n) for e, n in cases]
    results = {r.request_id: r for r in server.drain()}
    for rid, (edges, n) in zip(ids, cases):
        res = results[rid]
        exp = oracle.triangle_counts(edges, n)
        assert np.array_equal(np.asarray(res.per_vertex), exp)
        assert int(res.per_vertex.sum()) == 3 * res.triangles


def test_degraded_approx_answers_have_no_per_vertex():
    """Admission overflow degrades to the wedge sampler, which cannot
    attribute: those answers must say so with per_vertex=None."""
    e, n = FIXTURES["rmat8"]
    eng = TriangleEngine(TCOptions(
        per_vertex=True, backend="jnp", admission_tokens=1,
        approx_samples=4096,
    ))
    server = eng.serve(batch_size=8)
    server.submit(e, n)          # takes the cell's only token
    r1 = server.submit(e, n)     # over admission: degraded to approx
    approx = [r for r in server.results if r.request_id == r1]
    assert len(approx) == 1 and approx[0].approx is not None
    assert approx[0].per_vertex is None
    results = server.drain()
    exact = [r for r in results if r.approx is None]
    assert all(r.per_vertex is not None for r in exact)


def test_approx_route_has_no_per_vertex():
    e, n = FIXTURES["karate"]
    rep = TriangleEngine(TCOptions(per_vertex=True)).count(
        (e, n), route="approx", options=TCOptions(
            per_vertex=True, approx_samples=2048,
        ),
    )
    assert rep.route == "approx" and rep.per_vertex is None


# ------------------------------------------------------- distributed route
@pytest.mark.slow
def test_distributed_matches_oracle_over_device_counts():
    """p in {1, 2, 4}, both hedge modes: bit-identical to the brute force
    (embedded as a literal so the subprocess needs no test imports) and
    to the local route, with sum == 3T throughout."""
    edges, n = FIXTURES["karate"]
    exp = oracle.triangle_counts(edges, n)
    out = run_multidevice(
        f"""
        import jax, numpy as np
        from jax.sharding import Mesh
        from repro.api import TCOptions, TriangleEngine
        from repro.graph import generators as gen
        from repro.graph.csr import from_edges

        expected = np.array({exp.tolist()}, dtype=np.int64)
        edges, n = gen.karate()
        g = from_edges(edges, n)
        eng = TriangleEngine()
        local = eng.count(g, route="local",
                          options=TCOptions(per_vertex=True))
        assert np.array_equal(np.asarray(local.per_vertex), expected)
        devs = np.array(jax.devices())
        for p in (1, 2, 4):
            mesh = Mesh(devs[:p].reshape(p), ('p',))
            for mode in ('allgather', 'ring'):
                res = eng.count_distributed_raw(
                    g, mesh=mesh,
                    options=TCOptions(per_vertex=True, mode=mode),
                )
                pv = np.asarray(res.per_vertex)
                assert pv.shape == (n,), (p, mode, pv.shape)
                assert np.array_equal(pv, expected), (p, mode)
                assert int(pv.sum()) == 3 * int(res.triangles), (p, mode)
        print('DIST_PV_OK')
        """,
        ndev=4,
    )
    assert "DIST_PV_OK" in out


@pytest.mark.slow
def test_distributed_rmat_and_comm_invariant_with_attribution():
    """Attribution adds exactly one n-word allreduce to the reduce phase:
    measured == tally == modeled must stay bitwise-true with the flag on,
    and the running tally must price the credit psum."""
    edges, n = FIXTURES["rmat8"]
    exp = oracle.triangle_counts(edges, n)
    out = run_multidevice(
        f"""
        import jax, numpy as np
        from jax.sharding import Mesh
        from repro.api import TCOptions, TriangleEngine
        from repro.core import comm_instrument as ci
        from repro.graph import generators as gen
        from repro.graph.csr import from_edges

        expected = np.array({exp.tolist()}, dtype=np.int64)
        edges, n = gen.rmat(8, 8, seed=1)
        g = from_edges(edges, n)
        eng = TriangleEngine()
        res = eng.count_distributed_raw(
            g, options=TCOptions(per_vertex=True, mode='allgather'),
        )
        assert np.array_equal(np.asarray(res.per_vertex), expected)
        sweeps = int(np.asarray(res.comm.bfs_sweeps))
        m2 = int(np.asarray(g.n_edges_dir))
        p = len(jax.devices())
        for pv in (False, True):
            r = ci.comm_report(n, m2, p, sweeps=sweeps, mode='allgather',
                               per_vertex=pv)
            for ph, v in r['phases'].items():
                assert v['measured'] == v['tally'] == v['modeled'], (pv, ph)
        r1 = ci.comm_report(n, m2, p, sweeps=sweeps, mode='allgather',
                            per_vertex=True)
        assert res.comm.phase_bytes()['reduce'] == \\
            r1['phases']['reduce']['tally']
        print('DIST_COMM_OK')
        """,
        ndev=8,
    )
    assert "DIST_COMM_OK" in out
