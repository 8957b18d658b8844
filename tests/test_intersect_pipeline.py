"""Compacted / degree-bucketed / Pallas-dispatched pipeline vs the dense
seed reference: bit-identical (triangles, c1, c2) on every fixture, bucket
boundary cases, and the backend switch itself."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import hub_graph
from tests import oracle

from repro.api import TCOptions, TriangleEngine
from repro.core import intersect
from repro.core import sequential as seq
from repro.core.intersect import (
    IntersectPlan,
    PlanBucket,
    count_common_neighbors,
    plan_buckets,
    probe_block,
    resolve_backend,
)
from repro.core.sequential import (
    find_triangles,
    find_triangles_dense,
    triangle_count,
    triangle_count_dense,
)
from repro.graph import generators as gen
from repro.graph.csr import (
    GraphBatch,
    from_edges,
    from_edges_batch,
    max_degree,
    to_batch,
)

BACKENDS = ("jnp", "pallas")


def _assert_equiv(res, ref):
    assert int(res.triangles) == int(ref.triangles)
    assert int(res.c1) == int(ref.c1)
    assert int(res.c2) == int(ref.c2)
    assert int(res.num_horizontal) == int(ref.num_horizontal)


@pytest.mark.parametrize("backend", BACKENDS)
def test_fixture_equivalence(named_graph, backend):
    name, edges, n, g = named_graph
    ref = triangle_count_dense(g, d_max=max(1, max_degree(g)))
    res = triangle_count(g, intersect_backend=backend)
    _assert_equiv(res, ref)
    # compaction really happened: padded rows never exceed slot count and
    # track the horizontal-edge count, not the 2m slots
    assert int(res.probe_rows) <= g.num_slots
    assert int(res.probe_rows) >= int(res.num_horizontal)
    assert not bool(res.h_overflow)


@pytest.mark.parametrize("backend", BACKENDS)
def test_query_chunk_equivalence(named_graph, backend):
    name, edges, n, g = named_graph
    ref = triangle_count_dense(g, d_max=max(1, max_degree(g)))
    for chunk in (32, 128):
        res = triangle_count(
            g, intersect_backend=backend, query_chunk=chunk
        )
        _assert_equiv(res, ref)


def test_bucket_boundary_degrees():
    """Degree exactly at a bucket edge must land inside that bucket
    (candidate width == small-endpoint degree, no truncation)."""
    edges, n = gen.complete(9)  # every degree is exactly 8
    g = from_edges(edges, n)
    ref = triangle_count_dense(g, d_max=8)
    for widths in ((8,), (7,), (9,), (4, 8), (1, 2, 3)):
        for backend in BACKENDS:
            res = triangle_count(
                g, intersect_backend=backend, bucket_widths=widths
            )
            _assert_equiv(res, ref)


def test_bucket_layout_split(named_graph):
    """Odd bucket layouts never change the counts, only the padding."""
    name, edges, n, g = named_graph
    ref = triangle_count_dense(g, d_max=max(1, max_degree(g)))
    for widths in ((1,), (2, 4, 8, 16), (10_000,)):
        res = triangle_count(g, bucket_widths=widths)
        _assert_equiv(res, ref)


def test_all_horizontal_clique():
    """BFS from any clique vertex puts the other 8 on one level: all
    C(8,2) = 28 non-root edges are horizontal."""
    edges, n = gen.complete(9)
    g = from_edges(edges, n)
    res = triangle_count(g)
    assert int(res.num_horizontal) == 28
    assert int(res.triangles) == 84  # C(9,3)
    _assert_equiv(res, triangle_count_dense(g, d_max=8))


def test_zero_horizontal_star():
    """A star has no horizontal edges: the plan is empty, nothing is
    probed, and the count is exactly zero."""
    leaves = 12
    edges = np.array([(0, i) for i in range(1, leaves + 1)])
    g = from_edges(edges, leaves + 1)
    for backend in BACKENDS:
        res = triangle_count(g, intersect_backend=backend)
        assert int(res.triangles) == 0
        assert int(res.num_horizontal) == 0
        assert int(res.probe_rows) == 0
        assert int(res.probe_cells) == 0
    tri, cnt = find_triangles(g, max_triangles=8)
    assert int(cnt) == 0
    assert (np.asarray(tri) == -1).all()


def test_cap_h_overflow_flagged():
    edges, n = gen.karate()
    g = from_edges(edges, n)
    full = triangle_count(g)
    capped = triangle_count(g, cap_h=4)
    assert bool(capped.h_overflow)
    assert not bool(full.h_overflow)
    assert int(capped.probe_rows) <= 64  # one padded bucket at most
    assert int(capped.triangles) <= int(full.triangles)


def _tri_set(tri, cnt):
    return {tuple(sorted(r)) for r in np.asarray(tri)[: int(cnt)].tolist()}


@pytest.mark.parametrize("backend", BACKENDS)
def test_find_triangles_equivalence(named_graph, backend):
    name, edges, n, g = named_graph
    dm = max(1, max_degree(g))
    mt = min(4096, g.num_slots * dm)
    tri_d, cnt_d = find_triangles_dense(g, d_max=dm, max_triangles=mt)
    tri, cnt = find_triangles(g, max_triangles=mt, intersect_backend=backend)
    assert int(cnt) == int(cnt_d)
    assert int(cnt) <= mt  # full comparison below is meaningful
    assert _tri_set(tri, cnt) == _tri_set(tri_d, cnt_d)
    pad = np.asarray(tri)[int(cnt):]
    assert (pad == -1).all()


@pytest.mark.parametrize("backend", BACKENDS)
def test_probe_block_backends_bit_identical(backend):
    """The two probe backends share the CSR gather, so (cand, found) —
    not just the counts — must match elementwise."""
    edges, n = gen.rmat(7, 8, seed=5)
    g = from_edges(edges, n)
    rng = np.random.default_rng(0)
    qu = jnp.asarray(rng.integers(0, n, size=64).astype(np.int32))
    qw = jnp.asarray(rng.integers(0, n, size=64).astype(np.int32))
    keep = qu < qw  # sentinel some rows too
    qu = jnp.where(keep, qu, n)
    qw = jnp.where(keep, qw, n)
    dm = max(1, max_degree(g))
    cand_j, found_j = probe_block(g, qu, qw, d_cand=dm, d_targ=dm,
                                  backend="jnp")
    cand_b, found_b = probe_block(g, qu, qw, d_cand=dm, d_targ=dm,
                                  backend=backend, interpret=True)
    np.testing.assert_array_equal(np.asarray(cand_j), np.asarray(cand_b))
    np.testing.assert_array_equal(np.asarray(found_j), np.asarray(found_b))


def test_count_common_neighbors_chunk_invariance():
    edges, n = gen.erdos_renyi(120, 0.08, seed=11)
    g = from_edges(edges, n)
    lev = jnp.zeros((n,), jnp.int32)  # everything "same level" -> all c2
    rng = np.random.default_rng(3)
    qu = jnp.asarray(np.sort(rng.integers(0, n, size=128)).astype(np.int32))
    qw = jnp.asarray(rng.integers(0, n, size=128).astype(np.int32))
    lo = jnp.minimum(qu, qw)
    hi = jnp.maximum(qu, qw)
    qu, qw = jnp.where(lo == hi, n, lo), jnp.where(lo == hi, n, hi)
    dm = max(1, max_degree(g))
    base = count_common_neighbors(g, qu, qw, lev, d_cand=dm, d_targ=dm)
    for chunk in (16, 64, 128):
        got = count_common_neighbors(
            g, qu, qw, lev, d_cand=dm, d_targ=dm, query_chunk=chunk
        )
        assert int(got[0]) == int(base[0]) and int(got[1]) == int(base[1])


def test_resolve_backend():
    # this container is CPU: auto must pick the jnp probe + interpreter
    backend, interpret = resolve_backend("auto", None)
    if jax.default_backend() == "tpu":
        assert backend == "pallas" and interpret is False
    else:
        assert backend == "jnp" and interpret is True
    assert resolve_backend("pallas", False) == ("pallas", False)
    with pytest.raises(ValueError):
        resolve_backend("cuda")


# ------------------------------------------------------- target bands

#: a desc-layout profile (small degrees descending) whose w512 and w256
#: buckets hold larger degrees in three bands, in mixed order
HUB_DS = np.array([300, 300, 300, 100, 100, 100, 100, 10, 10])
HUB_DL = np.array([600, 3000, 400, 100, 5000, 2000, 100, 20, 50])


def test_band_layout_by_hand():
    plan = plan_buckets(HUB_DS, HUB_DL, backend="pallas", layout="desc")
    assert plan.band_ends == (3, 7, 9)
    assert plan.buckets == (
        PlanBucket(start=7, count=2, rows=64, d_cand=32, d_targ=128),
        PlanBucket(start=3, count=1, rows=64, d_cand=256, d_targ=5120),
        PlanBucket(start=4, count=1, rows=64, d_cand=256, d_targ=2048),
        PlanBucket(start=5, count=2, rows=64, d_cand=128, d_targ=128),
        PlanBucket(start=0, count=1, rows=64, d_cand=512, d_targ=3072),
        PlanBucket(start=1, count=1, rows=64, d_cand=512, d_targ=640),
        PlanBucket(start=2, count=1, rows=64, d_cand=512, d_targ=512),
    )


def test_narrow_targets_keep_the_unbanded_plan():
    """Larger degrees all at most 512 (a skew-free graph): the plan is
    the one without bands, widths and all."""
    dl = np.minimum(HUB_DL, 512)
    plan = plan_buckets(HUB_DS, dl, backend="pallas", layout="desc")
    assert plan == IntersectPlan(
        buckets=(
            PlanBucket(start=7, count=2, rows=64, d_cand=32, d_targ=128),
            PlanBucket(start=3, count=4, rows=64, d_cand=256, d_targ=512),
            PlanBucket(start=0, count=3, rows=64, d_cand=512, d_targ=512),
        ),
        backend="pallas",
    )


def test_jnp_plans_never_band():
    """The jnp probe searches the CSR and gathers no targets."""
    plan = plan_buckets(HUB_DS, HUB_DL, backend="jnp", layout="desc")
    assert plan.band_ends == ()
    assert [(b.start, b.count, b.d_cand, b.d_targ) for b in plan.buckets] == [
        (7, 2, 32, 128), (3, 4, 256, 5120), (0, 3, 512, 3072)]


@pytest.mark.parametrize("seed", range(4))
def test_bands_cover_their_rows(seed):
    """Heavy-tailed random profiles: in ``run_plan``'s row order every
    band's rows fit its widths, bands tile each candidate bucket, and
    ``d_cand <= d_targ``."""
    rng = np.random.default_rng(seed)
    a = np.minimum(rng.pareto(0.8, 3000).astype(np.int64) + 1, 20_000)
    b = np.minimum(rng.pareto(0.8, 3000).astype(np.int64) + 1, 20_000)
    ds, dl = np.minimum(a, b), np.maximum(a, b)
    order = np.argsort(-ds, kind="stable")
    ds, dl = ds[order], dl[order]
    plan = plan_buckets(ds, dl, backend="pallas", layout="desc",
                        row_mult=32)
    assert plan.band_ends
    perm = np.asarray(intersect.band_order(plan, jnp.asarray(dl)))
    assert sorted(perm.tolist()) == list(range(ds.size))
    ds_p, dl_p = ds[perm], dl[perm]
    covered = np.zeros(ds.size, int)
    for bk in plan.buckets:
        rows = slice(bk.start, bk.start + bk.count)
        assert bk.d_cand <= bk.d_targ and bk.rows % 32 == 0
        assert ds_p[rows].max() <= bk.d_cand
        assert dl_p[rows].max() <= bk.d_targ
        covered[rows] += 1
    assert (covered == 1).all()
    seg = np.searchsorted(plan.band_ends, np.arange(ds.size), "right")
    assert (seg[perm] == seg).all()  # no row leaves its candidate bucket


def _pallas(**kw):
    return TCOptions(backend="pallas", interpret=True, **kw)


def _exact_plan(g, o):
    """The exact plan a count of ``g`` (a graph or a batch) runs."""
    gb = g if isinstance(g, GraphBatch) else to_batch(g)
    return seq._exact_batch_plan(
        gb.lane_view(), 0, o.cap_h, o.bucket_widths, o.d_max,
        int(o.query_chunk or o.row_mult), "pallas", True, o.query_chunk,
    )[-1]


@pytest.fixture(scope="module")
def hubs():
    edges, n = hub_graph()
    return edges, n, from_edges(edges, n)


@pytest.mark.parametrize("chunk", [None, 32, 64])
def test_banded_count_and_per_vertex_exact(hubs, chunk):
    """Pallas (interpreted) over a plan whose w16 bucket splits into
    three target bands; ``query_chunk`` 64 equals every band's rows, 32
    is smaller than the 36-row band's."""
    edges, n, g = hubs
    o = _pallas(query_chunk=chunk, per_vertex=True)
    assert len(_exact_plan(g, o).band_ends) == 1
    assert len(_exact_plan(g, o).buckets) == 3
    rep = TriangleEngine(o).count(g, route="local")
    assert rep.triangles == oracle.total_triangles(edges, n) == 210
    assert not rep.overflow
    np.testing.assert_array_equal(
        np.asarray(rep.per_vertex), oracle.triangle_counts(edges, n))


def test_banded_find_exact(hubs):
    edges, n, g = hubs
    tri, cnt = TriangleEngine(_pallas()).find_raw(g, max_triangles=512)
    tri_d, cnt_d = find_triangles_dense(
        g, d_max=max_degree(g), max_triangles=512)
    assert int(cnt) == int(cnt_d) == 210
    assert _tri_set(tri, cnt) == _tri_set(tri_d, cnt_d)


def test_banded_pooled_batch_exact():
    """Two lanes with different hubs share one plan laid out from their
    pooled per-row maxima; each lane sorts its own rows."""
    graphs = [hub_graph(10, (600, 2500)), hub_graph(12, (1500,))]
    gb = from_edges_batch(graphs)
    assert _exact_plan(gb, _pallas()).band_ends
    res = TriangleEngine(_pallas()).count_batch_raw(gb)
    for i, (edges, n) in enumerate(graphs):
        assert int(res.triangles[i]) == oracle.total_triangles(edges, n)
        assert not bool(res.h_overflow[i])


def test_banded_overflow_matches_unbanded(hubs, monkeypatch):
    """Under an explicit ``d_max`` that clips candidate lists, the
    banded plan gives the unbanded plan's count and overflow flag."""
    edges, n, g = hubs
    o = _pallas(d_max=8)
    assert _exact_plan(g, o).band_ends
    banded = TriangleEngine(o).count(g, route="local")
    monkeypatch.setattr(intersect, "TARGET_BAND_EDGE", 1 << 30)
    assert not _exact_plan(g, o).band_ends
    flat = TriangleEngine(o).count(g, route="local")
    assert banded.overflow and flat.overflow
    assert banded.triangles == flat.triangles < 210
