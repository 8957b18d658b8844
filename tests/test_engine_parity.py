"""The unified intersection engine (core/intersect.py): the adjacency
views agree with each other, bounded plans are provably safe, and
Algorithm 2 run through the engine is bit-identical to Algorithm 1 and
the dense seed reference on 1-, 2- and 4-device meshes, both backends."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import intersect
from repro.core.intersect import (
    CsrAdjacency,
    IntersectPlan,
    PairListAdjacency,
    PlanBucket,
    plan_buckets_bounded,
    run_plan,
)
from repro.graph import generators as gen
from repro.graph.csr import from_edges, max_degree
from tests.conftest import nx_triangles
from tests.test_parallel_tc import run_multidevice

BACKENDS = ("jnp", "pallas")


def _random_queries(n, q, seed):
    rng = np.random.default_rng(seed)
    qu = rng.integers(0, n, size=q).astype(np.int32)
    qw = rng.integers(0, n, size=q).astype(np.int32)
    keep = qu != qw
    return (
        jnp.asarray(np.where(keep, qu, n)),
        jnp.asarray(np.where(keep, qw, n)),
    )


@pytest.mark.parametrize("backend", BACKENDS)
def test_pairlist_view_matches_csr(backend):
    """A Graph's CSR edge list IS a lex-sorted (owner, value) pair list,
    so both adjacency views must produce identical counts for any plan —
    this is exactly the sequential/distributed unification contract."""
    edges, n = gen.rmat(7, 8, seed=2)
    g = from_edges(edges, n)
    csr = CsrAdjacency.from_graph(g)
    pairs = PairListAdjacency(owners=g.src, values=g.dst, n_nodes=n)
    qu, qw = _random_queries(n, 96, seed=4)
    dm = max(1, max_degree(g))
    plan = IntersectPlan(
        buckets=(PlanBucket(0, 96, 96, dm, dm),),
        backend=backend, interpret=True,
    )
    level = jnp.asarray(np.random.default_rng(0).integers(0, 3, n), jnp.int32)
    for lev in (None, level):
        a = run_plan(csr, qu, qw, plan, level=lev)
        b = run_plan(pairs, qu, qw, plan, level=lev)
        assert int(a.c1) == int(b.c1) and int(a.c2) == int(b.c2)
        assert not bool(a.overflow) and not bool(b.overflow)


@pytest.mark.parametrize("backend", BACKENDS)
def test_bounded_sorted_plan_matches_exact(backend):
    """A bounded plan (static caps + in-trace descending degree sort, the
    shard_map layout) must count exactly what one max-width bucket does."""
    edges, n = gen.erdos_renyi(150, 0.06, seed=7)
    g = from_edges(edges, n)
    csr = CsrAdjacency.from_graph(g)
    qu, qw = _random_queries(n, 128, seed=9)
    dm = max(1, max_degree(g))
    ref_plan = IntersectPlan(
        buckets=(PlanBucket(0, 128, 128, dm, dm),),
        backend=backend, interpret=True,
    )
    ref = run_plan(csr, qu, qw, ref_plan)
    deg = np.asarray(g.deg)
    quh, qwh = np.asarray(qu), np.asarray(qw)
    real = (quh < n) & (qwh < n)
    mind = np.minimum(deg[np.clip(quh, 0, n - 1)], deg[np.clip(qwh, 0, n - 1)])
    widths = tuple(w for w in (4, 16) if w < dm)
    exceed = tuple((w, int((real & (mind > w)).sum())) for w in widths)
    for chunk in (None, 32):
        plan = plan_buckets_bounded(
            128, d_pad=dm, exceed=exceed, bucket_widths=widths,
            row_mult=chunk or 8, backend=backend, interpret=True,
            query_chunk=chunk,
        )
        assert plan.sort_queries == (len(plan.buckets) > 1)
        got = run_plan(csr, qu, qw, plan)
        assert int(got.c1) == int(ref.c1)
        assert not bool(got.overflow)


def test_bounded_plan_safety_property():
    """Widest-first allocation from exceedance bounds: after a descending
    degree sort, EVERY query rank must land in a bucket at least as wide
    as its degree — for any query subset consistent with the bounds."""
    rng = np.random.default_rng(11)
    for _ in range(25):
        d_pad = int(rng.integers(8, 400))
        widths = sorted({int(w) for w in rng.integers(1, d_pad, size=3)})
        universe = rng.integers(1, d_pad + 1, size=300)
        exceed = tuple((w, int((universe > w).sum())) for w in widths)
        subset = universe[rng.random(300) < rng.random()]
        q = np.sort(subset)[::-1]  # descending, as run_plan lays them out
        plan = plan_buckets_bounded(
            300, d_pad=d_pad, exceed=exceed,
            bucket_widths=tuple(widths), row_mult=int(rng.integers(1, 64)),
        )
        assert plan.total_rows >= 300
        spans = sorted(plan.buckets, key=lambda b: b.start)
        assert spans[0].start == 0
        for a, b in zip(spans, spans[1:]):
            assert a.start + a.rows == b.start  # contiguous, no gaps
        for rank, d in enumerate(q):
            bucket = next(
                b for b in spans if b.start <= rank < b.start + b.rows
            )
            assert d <= bucket.d_cand, (rank, d, bucket)


@pytest.mark.slow
def test_parallel_parity_meshes_and_backends():
    """Acceptance: parallel_tc on 1/2/4-device meshes is bit-identical to
    triangle_count and triangle_count_dense, across both backends."""
    out = run_multidevice(
        """
        import jax, numpy as np
        from jax.sharding import Mesh
        from repro.graph import generators as gen
        from repro.graph.csr import from_edges, max_degree
        from repro.core.parallel_tc import (
            parallel_triangle_count, plan_hedge_rounds,
        )
        from repro.core.sequential import triangle_count, triangle_count_dense

        devs = np.array(jax.devices())
        cases = {
            'karate': gen.karate(),
            'complete9': gen.complete(9),
            'er120': gen.erdos_renyi(120, 0.06, seed=3),
            'rmat7': gen.rmat(7, 8, seed=5),
        }
        for name, (edges, n) in cases.items():
            g = from_edges(edges, n)
            dense = triangle_count_dense(g, d_max=max(1, max_degree(g)))
            want = int(dense.triangles)
            for backend in ('jnp', 'pallas'):
                seq = triangle_count(g, intersect_backend=backend,
                                     interpret=True)
                assert int(seq.triangles) == want, (name, backend)
                # the plumbed path: the hedge plan the distributed run
                # executes must carry the caller's backend choice
                hp = plan_hedge_rounds(g, 2, intersect_backend=backend,
                                       interpret=True)
                assert hp.backend == backend, (name, backend)
                for p in (1, 2, 4):
                    mesh = Mesh(devs[:p].reshape(p), ('p',))
                    res = parallel_triangle_count(
                        g, mesh, intersect_backend=backend, interpret=True,
                        frontier_dtype='uint8' if p == 2 else 'int32')
                    assert int(res.triangles) == want, (name, backend, p)
                    assert not bool(res.transpose_overflow), (name, backend, p)
                    assert not bool(res.hedge_overflow), (name, backend, p)
            print(name, 'OK', want)
        print('DONE')
        """,
        ndev=4,
    )
    assert "DONE" in out


@pytest.mark.slow
def test_parallel_parity_ring_mode():
    """Ring-mode rounds route through the same engine plan."""
    out = run_multidevice(
        """
        import jax, numpy as np
        from jax.sharding import Mesh
        from repro.graph import generators as gen
        from repro.graph.csr import from_edges, max_degree
        from repro.core.parallel_tc import parallel_triangle_count
        from repro.core.sequential import triangle_count_dense

        devs = np.array(jax.devices())
        edges, n = gen.rmat(7, 8, seed=5)
        g = from_edges(edges, n)
        want = int(triangle_count_dense(g, d_max=max(1, max_degree(g)))
                   .triangles)
        for p in (2, 4):
            mesh = Mesh(devs[:p].reshape(p), ('p',))
            res = parallel_triangle_count(g, mesh, mode='ring',
                                          hedge_chunk=64)
            assert int(res.triangles) == want, p
        print('DONE')
        """,
        ndev=4,
    )
    assert "DONE" in out


# ------------------------------------------ bounded target bands, row cap


def _seed_plan_buckets_bounded(total_rows, *, d_pad, exceed=None,
                               bucket_widths=(32, 256), row_mult=1,
                               backend="jnp", interpret=True,
                               query_chunk=None, sort_queries=None):
    """The bounded planner as it was before it took ``exceed_large``:
    the reference its callers without that table must still match."""
    def ceil_to(x, mult):
        return max(mult, -(-int(x) // mult) * mult)

    T = ceil_to(total_rows, row_mult) if total_rows > 0 else 0
    if T == 0:
        return IntersectPlan((), backend, interpret, query_chunk, False)
    if sort_queries is None:
        sort_queries = True
    top = int(d_pad)
    bound = dict(exceed or ())
    widths = sorted(w for w in {int(w) for w in bucket_widths}
                    if 0 < w < top and w in bound)
    widths.append(top)
    buckets, used = [], 0
    for i in range(len(widths) - 1, -1, -1):
        if i == 0:
            rows = T - used
        else:
            need = int(bound[widths[i - 1]])
            need_rows = ceil_to(need, row_mult) if need > 0 else 0
            rows = min(T - used, max(0, need_rows - used))
        if rows <= 0:
            continue
        buckets.append(PlanBucket(used, rows, rows, widths[i], top))
        used += rows
    return IntersectPlan(
        buckets=tuple(buckets), backend=backend, interpret=interpret,
        query_chunk=query_chunk,
        sort_queries=bool(sort_queries) and len(buckets) > 1,
    )


def _skewed_profile(rng, U, d_pad):
    """``(small, large)`` degrees of ``U`` edges: a few hub edges, many
    narrow ones, ``small <= large <= d_pad``."""
    large = np.minimum(d_pad, (rng.pareto(0.9, U) * 40 + 1).astype(int))
    large[: max(1, U // 50)] = d_pad
    small = np.maximum(1, (large * rng.random(U) ** 3).astype(int))
    return small, large


@pytest.mark.parametrize("seed", range(8))
def test_bounded_bands_fit_every_local_row(seed):
    """Bands and the row cap from global-degree bounds: after
    ``run_plan``'s two sorts (min list descending, then each candidate
    bucket by larger list descending), every real row of any block the
    bounds cover — a subset of the edges, each list shrunk to a local
    sublist no longer than the degree — lies inside the plan, in a
    bucket at least as wide as both of its lists."""
    rng = np.random.default_rng(seed)
    d_pad = int(rng.integers(520, 9000))
    U = int(rng.integers(200, 700))
    small, large = _skewed_profile(rng, U, d_pad)
    widths = (32, 256)
    edges = (0,) + intersect.target_band_edges(d_pad)
    plan = plan_buckets_bounded(
        2 * U + 5, d_pad=d_pad,
        exceed=tuple((w, int((small > w).sum())) for w in widths),
        exceed_large=tuple((e, int((large > e).sum())) for e in edges),
        bucket_widths=widths, row_mult=int(rng.integers(1, 48)),
        backend="pallas",
    )
    assert plan.band_ends and plan.sort_queries
    assert U <= plan.total_rows < 2 * U + 5 + 48
    pick = rng.random(U) < rng.uniform(0.3, 1.0)
    shrink = rng.uniform(0.0, 1.0, (2, U))
    lu = np.floor(small * shrink[0]).astype(int)[pick]
    lw = np.floor(large * shrink[1]).astype(int)[pick]
    lo, hi = np.minimum(lu, lw), np.maximum(lu, lw)
    block = 2 * U + 5
    key = np.full(block, -1)
    key[: lo.size] = lo
    big = np.zeros(block, int)
    big[: hi.size] = hi
    order = np.argsort(-key, kind="stable")
    key, big = key[order], big[order]
    ends = np.asarray(plan.band_ends)
    seg = np.searchsorted(ends, np.arange(ends[-1]), side="right")
    inner = np.lexsort((-big[: ends[-1]], seg))
    key[: ends[-1]], big[: ends[-1]] = key[inner], big[inner]
    assert (key[plan.total_rows:] <= 0).all()  # nothing real left out
    for b in plan.buckets:
        rows = slice(b.start, b.start + b.rows)
        assert (key[rows] <= b.d_cand).all(), b
        assert (big[rows] <= b.d_targ).all(), b


def test_bounded_plan_without_large_table_is_the_seed_plan():
    """Called without ``exceed_large``, the planner returns the plan it
    returned before, field for field, for any arguments."""
    rng = np.random.default_rng(3)
    for _ in range(200):
        d_pad = int(rng.integers(1, 5000))
        widths = tuple(sorted({int(w) for w in rng.integers(1, 600, 3)}))
        exceed = (None if rng.random() < 0.2 else tuple(
            (w, int(rng.integers(0, 900))) for w in widths))
        kw = dict(
            d_pad=d_pad, exceed=exceed, bucket_widths=widths,
            row_mult=int(rng.integers(1, 100)),
            backend=str(rng.choice(["jnp", "pallas"])),
            query_chunk=None if rng.random() < 0.5 else 64,
            sort_queries=[None, False, True][int(rng.integers(0, 3))],
        )
        rows = int(rng.integers(0, 3000))
        assert (plan_buckets_bounded(rows, **kw)
                == _seed_plan_buckets_bounded(rows, **kw)), (rows, kw)


def test_callers_without_large_table_get_the_seed_plan(monkeypatch):
    """The serving path (``batch_plan_for``), the dry runs
    (``analysis.routes.bounded_plan``) and ``build_tc_shard_fn``'s
    ``exceed=None`` fallback pass no larger-list table and get exactly
    the plan the planner gave them before."""
    from repro.analysis import routes
    from repro.core import parallel_tc, sequential
    from repro.graph.csr import from_edges_batch

    calls = []

    def recorded(*a, **kw):
        plan = plan_buckets_bounded(*a, **kw)
        calls.append((a, kw, plan))
        return plan

    for mod in (routes, sequential, parallel_tc):
        monkeypatch.setattr(mod, "plan_buckets_bounded", recorded)
    for n_budget, slots in ((64, 512), (1024, 8192), (4096, 65536)):
        routes.bounded_plan(routes.synthetic_meta(n_budget, slots),
                            backend="pallas")
    gb = from_edges_batch([gen.rmat(7, 8, seed=1), gen.karate(),
                           gen.complete(9)])
    sequential.batch_plan_for(gb, intersect_backend="pallas",
                              cache={}, stats={"hits": 0, "misses": 0})
    for mode in ("allgather", "ring"):
        parallel_tc.build_tc_shard_fn(n=300, m2=4000, p=4, d_pad=97,
                                      mode=mode, hedge_chunk=64)
    assert len(calls) == 6
    for a, kw, plan in calls:
        assert "exceed_large" not in kw
        assert plan == _seed_plan_buckets_bounded(*a, **kw)


@pytest.mark.parametrize("factor", (0.0, 0.3, 1.0))
def test_undersized_band_bound_flags_overflow(factor):
    """A larger-list bound scaled below the truth moves hub rows into a
    band too narrow for them: the probe flags ``overflow``, and a count
    without the flag is exact."""
    from tests.conftest import hub_graph

    edges, n = hub_graph(clique=10, hubs=(600,))
    g = from_edges(edges, n)
    src, dst = np.asarray(g.src), np.asarray(g.dst)
    und = src < dst
    qu, qw = jnp.asarray(src[und]), jnp.asarray(dst[und])
    deg = np.asarray(g.deg)
    big = np.maximum(deg[src[und]], deg[dst[und]])
    d_pad = max_degree(g)
    edges_ = (0,) + intersect.target_band_edges(d_pad)
    true = {e: int((big > e).sum()) for e in edges_}
    plan = plan_buckets_bounded(
        qu.shape[0], d_pad=d_pad, exceed=((32, 0), (256, 0)),
        exceed_large=tuple((e, true[e] if e == 0 else int(factor * true[e]))
                           for e in edges_),
        row_mult=64, backend="pallas", interpret=True,
    )
    got = run_plan(CsrAdjacency.from_graph(g), qu, qw, plan)
    want = 3 * nx_triangles(edges, n)  # each triangle at its 3 edges
    assert bool(got.overflow) == (factor < 1.0)
    assert bool(got.overflow) or int(got.c1) == want


@pytest.mark.parametrize("mode", ("allgather", "ring"))
@pytest.mark.parametrize("name", ("hub", "er", "rmat9", "complete"))
def test_hedge_plan_ends_at_the_rows_that_can_be_real(name, mode):
    """The hedge plan ends at the first chunk boundary at or past the
    rows one block can hold for real: the graph's m undirected edges
    for the gathered block, the fullest shard's in ring mode (or the
    block's buffer, where that is shorter)."""
    from repro.core import parallel_tc
    from repro.graph.partition import shard_edges

    edges, n = _BAND_GRAPHS[name]()
    g = from_edges(edges, n)
    m2 = int(g.n_edges_dir)
    rows, chunk = parallel_tc._hedge_layout(m2, 4, mode, 64)
    plan = parallel_tc.plan_hedge_rounds(
        g, 4, mode=mode, hedge_chunk=64, intersect_backend="pallas")
    if mode == "allgather":
        real = m2 // 2
    else:
        s, d = shard_edges(g, 4, capacity=None)[:2]
        real = int((s < d).sum(axis=1).max())
    assert plan.total_rows == chunk * min(-(-real // chunk),
                                          -(-rows // chunk))
    if plan.total_rows < rows:  # the real rows must be sorted first
        assert plan.sort_queries
    # a ring plan is too short for the gathered block
    if mode == "ring":
        with pytest.raises(ValueError, match="mode mismatch"):
            parallel_tc.build_tc_shard_fn(n=n, m2=m2, p=4, hplan=plan,
                                          mode="allgather", hedge_chunk=64)


@pytest.mark.parametrize("backend", ("jnp", "pallas"))
def test_profile_plan_rounds_to_share_compiles(backend):
    """Two measured profiles of one graph whose bounds fall in the same
    power-of-two cells (of 1024-row chunks; the longest list of
    widths) give one plan, so a numbering of the graph that splits its
    lists otherwise reuses the compiled probe; every bound still covers
    its count, the top width its longest list, and the plan ends at
    the real rows."""
    from repro.core import parallel_tc

    kw = dict(rows=882_192, chunk=1024, widths=(32, 256),
              edges=(512, 2048), d_max=5993, intersect_backend=backend)
    # per device: rows past width 32, 256; past edge 512, 2048; the
    # longest local list; the real rows
    a = np.array([[68_240, 367, 18_879, 43, 2_487, 339_710],
                  [45_020, 48, 9_000, 0, 1_006, 339_710]])
    b = np.array([[87_151, 810, 28_327, 43, 2_841, 339_710],
                  [30_201, 18, 2_368, 0, 1_035, 339_710]])
    plan = parallel_tc.plan_from_profile(a, **kw)
    assert plan == parallel_tc.plan_from_profile(b, **kw)
    assert plan.total_rows == 339_968  # ceil(339,710, 1024)
    assert max(bk.d_targ for bk in plan.buckets) == 4096
    # rows planned at least as wide as each bound, widest first
    ranked = sorted(plan.buckets, key=lambda bk: bk.start)
    for w, need in ((32, 87_151), (256, 810)):
        wide = sum(bk.rows for bk in ranked if bk.d_cand > w)
        assert wide >= need
    if backend == "pallas":
        for e, need in ((512, 28_327), (2048, 43)):
            assert sum(bk.rows for bk in ranked if bk.d_targ > e) >= need
    # the top width stops at the largest degree
    c = a.copy()
    c[0, 4] = 5_000
    top = parallel_tc.plan_from_profile(c, **kw)
    assert max(bk.d_targ for bk in top.buckets) == 5993


def _hub_er():
    """``conftest.hub_graph`` (a 600-leaf hub on a 10-clique) beside a
    uniform piece: larger lists on both sides of the first band edge."""
    from tests.conftest import hub_graph

    he, hn = hub_graph(clique=10, hubs=(600,))
    ee, en = gen.erdos_renyi(150, 0.05, seed=4)
    return np.concatenate([he, np.asarray(ee) + hn]), hn + en


_BAND_GRAPHS = {
    "hub": _hub_er,
    "er": lambda: gen.erdos_renyi(200, 0.05, seed=5),
    "rmat9": lambda: gen.rmat(9, 16, seed=27491095),
    "complete": lambda: gen.complete(40),
}

BANDED_BODY = """
import json
import jax, numpy as np
from jax.sharding import Mesh
from repro.api import TCOptions, TriangleEngine
from repro.core import parallel_tc as ptc
from repro.core.intersect import plan_buckets_bounded
from repro.graph.csr import from_edges
from tests import oracle
from tests.test_engine_parity import _BAND_GRAPHS

mesh = Mesh(np.array(jax.devices()[:4]), ("p",))
engine = TriangleEngine(mesh=mesh)
ran, tables = [], []  # the plan of each count, each measured profile
impl, planner = ptc._parallel_triangle_count, ptc.plan_from_profile
def recorded(*a, **kw):
    res, plan = impl(*a, **kw)
    ran.append(plan)
    return res, plan
def measured(table, **kw):
    tables.append((np.asarray(table), kw))
    return planner(table, **kw)
ptc._parallel_triangle_count = recorded
ptc.plan_from_profile = measured

def cut_rows(table, **kw):  # the real rows' bound a chunk short
    tables.append((np.asarray(table), kw))
    table = np.array(table)
    table[:, -1] -= kw["chunk"]
    return planner(table, **kw)
def cut_width(table, **kw):  # the first candidate width's bound to 0
    tables.append((np.asarray(table), kw))
    table = np.array(table)
    table[:, 0] = 0
    return planner(table, **kw)

out = {}
for name in ("hub", "er"):
    edges, n = _BAND_GRAPHS[name]()
    want = int(oracle.triangle_counts(edges, n).sum()) // 3
    for root in (0, 10):
        for mode in ("allgather", "ring"):
            for backend in ("jnp", "pallas"):
                o = TCOptions(backend=backend, interpret=True, root=root,
                              mode=mode, hedge_chunk=64)
                rep = engine.count((edges, n), route="distributed",
                                   options=o)
                plan = ptc.plan_hedge_rounds(
                    from_edges(edges, n), 4, mode=mode, hedge_chunk=64,
                    intersect_backend=backend)
                case = f"{name}/{root}/{mode}/{backend}"
                out[case] = {
                    "want": want, "got": rep.triangles,
                    "overflow": bool(rep.overflow),
                    "banded": bool(plan.band_ends),
                    "ran_entries": ran[-1].gather_entries,
                    "static_entries": plan.gather_entries,
                }
                if mode == "ring":
                    continue
                # plans from a profile with one bound or the width cut
                # short: the real rows' bound, a candidate width's bound
                # (at width 1, which real rows exceed), the top width
                longest = int(tables[-1][0][:, -2].max())
                cuts = {
                    "rows": (cut_rows, o),
                    "width_bound": (cut_width, TCOptions(
                        backend=backend, interpret=True, root=root,
                        mode=mode, hedge_chunk=64, bucket_widths=(1,))),
                    "top": (measured, TCOptions(
                        backend=backend, interpret=True, root=root,
                        mode=mode, hedge_chunk=64, d_pad=longest - 1)),
                }
                for cut, (fn, oc) in cuts.items():
                    ptc.plan_from_profile = fn
                    try:
                        r = engine.count((edges, n), route="distributed",
                                         options=oc)
                    finally:
                        ptc.plan_from_profile = measured
                    out[f"{case}/{cut}"] = {
                        "want": want, "got": r.triangles,
                        "overflow": bool(r.overflow),
                        "width_rows": int(tables[-1][0][:, 0].max())
                        if cut == "width_bound" else None,
                    }
# a ring plan one chunk past the least the guard lets through: the
# complete graph's first shard holds more horizontal edges than that
edges, n = _BAND_GRAPHS["complete"]()
g = from_edges(edges, n)
m2 = int(g.n_edges_dir)
short = plan_buckets_bounded(ptc._least_plan_rows(m2, 4, "ring", 64),
                             d_pad=39, row_mult=64, query_chunk=64)
s, d, _, _ = ptc.shard_edges(g, 4, capacity=ptc._capacities(m2, 4, 4.0)[0])
fn, _ = ptc.build_tc_shard_fn(n=n, m2=m2, p=4, mode="ring",
                              hedge_chunk=64, hplan=short)
res = ptc._tc_distributed(jax.numpy.asarray(s.reshape(-1)),
                          jax.numpy.asarray(d.reshape(-1)), mesh=mesh,
                          body=tuple(sorted(fn.keywords.items())))
out["short_ring"] = {"hedge_overflow": bool(res.hedge_overflow),
                     "got": int(res.triangles),
                     "want": int(oracle.triangle_counts(edges, n).sum()) // 3}
print("RESULT", json.dumps(out))
"""

BANDED_CASES = [f"{g}/{r}/{m}/{b}" for g in ("hub", "er") for r in (0, 10)
                for m in ("allgather", "ring") for b in ("jnp", "pallas")]


@pytest.fixture(scope="module")
def banded_runs():
    import json

    out = run_multidevice(BANDED_BODY, ndev=4)
    (line,) = [ln for ln in out.splitlines() if ln.startswith("RESULT ")]
    return json.loads(line[len("RESULT "):])


@pytest.mark.parametrize("case", BANDED_CASES)
def test_banded_algorithm2_equals_oracle(banded_runs, case):
    """Algorithm 2 on four devices through the banded, capped hedge
    plan: exact, no overflow, every root, mode and backend.  The skewed
    graph's gathered Pallas plan is banded (in ring mode the hub's shard
    fills its widest band), and no ``jnp`` plan is."""
    r = banded_runs[case]
    assert r["want"] > 0
    assert r["got"] == r["want"] and not r["overflow"], r
    name, _, mode, backend = case.split("/")
    if backend == "jnp" or name == "hub" and mode == "allgather":
        assert r["banded"] == (backend == "pallas")


MEASURED_CASES = [c for c in BANDED_CASES if "/allgather/" in c]


@pytest.mark.parametrize("case", MEASURED_CASES)
def test_measured_plan_gathers_no_more_than_static(banded_runs, case):
    """In allgather mode the plan that ran is measured from the
    gathered block's sublist lengths on each device: never more list
    entries than the static plan from the whole graph's degrees."""
    r = banded_runs[case]
    assert 0 < r["ran_entries"] <= r["static_entries"], r


@pytest.mark.parametrize("cut", ("rows", "width_bound", "top"))
@pytest.mark.parametrize("case", MEASURED_CASES)
def test_cut_profile_flags_overflow(banded_runs, case, cut):
    """A plan from a measured profile with one bound cut short (the
    real rows a chunk short, the rows past width 1 set to 0) or with
    its top width below the longest local list flags overflow; the
    flagged count never exceeds the truth."""
    r = banded_runs[f"{case}/{cut}"]
    if cut == "width_bound":
        assert r["width_rows"] > 0
    assert r["overflow"] and r["got"] <= r["want"], r


def test_too_short_ring_plan_flags_hedge_overflow(banded_runs):
    r = banded_runs["short_ring"]
    assert r["hedge_overflow"] and r["got"] < r["want"]
