"""Spans and counters of the count path (``repro.obs``): the spans nest
inside ``tc.count`` in a real profiler trace, the gather counters equal
a NumPy recomputation from the graph's degrees, and the module-global
plan-cache stats read as they did."""
from __future__ import annotations

import sys
from pathlib import Path

import numpy as np
import pytest

from repro import obs
from repro.api import TCOptions, TriangleEngine
from repro.core import sequential as seq
from repro.graph import generators as gen
from repro.graph.csr import from_edges_batch

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench import tracing  # noqa: E402

COUNT_SPANS = ("tc.ingest", "tc.plan_sync", "tc.plan_layout", "tc.probe",
               "tc.fetch")


def test_registry_incr_snapshot_reset():
    obs.reset()
    obs.incr("a")
    obs.incr("a", 2)
    obs.incr("b", 0.5)
    snap = obs.counters()
    assert snap == {"a": 3, "b": 0.5}
    obs.incr("a")
    assert snap["a"] == 3  # a snapshot, not a view
    obs.reset()
    assert obs.counters() == {}


def _traced(tmp_path, fn):
    """Host events of ``fn()`` run under the profiler, inside a
    ``bench.window`` span."""
    import jax

    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span(tracing.WINDOW_SPAN):
            fn()
    finally:
        jax.profiler.stop_trace()
    return tracing.TraceSummary(
        tracing.load_xplane(tracing.find_xplane(tmp_path)))


def _spans(summary, name):
    return [(s, e, line) for plane, line, n, s, e in summary.events
            if plane == tracing.HOST_PLANE and n == name]


def test_count_spans_nest_inside_tc_count(tmp_path):
    engine = TriangleEngine()
    edges, n = gen.rmat(7, 8, seed=3)
    engine.count((edges, n))  # compile outside the trace
    t = _traced(tmp_path, lambda: engine.count((edges, n)))
    (count,) = _spans(t, "tc.count")
    s0, e0, line = count
    assert line == t.host_line
    for name in COUNT_SPANS:
        (span,) = _spans(t, name)
        s, e, ln = span
        assert ln == line and s0 <= s <= e <= e0, name


def test_readers_on_a_traced_count(tmp_path):
    """The span and counter readers of the count cells find their
    numbers in a CPU trace of one count (the device-op readers are
    checked on a recorded chip trace in ``tests/bench``)."""
    from types import SimpleNamespace

    from bench import run

    engine = TriangleEngine()
    edges, n = gen.rmat(7, 8, seed=5)
    engine.count((edges, n))
    obs.reset()
    t = _traced(tmp_path, lambda: engine.count((edges, n)))
    ctx = SimpleNamespace(trace=t, counters={"counts": 1})
    (ingest,) = _spans(t, "tc.ingest")
    for cell in ("count", "urand"):
        got = run.load_module(
            ROOT / "bench" / "metrics" / f"ingest_s.{cell}.py").read(ctx)
        assert got == pytest.approx((ingest[1] - ingest[0]) * 1e-9)
        fill = run.load_module(
            ROOT / "bench" / "metrics" / f"gather_fill.{cell}.py").read(ctx)
        c = obs.counters()
        assert 0 < fill <= 100
        assert fill == pytest.approx(
            100 * c["probe.entries_real"] / c["probe.entries_gathered"])


def _ceil_to(x, mult):
    return max(mult, -(-x // mult) * mult)


def _expected_entries(edges, n, levels, backend, widths=(32, 256),
                      row_mult=64):
    """``(gathered, real)`` of one exact count, recomputed from the
    graph: its horizontal edges, their endpoints' degrees, and the
    bucket layout (widths, row padding, 128-aligned target width).
    Where targets are gathered and some bucket's larger degrees fall on
    both sides of 512 (or of 2048, 8192, ...), every bucket is gathered
    band by band: each band at its widest target and at the smaller of
    the bucket's width and that target."""
    e = np.sort(np.asarray(edges, np.int64), axis=1)
    e = np.unique(e[e[:, 0] != e[:, 1]], axis=0)
    deg = np.bincount(e.ravel(), minlength=n)
    h = e[levels[e[:, 0]] == levels[e[:, 1]]]
    ds = np.minimum(deg[h[:, 0]], deg[h[:, 1]])
    dl = np.maximum(deg[h[:, 0]], deg[h[:, 1]])
    top = 1 << max(0, int(ds.max()) - 1).bit_length()
    bounds = [w for w in widths if w < top] + [top]
    targ = backend != "jnp"
    band = np.zeros(dl.size, int)
    for edge in 512 * 4 ** np.arange(8):
        band += dl > edge
    buckets, lo = [], 0
    for w in bounds:
        rows = (ds > lo) & (ds <= w)
        lo = w
        if rows.any():
            buckets.append((w, rows))
    banded = targ and any(len(set(band[r])) > 1 for _, r in buckets)
    gathered = 0
    for w, rows in buckets:
        for b in set(band[rows]) if banded else (None,):
            sel = rows & (band == b) if banded else rows
            d_targ = _ceil_to(int(dl[sel].max()), 128)
            d_cand = min(w, d_targ) if banded else w
            gathered += _ceil_to(int(sel.sum()), row_mult) * (
                d_cand + (d_targ if targ else 0))
    real = int(ds.sum()) + (int(dl.sum()) if targ else 0)
    return gathered, real


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_gather_counters_match_numpy(backend):
    edges, n = gen.rmat(7, 8, seed=1)
    engine = TriangleEngine(TCOptions(backend=backend))
    obs.reset()
    rep = engine.count((edges, n))
    c = obs.counters()
    gathered, real = _expected_entries(edges, n, rep.levels, backend)
    assert gathered > real > 0
    assert c["probe.entries_gathered"] == gathered
    assert c["probe.entries_real"] == real


def test_gather_counters_match_numpy_with_bands():
    """A clique with hubs of 600 and 2,500 leaves: the Pallas plan
    gathers its one candidate bucket in three target bands, and the
    counters still equal the recount."""
    from conftest import hub_graph

    edges, n = hub_graph()
    engine = TriangleEngine(TCOptions(backend="pallas", interpret=True))
    obs.reset()
    rep = engine.count((edges, n))
    c = obs.counters()
    gathered, real = _expected_entries(edges, n, rep.levels, "pallas")
    assert (gathered, real) == (64 * (16 + 2560 + 16 + 640 + 16 + 128),
                                9 * (11 + 2510) + 9 * (11 + 610) + 36 * 22)
    assert c["probe.entries_gathered"] == gathered
    assert c["probe.entries_real"] == real


def test_gather_counters_skip_pooled_batches():
    """A batch's exact plan is laid out from its lanes' pooled maxima,
    which bound each lane's degrees but do not give them: only a
    one-lane plan adds to the counters, so they stay exact."""
    engine = TriangleEngine()
    exact = TCOptions(d_max=1 << 20)  # forces the exact two-stage plan
    graphs = [gen.rmat(6, 8, seed=1), gen.rmat(6, 8, seed=2)]
    obs.reset()
    engine.count_batch(graphs, options=exact)
    assert obs.counters() == {}
    engine.count_batch(graphs[:1], options=exact)
    c = obs.counters()
    assert c["probe.entries_gathered"] > c["probe.entries_real"] > 0


def test_batch_plan_cache_stats_read_as_before():
    seq._BATCH_PLAN_CACHE.clear()
    seq.batch_plan_cache_stats(reset=True)
    gb = from_edges_batch([gen.erdos_renyi(50, 0.1, seed=1)])
    p1 = seq.batch_plan_for(gb, intersect_backend="jnp")
    p2 = seq.batch_plan_for(gb, intersect_backend="jnp")
    assert p1 is p2
    # an engine's own cache keeps its own stats
    TriangleEngine().plan_for(gb)
    assert seq.batch_plan_cache_stats() == {
        "hits": 1, "misses": 1, "size": 1, "evictions": 0,
        "capacity": seq.DEFAULT_PLAN_CACHE_CAPACITY,
    }
    assert seq.batch_plan_cache_stats(reset=True)["hits"] == 1
    assert seq.batch_plan_cache_stats()["hits"] == 0
    assert seq.batch_plan_cache_stats()["size"] == 1


def test_serving_spans(tmp_path):
    server = TriangleEngine().serve(batch_size=2)
    graphs = [gen.erdos_renyi(30, 0.2, seed=1)] * 3  # one budget cell
    for e, n in graphs:  # compile outside the trace
        server.submit(e, n)
    server.drain()

    def serve():
        for e, n in graphs:
            server.submit(e, n)
        server.drain()

    t = _traced(tmp_path, serve)
    flushes = _spans(t, "serve.flush")
    packs = _spans(t, "tc.pack")
    assert len(flushes) == 2 and len(packs) == 2
    assert _spans(t, "serve.finalize")
    for s, e, _ in packs:
        assert any(fs <= s <= e <= fe for fs, fe, _ in flushes)
