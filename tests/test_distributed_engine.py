"""Algorithm 2 through the engine's normal path on four host devices:
``TriangleEngine(mesh=<1-D mesh "p">).count(..., route="distributed")``
equals the brute-force oracle and the local route, its per-device
partials sum to its total, it flags no overflow, a second count of the
same graph traces neither of its two programs, and the ``dist.*``
counters hold what the plan that ran and the report give.  The plan
measured from the gathered block is never larger than the static one
from the whole graph's degrees.  The spans of the route nest inside
``tc.count`` in a real profiler trace (one device, in process)."""
from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

from repro import obs
from repro.api import TriangleEngine
from repro.graph import generators as gen
from tests.test_parallel_tc import run_multidevice

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench import tracing  # noqa: E402

GRAPHS = ("kron9", "urand", "components")
DIST_SPANS = ("tc.ingest", "tc.shard", "tc.plan_sync", "tc.plan_layout",
              "tc.probe", "tc.fetch")

BODY = """
import json
import jax, numpy as np
from jax.sharding import Mesh
from repro import obs
from repro.api import TCOptions, TriangleEngine
from repro.core import parallel_tc as ptc
from repro.graph import generators as gen
from repro.graph.csr import from_edges
from tests import oracle

traces = {"front": [], "back": []}
def hooked(name, body):
    def counted(*a, **kw):
        traces[name].append(1)
        return body(*a, **kw)
    return counted
ptc._tc_front = hooked("front", ptc._tc_front)
ptc._tc_back = hooked("back", ptc._tc_back)

plans = []  # the plan each count's report was given
impl = ptc._parallel_triangle_count
def recorded(*a, **kw):
    res, plan = impl(*a, **kw)
    plans.append(plan)
    return res, plan
ptc._parallel_triangle_count = recorded

def ntraces():
    return {k: len(v) for k, v in traces.items()}

def components():
    # two cliques, a ring of cliques, a sparse R-MAT piece and isolated
    # vertices: several BFS reseeds, each component its own levels
    parts, off = [], 0
    for e, n in (gen.complete(7), gen.ring_of_cliques(4, 5),
                 gen.rmat(6, 4, seed=11), gen.complete(5)):
        parts.append(np.asarray(e) + off)
        off += n
    return np.concatenate(parts), off + 9

graphs = {
    "kron9": gen.rmat(9, 16, seed=27491095),
    "urand": gen.erdos_renyi(400, 0.04, seed=5),
    "components": components(),
}
mesh = Mesh(np.array(jax.devices()[:4]), ("p",))
engine = TriangleEngine(mesh=mesh)
out = {}
for name, (edges, n) in graphs.items():
    obs.reset()
    t0 = ntraces()
    rep = engine.count((edges, n), route="distributed")
    t1 = ntraces()
    again = engine.count((edges, n), route="distributed")
    t2 = ntraces()
    c = obs.counters()
    g = from_edges(edges, n)
    plan = plans[-1]
    assert plans[-2] == plan
    static = ptc.plan_hedge_rounds(g, 4)
    out[name] = {
        "oracle": int(oracle.triangle_counts(edges, n).sum()) // 3,
        "local": engine.count((edges, n), route="local").triangles,
        "dist": rep.triangles, "again": again.triangles,
        "per_device": [int(x) for x in rep.per_device],
        "overflow": bool(rep.overflow), "plan_id": rep.plan_id,
        "traces_first": {k: t1[k] - t0[k] for k in t0},
        "traces_second": {k: t2[k] - t1[k] for k in t0},
        "counters": c, "plan_rows": plan.probe_rows,
        "plan_entries": plan.gather_entries,
        "static_entries": static.gather_entries,
        "m": int(g.n_edges_dir) // 2, "plan_chunk": plan.query_chunk,
        "num_horizontal": rep.num_horizontal, "wire": rep.comm.total,
    }
edges, n = graphs["kron9"]
obs.reset()
ring = engine.count((edges, n), route="distributed",
                    options=TCOptions(mode="ring"))
out["ring"] = {
    "dist": ring.triangles, "plan_id": ring.plan_id,
    "counters": obs.counters(), "num_horizontal": ring.num_horizontal,
    "plan_rows": ptc.plan_hedge_rounds(from_edges(edges, n), 4,
                                       mode="ring").probe_rows,
    "plan_entries": ptc.plan_hedge_rounds(from_edges(edges, n), 4,
                                          mode="ring").gather_entries,
    "ran_static": plans[-1] == ptc.plan_hedge_rounds(from_edges(edges, n),
                                                     4, mode="ring"),
}
print("RESULT", json.dumps(out))
"""


@pytest.fixture(scope="module")
def counts():
    out = run_multidevice(BODY, ndev=4)
    (line,) = [ln for ln in out.splitlines() if ln.startswith("RESULT ")]
    return json.loads(line[len("RESULT "):])


@pytest.mark.parametrize("name", GRAPHS)
def test_distributed_equals_oracle_and_local(counts, name):
    c = counts[name]
    assert c["oracle"] > 0
    assert c["dist"] == c["again"] == c["local"] == c["oracle"]
    assert c["plan_id"] == "hedge/allgather/p4"


@pytest.mark.parametrize("name", GRAPHS)
def test_per_device_partials_sum_to_total_without_overflow(counts, name):
    c = counts[name]
    assert len(c["per_device"]) == 4
    assert sum(c["per_device"]) == c["dist"]
    assert not c["overflow"]


@pytest.mark.parametrize("name", GRAPHS)
def test_second_count_of_a_graph_traces_nothing(counts, name):
    assert counts[name]["traces_first"] == {"front": 1, "back": 1}
    assert counts[name]["traces_second"] == {"front": 0, "back": 0}


@pytest.mark.parametrize("name", GRAPHS)
def test_dist_counters_hold_plan_and_report(counts, name):
    c = counts[name]
    assert c["counters"] == {
        "dist.counts": 2,
        "dist.plans_profiled": 2,
        "dist.rows_planned": 2 * 4 * c["plan_rows"],
        "dist.rows_real": 2 * 4 * c["num_horizontal"],
        "dist.entries_planned": 2 * c["plan_entries"],
        "dist.wire_bytes": 2 * c["wire"],
    }
    # the measured plan ends at the first chunk past the real rows
    assert 0 < c["num_horizontal"] <= c["plan_rows"]
    assert c["plan_rows"] < c["num_horizontal"] + c["plan_chunk"]


@pytest.mark.parametrize("name", GRAPHS)
def test_measured_plan_gathers_no_more_than_static(counts, name):
    c = counts[name]
    assert 0 < c["plan_entries"] <= c["static_entries"]


def test_ring_mode_counts_p_rounds_of_rows(counts):
    r = counts["ring"]
    assert r["dist"] == counts["kron9"]["oracle"]
    assert r["plan_id"] == "hedge/ring/p4"
    assert r["counters"]["dist.plans_profiled"] == 0
    assert r["ran_static"]
    assert r["counters"]["dist.rows_planned"] == 4 * 4 * r["plan_rows"]
    assert r["counters"]["dist.rows_real"] == 4 * r["num_horizontal"]
    assert r["counters"]["dist.entries_planned"] == 4 * r["plan_entries"]
    # the same horizontal-edge volume crosses the wire in either mode
    assert r["counters"]["dist.wire_bytes"] == counts["kron9"]["wire"]


def test_distributed_spans_nest_inside_tc_count(tmp_path):
    import jax

    engine = TriangleEngine()
    edges, n = gen.rmat(7, 8, seed=3)
    engine.count((edges, n), route="distributed")  # compile outside
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span(tracing.WINDOW_SPAN):
            engine.count((edges, n), route="distributed")
    finally:
        jax.profiler.stop_trace()
    t = tracing.TraceSummary(
        tracing.load_xplane(tracing.find_xplane(tmp_path)))

    def spans(name):
        return [(s, e) for _, line, nm, s, e in t.host
                if nm == name and line == t.host_line]

    ((s0, e0),) = spans("tc.count")
    for name in DIST_SPANS:
        ((s, e),) = spans(name)
        assert s0 <= s <= e <= e0, name
