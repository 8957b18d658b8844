"""Option parity: every ``TCOptions`` value a count route takes gives
the exact answer.

One parametrised test.  Each case sets one field away from its default,
counts a small skewed graph (R-MAT) or a uniform one (Erdős–Rényi)
through ``TriangleEngine`` on one route, and checks four things against
the brute-force oracle: the total, the per-vertex credit where the route
returns it, clear overflow flags, and the route (and plan) that
answered.  The distributed cases run together in one subprocess on four
host devices, which prints every answer as JSON.

A field the engine comes to derive loses its cases here together with
the field.
"""
from __future__ import annotations

import json

import numpy as np
import pytest

from repro.api import TCOptions, TriangleEngine
from repro.graph import generators as gen
from tests import oracle
from tests.test_parallel_tc import run_multidevice

GRAPHS = {
    "rmat": lambda: gen.rmat(7, 6, seed=2),  # skewed, max degree 67
    "er": lambda: gen.erdos_renyi(160, 0.06, seed=4),  # max degree 17
}

#: ``(field, value)`` per route
FIELDS = {
    "local": (
        ("backend", "jnp"),
        ("backend", "pallas"),
        ("query_chunk", 24),
        ("bucket_widths", (4, 8, 16)),
        ("bucket_widths", (16,)),
        ("row_mult", 8),
        ("compact", False),
        ("root", 7),
        ("per_vertex", True),
    ),
    "batch": (
        ("backend", "jnp"),
        ("backend", "pallas"),
        ("bucket_widths", (4, 8, 16)),
    ),
    "distributed": (
        ("mode", "allgather"),
        ("slack", 2.0),
        ("slack", 8.0),
        ("d_pad", 128),
        ("hedge_chunk", 32),
        ("hedge_chunk", 100),
        ("frontier_dtype", "uint8"),
        ("gather_buffer_limit_bytes", 1),  # auto picks ring
    ),
}

CASES = [
    (route, field, value, graph)
    for route, fields in FIELDS.items()
    for field, value in fields
    for graph in GRAPHS
]


def case_id(route, field, value, graph) -> str:
    return f"{route}-{field}={value}-{graph}".replace(" ", "")


def answer(engine, route, field, value, graph) -> dict:
    """Count one case and keep what the test checks, as plain JSON."""
    edges, n = GRAPHS[graph]()
    rep = engine.count((edges, n), route=route,
                       options=TCOptions(**{field: value}))
    return {
        "triangles": rep.triangles,
        "per_vertex": (None if rep.per_vertex is None
                       else [int(x) for x in rep.per_vertex]),
        "overflow": [rep.overflow.h, rep.overflow.transpose,
                     rep.overflow.hedge],
        "num_horizontal": rep.num_horizontal,
        "route": rep.route,
        "backend": rep.backend,
        "plan_id": rep.plan_id,
    }


BODY = """
import json
import jax, numpy as np
from jax.sharding import Mesh
from repro.api import TriangleEngine
from tests.test_option_parity import CASES, answer, case_id

engine = TriangleEngine(mesh=Mesh(np.array(jax.devices()[:4]), ("p",)))
out = {case_id(*c): answer(engine, *c) for c in CASES
       if c[0] == "distributed"}
print("RESULT", json.dumps(out))
"""


@pytest.fixture(scope="module")
def distributed_answers():
    out = run_multidevice(BODY, ndev=4)
    (line,) = [ln for ln in out.splitlines() if ln.startswith("RESULT ")]
    return json.loads(line[len("RESULT "):])


@pytest.fixture(scope="module")
def engine():
    return TriangleEngine()


def _plan_prefix(route, field, value) -> str:
    """What the answering route writes first in ``plan_id``."""
    if route == "distributed":
        mode = "ring" if field == "gather_buffer_limit_bytes" else "allgather"
        return f"hedge/{mode}/p4"
    backend = value if field == "backend" else "jnp"
    return f"{'exact' if route == 'local' else 'bounded'}/{backend}"


@pytest.mark.parametrize(
    "route,field,value,graph", CASES,
    ids=[case_id(*c) for c in CASES],
)
def test_option_value_counts_exactly(request, route, field, value, graph):
    if route == "distributed":
        got = request.getfixturevalue("distributed_answers")[
            case_id(route, field, value, graph)]
    else:
        got = answer(request.getfixturevalue("engine"), route, field, value,
                     graph)
    edges, n = GRAPHS[graph]()
    want = oracle.triangle_counts(edges, n)
    assert got["triangles"] == int(want.sum()) // 3 > 0
    assert not any(got["overflow"])
    assert got["route"] == route
    assert got["plan_id"].startswith(_plan_prefix(route, field, value))
    if field == "per_vertex":
        assert got["per_vertex"] is not None
    if got["per_vertex"] is not None:
        np.testing.assert_array_equal(got["per_vertex"], want)
    if field == "query_chunk":  # some bucket ends in a ragged chunk
        assert got["num_horizontal"] % value != 0
    if route == "batch" and field == "bucket_widths":
        # "bounded/<backend>/<rows>x<width>+...", widest first: the
        # padded max degree, then the call's widths
        _, *narrow = (int(b.split("x")[1])
                      for b in got["plan_id"].split("/")[2].split("+"))
        assert narrow and set(narrow) <= set(value)
