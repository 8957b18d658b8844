"""Exact counts back to back through ``TriangleEngine.count``.

Set-up makes the configuration's graph, relabels it from the seed,
builds the engine with the configuration's options and makes one whole
count, which compiles every program the window runs.  The window then
counts the same edge list again and again, ingest included, as a user
who hands the engine an edge list pays it.  A count begins only while
the window is open, and the window closes when the last count that
began in it ends: ``count_s`` is the window over the counts in it.

After the window every count, the warm-up's too, is compared with the
reference: its total, its overflow flag and the route that answered.
"""
from __future__ import annotations

import time

import numpy as np
from jax.profiler import TraceAnnotation

from bench import graphs, reference


def setup(cfg, traffic, seed, *, variant=None):
    from repro.api import TCOptions, TriangleEngine

    variant = variant or {}
    ((edges, n),) = graphs.load(cfg["generator"]).generate(
        cfg, cfg["graph_seed"])
    edges = graphs.relabel(edges, n, np.random.default_rng(seed))
    opts = dict(cfg["options"], **variant.get("options", {}))
    st = {"edges": edges, "n": n, "route": traffic["route"],
          "call_route": variant.get("route", traffic["route"]),
          "engine": TriangleEngine(TCOptions(**opts)), "answers": []}
    with TraceAnnotation("bench.warmup"):
        rep = _count(st)
    st["context"] = _context(edges, n, rep.levels)
    return st


def _count(st):
    rep = st["engine"].count((st["edges"], st["n"]), route=st["call_route"])
    st["answers"].append((int(rep.triangles), bool(rep.overflow), rep.route))
    return rep


def _context(edges, n, levels):
    """The graph's unique undirected edges ``m``, and, as context and
    not a metric, its cover-edges (endpoints on one BFS level) with the
    int32 bytes of both endpoints' lists over them."""
    e = np.sort(np.asarray(edges, np.int64), axis=1)
    e = np.unique(e[e[:, 0] != e[:, 1]], axis=0)
    out = {"m": len(e)}
    if levels is not None:
        deg = np.bincount(e.ravel(), minlength=n)
        lev = np.asarray(levels)
        h = e[lev[e[:, 0]] == lev[e[:, 1]]]
        out.update(cover_edges=len(h), query_list_bytes=int(
            4 * (deg[h[:, 0]] + deg[h[:, 1]]).sum()))
    return out


def measure(st, seconds):
    before = len(st["answers"])
    t0 = time.perf_counter()
    with TraceAnnotation("bench.window"):
        while time.perf_counter() - t0 < seconds:
            with TraceAnnotation("bench.count"):
                _count(st)
        t1 = time.perf_counter()
    counts = len(st["answers"]) - before
    return {
        "window_s": t1 - t0,
        "end_to_end": {"count_s": (t1 - t0) / counts},
        "counters": dict(st["context"], counts=counts, n=st["n"]),
    }


def release(st):
    st.pop("engine", None)


def check(st):
    """Every count against the reference: ``(attempted, failed,
    {name: (value, limit)})``."""
    want = reference.triangles(st["edges"], st["n"])
    got = st["answers"]
    return len(got), sum(t != want or o or r != st["route"]
                         for t, o, r in got), {
        "count_error": (max(abs(t - want) for t, _, _ in got), 0),
        "overflow_flags": (sum(o for _, o, _ in got), 0),
        "other_route": (sum(r != st["route"] for _, _, r in got), 0),
    }
