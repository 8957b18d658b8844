"""Exact counts back to back through ``TriangleEngine.count`` on the
distributed route (Algorithm 2), over a 1-D mesh ``p`` of the
configuration's first ``mesh_chips`` devices.

The mix is ``count``'s (``bench/drivers/count.py``): set-up makes the
configuration's graph, relabels it from the seed, builds the engine and
makes one whole count, which traces and compiles the Algorithm 2
program; the window then counts the same edge list again and again,
ingest and sharding included.  A count begins only while the window is
open, and the window closes when the last count that began in it ends.
``count.py`` builds its engine over the lazy all-device mesh, hence
this driver.

After the window every count, the warm-up's too, is compared with the
reference: its total, its overflow flags, the route that answered, and
whether its per-chip partials sum to its total.
"""
from __future__ import annotations

import time

import numpy as np
from jax.profiler import TraceAnnotation

from bench import graphs, reference
from bench.drivers.count import _context, release  # noqa: F401


def setup(cfg, traffic, seed, *, variant=None):
    import jax
    from jax.sharding import Mesh

    from repro.api import TCOptions, TriangleEngine

    variant = variant or {}
    ((edges, n),) = graphs.load(cfg["generator"]).generate(
        cfg, cfg["graph_seed"])
    edges = graphs.relabel(edges, n, np.random.default_rng(seed))
    devices = jax.devices()[:cfg["mesh_chips"]]
    opts = dict(cfg["options"], **variant.get("options", {}))
    st = {"edges": edges, "n": n, "route": traffic["route"],
          "call_route": variant.get("route", traffic["route"]),
          "chips": len(devices),
          "engine": TriangleEngine(
              TCOptions(**opts), mesh=Mesh(np.array(devices), ("p",))),
          "answers": []}
    with TraceAnnotation("bench.warmup"):
        rep = _count(st)
    st["plan_id"] = rep.plan_id
    st["context"] = _context(edges, n, None)
    return st


def _count(st):
    rep = st["engine"].count((st["edges"], st["n"]), route=st["call_route"])
    parts = (None if rep.per_device is None
             else int(np.sum(rep.per_device, dtype=np.int64)))
    st["answers"].append(
        (int(rep.triangles), bool(rep.overflow), rep.route, parts))
    return rep


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
        "counters": dict(st["context"], counts=counts, n=st["n"],
                         chips=st["chips"], plan_id=st["plan_id"]),
    }


def check(st):
    """Every count against the reference: ``(attempted, failed,
    {name: (value, limit)})``.  A count with no per-chip partials (from
    another route) counts as unequal."""
    want = reference.triangles(st["edges"], st["n"])
    got = st["answers"]
    return len(got), sum(t != want or o or r != st["route"] or s != t
                         for t, o, r, s in got), {
        "count_error": (max(abs(t - want) for t, _, _, _ in got), 0),
        "overflow_flags": (sum(o for _, o, _, _ in got), 0),
        "other_route": (sum(r != st["route"] for _, _, r, _ in got), 0),
        "unequal_per_device": (sum(s != t for t, _, _, s in got), 0),
    }
