"""An open loop of small graphs through ``TriangleEngine.serve()``.

The configuration fixes a pool of graphs; the seed relabels each one
and draws the order in which the pool is cycled and the order of the
gaps between arrivals.  The gaps are the pool-sized set of quantiles of
an exponential law at the traffic's rate, so every seed offers the same
Poisson-like load in another order.  Every request carries the
traffic's latency limit as its ``deadline_s``, so the server's deadline
flushing runs; the loop calls ``pump()`` between arrivals and waits in
slices of ``poll_s``.

Each request is timed from when it was due to when its answer came back
to the loop.  An answer that is not exact (approximate, rejected,
overflowed or missing) counts as missing the limit.

Set-up warms every program the window runs: the whole pool once, which
raises each budget cell's pooled degree bound to its final value, then
each cell at every power-of-two lane count below the batch size, as
partial flushes use them.
"""
from __future__ import annotations

import math
import time

import numpy as np
from jax.profiler import TraceAnnotation

from bench import graphs, reference

EXACT_ROUTES = ("batched", "distributed")


def setup(cfg, traffic, seed, *, variant=None):
    from repro.api import TCOptions, TriangleEngine

    rng = np.random.default_rng(seed)
    pool = graphs.load(cfg["generator"]).generate(cfg, cfg["graph_seed"])
    pool = [(graphs.relabel(e, n, rng), n) for e, n in pool]
    opts = dict(cfg["options"], **(variant or {}).get("options", {}))
    engine = TriangleEngine(TCOptions(**opts))
    server = engine.serve(batch_size=cfg["serve"]["batch_size"])
    st = {"pool": pool, "engine": engine, "server": server,
          "rate": traffic["rate_per_s"],
          "order": rng.permutation(len(pool)),
          "gaps": rng.permutation(_exp_quantiles(len(pool),
                                                 traffic["rate_per_s"])),
          "limit_s": traffic["latency_limit_ms"] * 1e-3,
          "poll_s": traffic["poll_ms"] * 1e-3,
          "grace_s": traffic["grace_s"],
          "requests": {}}
    with TraceAnnotation("bench.warmup"):
        _warm(st, cfg["serve"]["batch_size"])
    return st


def _exp_quantiles(k, rate):
    return -np.log1p(-(np.arange(k) + 0.5) / k) / rate


def _warm(st, batch_size):
    server, pool = st["server"], st["pool"]
    by_rows = sorted(range(len(pool)), key=lambda i: -len(pool[i][0]))
    cells: dict = {}
    for i in by_rows:
        e, n = pool[i]
        st["requests"][server.submit(e, n, deadline_s=st["limit_s"])] = i
        cells.setdefault(st["engine"].budgets.budget_for(n, len(e)),
                         []).append(i)
    server.drain()
    lanes = 1
    while lanes < batch_size:
        for members in cells.values():
            for i in members[:lanes]:
                e, n = pool[i]
                st["requests"][server.submit(e, n)] = i
            server.drain()
        lanes *= 2


def measure(st, seconds):
    server, pool = st["server"], st["pool"]
    n_gaps = int(2 * seconds * st["rate"]) + len(pool)
    due = np.cumsum(st["gaps"][np.arange(n_gaps) % len(pool)])
    due = due[due < seconds]
    before = server.summary()
    seen = len(server.results)
    back: dict = {}

    def collect():
        nonlocal seen
        now = time.perf_counter()
        for r in server.results[seen:]:
            back[r.request_id] = (now, r)
        seen = len(server.results)

    sent, late = [], []
    t0 = time.perf_counter()
    with TraceAnnotation("bench.window"):
        for k, offset in enumerate(due):
            while (now := time.perf_counter()) < t0 + offset:
                with TraceAnnotation("bench.pump"):
                    server.pump()
                collect()
                wait = t0 + offset - time.perf_counter()
                if wait > 0:
                    with TraceAnnotation("bench.wait"):
                        time.sleep(min(wait, st["poll_s"]))
            late.append(now - (t0 + offset))
            i = int(st["order"][k % len(pool)])
            e, n = pool[i]
            with TraceAnnotation("bench.submit"):
                rid = server.submit(e, n, deadline_s=st["limit_s"])
            sent.append((rid, i, t0 + offset))
            collect()
        close = time.perf_counter()
        with TraceAnnotation("bench.tail"):
            while (len(back) < len(sent)
                   and time.perf_counter() < close + st["grace_s"]):
                server.pump()
                collect()
                time.sleep(st["poll_s"])
        t_end = time.perf_counter()
    server.drain()
    collect()
    after = server.summary()
    lat = []
    for rid, i, t_due in sent:
        st["requests"][rid] = i
        t_back, r = back.get(rid, (None, None))
        ok = t_back is not None and t_back <= t_end and _exact(r)
        lat.append(t_back - t_due if ok else max(t_end - t_due,
                                                 2 * st["limit_s"]))
    batches = after["batches"] - before["batches"]
    return {
        "window_s": t_end - t0,
        "end_to_end": {"serve_p95_ms": 1e3 * _p95(lat)},
        "counters": {
            "requests": len(sent),
            "lanes_per_flush": (after["completed"] - before["completed"])
            / max(batches, 1),
            "gen_late_p95_ms": 1e3 * _p95(late),
            "compiles_in_window": after["jit_compiles"]
            - before["jit_compiles"],
            "deadline_flushes": after["deadline_flushes"]
            - before["deadline_flushes"],
            "size_flushes": after["size_flushes"] - before["size_flushes"],
        },
    }


def _exact(r) -> bool:
    return (getattr(r, "route", None) in EXACT_ROUTES
            and not r.overflow and r.approx is None)


def _p95(xs) -> float:
    xs = sorted(xs)
    return xs[max(0, math.ceil(0.95 * len(xs)) - 1)] if xs else 0.0


def release(st):
    st["server"].drain()
    st["results"] = {r.request_id: r for r in st["server"].results}
    st.pop("server")
    st.pop("engine")


def check(st):
    """Every answered request, warm-up included, against the reference:
    ``(attempted, failed, {name: (value, limit)})``."""
    want = [reference.triangles(e, n) for e, n in st["pool"]]
    wrong = not_exact = 0
    for rid, i in st["requests"].items():
        r = st["results"].get(rid)
        if r is None or not _exact(r):
            not_exact += 1
        elif r.triangles != want[i]:
            wrong += 1
    return len(st["requests"]), wrong + not_exact, {
        "wrong_answers": (wrong, 0),
        "not_exact": (not_exact, 0),
    }
