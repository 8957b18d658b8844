#!/usr/bin/env python3
"""Drive the cover-edge engine's main paths once on a TPU and check them
exactly.

    python chip_smoke.py              # one chip: local count, then serving
    python chip_smoke.py --chips 4    # Algorithm 2 over four chips, checked
                                      # against the one-chip local count
    python chip_smoke.py --scale 18   # RMAT scale of the local / four-chip
                                      # graph (default 16)

The graph is Graph500 Kronecker (R-MAT a=.57, b=.19, c=.19, edge factor
16, seed 0).  Every phase prints one JSON line.  The last line,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``,
is printed only when every phase ran and matched its reference.  Any
exception, mismatch or set overflow flag, and a run that finds no TPU,
exit non-zero without it.  One process holds the chip throughout.

The references are plain SciPy sparse products over the same edge list
(:func:`reference_counts`); they import nothing from this repository.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import numpy as np
import scipy.sparse as sp

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.api import TCOptions, TriangleEngine  # noqa: E402
from repro.graph.generators import rmat  # noqa: E402

#: probe rows per block on the local and four-chip routes.  At RMAT scale
#: 16 the widest bucket is 8192 candidates x 9856 targets, so one block
#: holds 4096 x (2 x 8192 + 9856) int32 — candidates, their BFS levels
#: and targets — 0.43 GB of the chip's 16 GB.
QUERY_CHUNK = 4096


class SmokeFailure(AssertionError):
    """A result that disagrees with its reference or its contract."""


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def _peak_bytes(device):
    """``peak_bytes_in_use`` of ``device``; ``None`` where the backend
    keeps no memory statistics (the CPU)."""
    return (device.memory_stats() or {}).get("peak_bytes_in_use")


def reference_counts(edges, n: int) -> tuple[int, np.ndarray]:
    """``(triangles, per-vertex triangle counts int64[n])`` of the simple
    undirected graph on ``edges`` (self-loops dropped, duplicates and
    both directions merged), by sparse matrix products.

    Vertices are ranked by degree and every edge points from the lower
    rank to the higher (``U = triu`` of the ranked adjacency), which
    keeps the products small around hubs.  ``X = (U @ U) * U`` counts
    each triangle once, at (lowest, highest) corner; ``Y = (U.T @ U) *
    U`` counts it once at (middle, highest).  A vertex's triangles are
    its row and column sums of ``X`` plus its row sums of ``Y``."""
    e = np.asarray(edges, np.int64).reshape(-1, 2)
    e = e[e[:, 0] != e[:, 1]]
    a = sp.coo_matrix(
        (np.ones(len(e), np.int64), (e[:, 0], e[:, 1])), shape=(n, n)
    ).tocsr()
    a = ((a + a.T) > 0).astype(np.int64)
    order = np.argsort(np.asarray(a.sum(axis=1)).ravel(), kind="stable")
    u = sp.triu(a[order][:, order], k=1, format="csr")
    x = (u @ u).multiply(u)
    y = (u.T @ u).multiply(u)
    ranked = (
        np.asarray(x.sum(axis=1)).ravel()
        + np.asarray(x.sum(axis=0)).ravel()
        + np.asarray(y.sum(axis=1)).ravel()
    )
    per_vertex = np.zeros(n, np.int64)
    per_vertex[order] = ranked
    return int(x.sum()), per_vertex


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def phase_local(edges, n, *, ref, query_chunk=QUERY_CHUNK, backend="auto",
                interpret=None) -> dict:
    """The exact count through ``TriangleEngine.count(route="local")``:
    triangles against the reference, ``c1``/``c2``/``k`` bit-identical
    to the jnp probe on the same device, and per-vertex credit (the
    hit-mask kernel) vertex by vertex."""
    tri_ref, pv_ref = ref

    def engine(**kw):
        kw = {"backend": backend, "interpret": interpret, **kw}
        return TriangleEngine(TCOptions(query_chunk=query_chunk, **kw))

    def count(eng):
        return eng.count((edges, n), route="local")

    eng = engine()
    rep, first_s = _timed(lambda: count(eng))
    warm, warm_s = _timed(lambda: count(eng))
    resolved = rep.options.resolved()
    _check(rep.backend == "pallas", f"backend {rep.backend!r}, not pallas")
    _check(resolved.interpret == bool(interpret),
           f"Pallas interpret={resolved.interpret}")
    for r in (rep, warm):
        _check(not r.overflow, f"overflow flagged: {r.overflow}")
    _check(rep.triangles == tri_ref,
           f"triangles {rep.triangles} != reference {tri_ref}")
    _check(rep.c1 + rep.c2 // 3 == rep.triangles,
           f"c1 + c2 // 3 = {rep.c1 + rep.c2 // 3} != {rep.triangles}")
    _check((warm.triangles, warm.c1, warm.c2, warm.k)
           == (rep.triangles, rep.c1, rep.c2, rep.k),
           "the warm call disagrees with the first")
    jnp_rep, jnp_s = _timed(lambda: count(engine(backend="jnp")))
    _check(not jnp_rep.overflow, "jnp overflow flagged")
    _check((jnp_rep.c1, jnp_rep.c2, jnp_rep.k) == (rep.c1, rep.c2, rep.k),
           f"jnp (c1, c2, k) {(jnp_rep.c1, jnp_rep.c2, jnp_rep.k)} != "
           f"pallas {(rep.c1, rep.c2, rep.k)}")
    pv, pv_s = _timed(lambda: count(engine(per_vertex=True)))
    _check(not pv.overflow, "per-vertex overflow flagged")
    _check(pv.triangles == tri_ref, "per-vertex run: triangles differ")
    bad = np.flatnonzero(np.asarray(pv.per_vertex) != pv_ref)
    _check(bad.size == 0,
           f"per-vertex credit differs at {bad.size} vertices, "
           f"first {bad[:5].tolist()}")
    return {
        "phase": "local", "n": n, "edge_rows": int(len(edges)),
        "triangles": rep.triangles, "c1": rep.c1, "c2": rep.c2,
        "k": rep.k, "backend": rep.backend,
        "interpret": resolved.interpret, "query_chunk": query_chunk,
        "first_call_s": first_s, "warm_call_s": warm_s,
        "jnp_call_s": jnp_s, "per_vertex_call_s": pv_s,
        "peak_bytes_in_use": _peak_bytes(jax.devices()[0]),
    }


def phase_serve(num_requests=64, *, batch_size=8, backend="auto",
                interpret=None) -> dict:
    """``engine.serve()`` over the seeded request mix: every request
    answered exactly, no failed batch, no rejection, no approximate
    answer."""
    from repro.launch.serve_tc import synth_requests

    reqs = synth_requests(num_requests, seed=0)
    eng = TriangleEngine(TCOptions(backend=backend, interpret=interpret))
    server = eng.serve(batch_size=batch_size)
    t0 = time.perf_counter()
    rids = [server.submit(e, n) for e, n in reqs]
    answers = {r.request_id: r for r in server.drain()}
    wall_s = time.perf_counter() - t0
    s = server.summary()
    _check(s["failed_batches"] == 0, f"{s['failed_batches']} failed batches")
    _check(s["rejected"] == 0, f"{s['rejected']} rejected requests")
    _check("approx" not in s["by_route"], f"approx answers: {s['by_route']}")
    _check(sorted(answers) == sorted(rids), "not every request was answered")
    for rid, (e, n) in zip(rids, reqs):
        r = answers[rid]
        want = reference_counts(e, n)[0]
        _check(r.triangles == want and not r.overflow,
               f"request {rid}: {r.triangles} != reference {want}")
    return {
        "phase": "serve", "requests": len(reqs), "exact": len(answers),
        "batch_size": batch_size, "batches": s["batches"],
        "by_route": s["by_route"], "jit_compiles": s["jit_compiles"],
        "p50_ms": s["p50_ms"], "p99_ms": s["p99_ms"], "wall_s": wall_s,
    }


def phase_distributed(edges, n, *, devices, ref, query_chunk=QUERY_CHUNK,
                      backend="auto", interpret=None) -> dict:
    """Algorithm 2 (``count(route="distributed")``) on a mesh over
    ``devices``, checked against the reference and against the local
    route on the first of those devices."""
    from jax.sharding import Mesh

    tri_ref = ref[0]
    mesh = Mesh(np.array(devices), ("p",))
    eng = TriangleEngine(
        TCOptions(query_chunk=query_chunk, backend=backend,
                  interpret=interpret),
        mesh=mesh,
    )
    rep, dist_s = _timed(
        lambda: eng.count((edges, n), route="distributed"))
    peaks = [_peak_bytes(d) for d in devices]
    _check(eng.mesh.devices.size == len(devices),
           f"mesh holds {eng.mesh.devices.size} devices")
    _check(rep.route == "distributed", f"answered on {rep.route!r}")
    _check(not rep.overflow, f"overflow flagged: {rep.overflow}")
    _check(len(rep.per_device) == len(devices),
           f"{len(rep.per_device)} per-device partials")
    _check(int(np.sum(rep.per_device)) == rep.triangles,
           "per-device partials do not sum to the count")
    _check(rep.triangles == tri_ref,
           f"distributed {rep.triangles} != reference {tri_ref}")
    if devices[0].platform != "cpu":  # the CPU keeps no memory stats
        _check(all((p or 0) > 0 for p in peaks),
               f"a device held nothing: peak bytes {peaks}")
    with jax.default_device(devices[0]):
        local, local_s = _timed(lambda: eng.count((edges, n), route="local"))
    _check(local.triangles == rep.triangles,
           f"local {local.triangles} != distributed {rep.triangles}")
    return {
        "phase": "distributed", "devices": len(devices), "n": n,
        "triangles": rep.triangles, "local_triangles": local.triangles,
        "per_device": [int(x) for x in rep.per_device],
        "backend": rep.backend, "mode": rep.options.mode,
        "comm_bytes": rep.comm.phase_bytes(),
        "distributed_call_s": dist_s, "local_call_s": local_s,
        "peak_bytes_in_use": peaks,
    }


def _emit(row: dict) -> None:
    print(json.dumps(row), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only Algorithm 2 across four chips and "
                         "what it is compared with")
    ap.add_argument("--scale", type=int, default=16,
                    help="RMAT scale of the local / four-chip graph")
    args = ap.parse_args(argv)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU — JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} devices", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    (edges, n), gen_s = _timed(lambda: rmat(args.scale, 16, seed=0))
    ref, ref_s = _timed(lambda: reference_counts(edges, n))
    _emit({"phase": "setup", "scale": args.scale, "n": n,
           "edge_rows": int(len(edges)), "reference_triangles": ref[0],
           "generate_s": gen_s, "reference_s": ref_s,
           "compile_cache": cache_dir})
    if args.chips == 1:
        _emit(phase_local(edges, n, ref=ref))
        _emit(phase_serve())
    else:
        _emit(phase_distributed(edges, n, devices=devices[:args.chips],
                                ref=ref))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": args.chips,
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
