"""Algorithm 2 — communication-optimal parallel cover-edge triangle counting.

SPMD mapping of the paper onto a 1-D device axis via ``shard_map``
(DESIGN.md §3 walks the whole chain):

  line 2      parallel BFS            -> ``bfs_levels(axis_name=...)``
                                         (one int32 pmax of the level vector
                                         per BFS level)
  lines 3-5   modified neighborhoods  -> drop (v, w) pairs with
                                         horizontal & v < w from the local
                                         CSR shard (N-hat has (2-k)m entries)
  lines 6-28  sample-sort transpose   -> ``repartition_by_value`` (regular
                                         sampling, ONE all_to_all)
  lines 29-43 horizontal-edge rounds  -> all_gather of the horizontal-edge
                                         shard (volume k·m·p, same as the
                                         paper's p-round pairwise swap),
                                         then purely-local planned-bucket
                                         intersections of the transposed
                                         sublists through the shared engine
                                         (``core.intersect.run_plan`` over a
                                         ``PairListAdjacency`` view)
  line 44     reduction               -> psum

Because the modified neighborhoods break symmetry, every triangle is
counted exactly once (no /3 here — that dedup is the point of N-hat).

All shapes are static; the two data-dependent capacities carry overflow
flags (regular sampling bounds any receiver at 2x the average — the flags
make the bound *checked* instead of assumed).  The intersection plan is
likewise static: ``plan_hedge_rounds`` sizes its degree buckets, their
target bands and its total rows on the host from the graph's degree
histogram (an upper bound valid for any BFS), and ``run_plan``
degree-sorts each gathered round in-trace so every query provably fits
its bucket — width mis-fits, and more horizontal edges than a ring plan
has rows, flag overflow instead of miscounting.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import obs
from repro.core.bfs import UNVISITED, bfs_levels
from repro.core.comm_instrument import CommTally, tally_comm
from repro.core.edges import degree_exceedance, horizontal_mask
from repro.core.intersect import (
    DEFAULT_BUCKET_WIDTHS,
    IntersectPlan,
    PairListAdjacency,
    plan_buckets_bounded,
    resolve_backend,
    run_plan,
    target_band_edges,
)
from repro.core.sampling import repartition_by_value
from repro.graph.csr import Graph, _ceil_to, max_degree
from repro.graph.partition import shard_edges


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ParallelTCResult:
    triangles: jnp.ndarray
    per_device: jnp.ndarray   # t_i
    k: jnp.ndarray            # measured horizontal fraction
    num_horizontal: jnp.ndarray
    transpose_overflow: jnp.ndarray
    hedge_overflow: jnp.ndarray
    recv_counts: jnp.ndarray  # transposed elements per device
    comm: CommTally           # per-phase wire bytes this run moved
    per_vertex: jnp.ndarray | None = None  # int32[n] exactly-once credit
    #   (psum over shards, replicated); None unless per_vertex was
    #   requested — sum == 3 * triangles


def result_out_specs(axis_name: str = "p", per_vertex: bool = False):
    """``shard_map`` out_specs pytree for ``_tc_shard``'s result —
    per-device fields sharded over ``axis_name``, everything else
    (scalars + the comm tally) replicated.  The ONE definition shared
    by ``parallel_triangle_count`` and the comm instrument, so adding a
    result field cannot silently desynchronize them.  ``per_vertex``
    must match the shard fn's flag: the spec pytree has to mirror the
    result's (``None`` when attribution is off, a replicated vector —
    it is psummed in the body — when on)."""
    rep = P()
    return ParallelTCResult(
        triangles=rep,
        per_device=P(axis_name),
        k=rep,
        num_horizontal=rep,
        transpose_overflow=rep,
        hedge_overflow=rep,
        recv_counts=P(axis_name),
        comm=CommTally(
            **{f.name: rep for f in dataclasses.fields(CommTally)}
        ),
        per_vertex=rep if per_vertex else None,
    )


def _capacities(m2: int, p: int, slack: float) -> tuple[int, int, int]:
    """Static capacities for a (n, 2m) graph on p devices: per-device edge
    slots, per-destination transpose chunk, horizontal-edge buffer.
    Only ``cap_chunk`` depends on ``slack``; ``cap_edges``/``cap_hedge``
    are pure functions of (m2, p), so the intersection plan and the shard
    body always agree on the horizontal buffer size."""
    cap_edges = max(1, math.ceil(m2 / p * 2))
    cap_chunk = max(4, math.ceil(slack * m2 / (p * p)))
    cap_hedge = cap_edges // 2 + 1
    return cap_edges, cap_chunk, cap_hedge


def _hedge_layout(
    m2: int, p: int, mode: str, hedge_chunk: int | None
) -> tuple[int, int]:
    """``(rows, chunk)`` of one horizontal round's query block — the ONE
    place this layout is computed, shared by ``plan_hedge_rounds`` and
    ``build_tc_shard_fn`` so the plan and the shard body cannot drift.

    ``chunk`` is both the fori-loop probe slice and the bucket-row
    granularity (``row_mult == query_chunk`` keeps every bucket a whole
    number of chunks).  The ``None`` default caps it at 1024 rather than
    the whole buffer: a whole-buffer granularity would collapse the plan
    to a single max-width bucket and silently give the hub padding back.
    """
    _, _, cap_hedge = _capacities(m2, p, slack=4.0)
    chunk = int(hedge_chunk) if hedge_chunk else min(cap_hedge, 1024)
    rows = p * cap_hedge if mode == "allgather" else cap_hedge
    return rows, chunk


def _least_plan_rows(m2: int, p: int, mode: str,
                     hedge_chunk: int | None) -> int:
    """The fewest rows a horizontal-round plan of ``plan_hedge_rounds``
    can end at: every undirected edge can be horizontal, and the
    gathered block holds each once; a ring block holds one shard's, and
    the fullest shard holds at least ``ceil(m / p)``."""
    rows, chunk = _hedge_layout(m2, p, mode, hedge_chunk)
    m = m2 // 2
    real = m if mode == "allgather" else -(-m // p)
    return min(rows, _ceil_to(real, chunk)) if real else 0


def plan_hedge_rounds(
    g: Graph,
    p: int,
    *,
    mode: str = "allgather",
    hedge_chunk: int | None = None,
    d_pad: int | None = None,
    bucket_widths: tuple[int, ...] = DEFAULT_BUCKET_WIDTHS,
    intersect_backend: str = "jnp",
    interpret: bool = True,
    shards=None,
) -> IntersectPlan:
    """The static intersection plan for Algorithm 2's horizontal rounds.

    One query block per round: the full gathered horizontal edge set
    (``allgather`` mode — p·cap_hedge rows, executed once) or one
    device's shard (``ring`` mode — cap_hedge rows, executed p times).
    Candidate buckets, target bands and the plan's last row come from
    degree-histogram exceedance bounds (``degree_exceedance``): any
    BFS's horizontal subset is bounded by the edges present — the whole
    graph for the gathered block, the per-shard max for ring blocks,
    ~p× tighter — so the plan is safe for whatever roots/levels the run
    produces.  The bounds count global degrees, and a device's sublist
    of a vertex is never longer than its degree, so every bound of the
    form "rows whose list exceeds E" holds for the local lists the
    devices probe.  The plan ends at the rows that can be real (at most
    m undirected edges, each once; the fullest shard's in ring mode):
    about half the gathered block, whose rows hold a device's buffer
    padding too.  ``hedge_chunk`` sets both the probe slice and the
    bucket-row granularity (small-cap_hedge/high-p runs coarsen to
    whole-buffer buckets).  ``shards``: optional pre-sharded
    ``(src[p, cap], dst[p, cap])`` for the ring bounds
    (``parallel_triangle_count`` passes its own to avoid sharding twice);
    the planner only reads edge content, so any capacity works.
    Exposed publicly so benchmarks and examples can introspect exactly
    the bucket layout the distributed path will execute.
    """
    import numpy as np

    m2 = int(jax.device_get(g.n_edges_dir))
    if d_pad is None:
        d_pad = max(1, max_degree(g))
    rows, chunk = _hedge_layout(m2, p, mode, hedge_chunk)
    widths = tuple(sorted(
        w for w in {int(w) for w in bucket_widths} if 0 < w < d_pad
    ))
    edges = (0,) + target_band_edges(d_pad)
    if mode == "ring":
        if shards is None:
            shards = shard_edges(g, p, capacity=None)[:2]
        src, dst = shards
    else:
        src, dst = (np.asarray(x)[None]
                    for x in jax.device_get((g.src, g.dst)))
    small, large = degree_exceedance(
        src, dst, jax.device_get(g.deg), widths=widths, edges=edges
    )
    return plan_buckets_bounded(
        rows,
        d_pad=d_pad,
        exceed=tuple(zip(widths, small)),
        exceed_large=tuple(zip(edges, large)),
        bucket_widths=widths,
        row_mult=chunk,
        backend=intersect_backend,
        interpret=interpret,
        query_chunk=chunk,
    )


def _tc_shard(
    src_i,
    dst_i,
    *,
    n: int,
    p: int,
    root: int,
    cap_chunk: int,
    cap_hedge: int,
    hplan: IntersectPlan,
    axis_name: str,
    mode: str = "allgather",
    frontier_dtype: str = "int32",
    per_vertex: bool = False,
):
    """Per-device body. ``src_i/dst_i`` int32[cap_edges] sentinel-padded.

    Besides the count, the result carries a ``CommTally``: per-phase
    wire bytes of this very run, computed from the static capacities
    plus the BFS sweep count (the one data-dependent factor — every
    sweep is one frontier pmax).  ``tests/test_comm_instrument.py``
    asserts the tally equals the per-collective volumes extracted from
    the lowered program, so the collective inventory below cannot drift
    from the accounting silently (see ``comm_model.NUM_SCALAR_REDUCES``
    when adding or removing a scalar psum/pmax here)."""
    inf = n + 1
    # ---- line 2: parallel BFS + horizontal marking -------------------
    with jax.named_scope("bfs"):
        level = bfs_levels(src_i, dst_i, n, root=root, axis_name=axis_name,
                           frontier_dtype=frontier_dtype)
        horiz = horizontal_mask(src_i, dst_i, level, n)
    valid = (src_i < n) & (dst_i < n)

    # ---- lines 3-5: modified neighborhoods N-hat ---------------------
    keep = valid & ~(horiz & (src_i < dst_i))
    # ---- lines 6-28: sample-sort transpose by neighbor value ---------
    with jax.named_scope("transpose"):
        rep = repartition_by_value(
            values=jnp.where(keep, dst_i, inf),
            carry=jnp.where(keep, src_i, inf),
            valid=keep,
            p=p,
            cap_chunk=cap_chunk,
            axis_name=axis_name,
            inf=inf,
        )
    # received pairs (owner v = carry, value x) sorted by (v, x) — exactly
    # the engine's pair-list adjacency view; sublist(v) is a sorted slice
    adj = PairListAdjacency(owners=rep.carry, values=rep.values, n_nodes=n)

    # ---- lines 29-43: horizontal-edge exchange + planned intersections
    is_h = horiz & (src_i < dst_i)
    with jax.named_scope("hedge"):
        order = jnp.argsort(~is_h, stable=True)
        hv = jnp.where(is_h[order], src_i[order], inf)[:cap_hedge]
        hw = jnp.where(is_h[order], dst_i[order], inf)[:cap_hedge]
        n_h_local = jnp.sum(is_h, dtype=jnp.int32)
        # a ring round probes one device's block, and a plan that ends
        # before it skips the rows past its end (``build_tc_shard_fn``
        # refuses a plan too short for the gathered block: m rows)
        limit = (cap_hedge if mode == "allgather"
                 else min(cap_hedge, hplan.total_rows))
        hedge_overflow = (
            jax.lax.pmax((n_h_local > limit).astype(jnp.int32),
                         axis_name) > 0
        )

    # fori_loop carries must be device-varying from the start (shard_map vma)
    t0 = jax.lax.pvary(jnp.int32(0), (axis_name,))
    o0 = jax.lax.pvary(jnp.bool_(False), (axis_name,))
    if mode == "allgather":
        # one collective, volume k·m·p — identical to the paper's p rounds
        with jax.named_scope("hedge"):
            all_hv = jax.lax.all_gather(hv, axis_name).reshape(-1)
            all_hw = jax.lax.all_gather(hw, axis_name).reshape(-1)
        with jax.named_scope("probe"):
            eng = run_plan(adj, all_hv, all_hw, hplan, per_vertex=per_vertex)
        t_i = t0 + eng.c1
        d_ovf = o0 | eng.overflow
        credit = eng.per_vertex
    elif mode == "ring":
        # probe the local shard, then p-1 ppermute rounds: O(cap_hedge)
        # memory, intersection of round r overlaps with the transfer of
        # round r+1 (the paper's lines 36-42).  Exactly p-1 permutes —
        # a p-th would only return the buffers to their origin, moving
        # k·m wire for nothing (and breaking the wire-volume equality
        # with allgather mode that the comm instrument asserts).
        perm = [(i, (i + 1) % p) for i in range(p)]
        with jax.named_scope("probe"):
            eng0 = run_plan(adj, hv, hw, hplan, per_vertex=per_vertex)

        def round_body(r, carry):
            t, o, cv, cw = carry[:4]
            with jax.named_scope("hedge"):
                cv = jax.lax.ppermute(cv, axis_name, perm)
                cw = jax.lax.ppermute(cw, axis_name, perm)
            with jax.named_scope("probe"):
                eng = run_plan(adj, cv, cw, hplan, per_vertex=per_vertex)
            out = (t + eng.c1, o | eng.overflow, cv, cw)
            return out + (
                (carry[4] + eng.per_vertex,) if per_vertex else ()
            )

        init = (t0 + eng0.c1, o0 | eng0.overflow, hv, hw) + (
            (eng0.per_vertex,) if per_vertex else ()
        )
        res = jax.lax.fori_loop(0, p - 1, round_body, init)
        t_i, d_ovf = res[0], res[1]
        credit = res[4] if per_vertex else None
    else:
        raise ValueError(mode)

    # ---- line 44: reduction -------------------------------------------
    with jax.named_scope("reduce"):
        d_overflow = jax.lax.pmax(d_ovf.astype(jnp.int32), axis_name) > 0
        T = jax.lax.psum(t_i, axis_name)
        # per-vertex credit is shard-local partials under N-hat's
        # exactly-once semantics: one n-vector psum (the "one extra
        # collective" of the attribution feature — priced as phase
        # "reduce" by the tally AND the HLO pricer; drop the engine's
        # sentinel slot before reducing)
        pv = (
            jax.lax.psum(credit[:n], axis_name) if per_vertex else None
        )
        n_h = jax.lax.psum(n_h_local, axis_name)
        m = jax.lax.psum(jnp.sum(valid & (src_i < dst_i), dtype=jnp.int32),
                         axis_name)
    k = n_h / jnp.maximum(m, 1)
    # every BFS sweep ran one frontier pmax and assigned level cur+1 to
    # at least one vertex (reseeds included), so sweeps = max level + 1;
    # level is pmax-synced, hence replicated, hence so is the tally
    sweeps = jnp.max(jnp.where(level == UNVISITED, 0, level)) + 1
    comm = tally_comm(
        n=n, p=p, cap_chunk=cap_chunk, cap_hedge=cap_hedge, mode=mode,
        frontier_dtype=frontier_dtype, sweeps=sweeps,
        per_vertex=per_vertex,
    )
    return ParallelTCResult(
        triangles=T,
        per_device=t_i.reshape(1),
        k=k,
        num_horizontal=n_h,
        transpose_overflow=rep.overflow | d_overflow,
        hedge_overflow=hedge_overflow,
        recv_counts=rep.count.reshape(1),
        comm=comm,
        per_vertex=pv,
    )


def build_tc_shard_fn(
    *,
    n: int,
    m2: int,
    p: int,
    axis_name: str = "p",
    root: int = 0,
    slack: float = 4.0,
    d_pad: int = 256,
    mode: str = "allgather",
    hedge_chunk: int | None = None,
    frontier_dtype: str = "int32",
    hplan: IntersectPlan | None = None,
    intersect_backend: str = "jnp",
    interpret: bool = True,
    per_vertex: bool = False,
):
    """Shard function + static capacities for a graph of (n, 2m) size —
    usable for dry-run lowering with ShapeDtypeStructs (no graph data).

    ``hplan`` is the horizontal-round intersection plan; ``None`` builds
    the degenerate single-bucket-at-``d_pad`` plan, which needs no graph
    data and is always safe (``parallel_triangle_count`` passes the
    degree-bucketed plan from ``plan_hedge_rounds`` instead).
    """
    cap_edges, cap_chunk, cap_hedge = _capacities(m2, p, slack)
    rows, chunk = _hedge_layout(m2, p, mode, hedge_chunk)
    if hplan is None:
        hplan = plan_buckets_bounded(
            rows, d_pad=d_pad, exceed=None, row_mult=chunk,
            backend=intersect_backend, interpret=interpret,
            query_chunk=chunk,
        )
    elif hplan.total_rows < _least_plan_rows(m2, p, mode, hedge_chunk):
        # run_plan probes only plan.total_rows rows — a plan shorter than
        # the rows that can be real (e.g. built for ring, used for
        # allgather) is refused here; the shard flags the rest at run time
        raise ValueError(
            f"hplan covers {hplan.total_rows} rows but mode={mode!r} "
            f"blocks of {rows} rows can hold "
            f"{_least_plan_rows(m2, p, mode, hedge_chunk)} real ones "
            f"(plan_hedge_rounds mode mismatch?)"
        )
    fn = functools.partial(
        _tc_shard, n=n, p=p, root=root, cap_chunk=cap_chunk,
        cap_hedge=cap_hedge, hplan=hplan, axis_name=axis_name, mode=mode,
        frontier_dtype=frontier_dtype, per_vertex=per_vertex,
    )
    return fn, cap_edges


@functools.partial(jax.jit, static_argnames=("mesh", "body"))
def _tc_distributed(src, dst, *, mesh: Mesh, body: tuple) -> ParallelTCResult:
    """Algorithm 2 as one program: ``_tc_shard`` on every device of
    ``mesh``'s one axis.  ``body`` is ``_tc_shard``'s static keywords as
    sorted ``(name, value)`` pairs (n, p, root, capacities, the plan,
    mode, frontier dtype, per_vertex, axis name); with the mesh it keys
    the program, so repeated counts of graphs of one static shape trace
    and lower it once.  Device traces name the program after this
    function."""
    kw = dict(body)
    axis = kw["axis_name"]
    return jax.shard_map(
        functools.partial(_tc_shard, **kw),
        mesh=mesh,
        in_specs=(P(axis), P(axis)),
        out_specs=result_out_specs(axis, per_vertex=kw["per_vertex"]),
    )(src, dst)


def _parallel_triangle_count(
    g: Graph, mesh: Mesh, *, axis_name: str = "p", options
) -> tuple[ParallelTCResult, IntersectPlan]:
    """Algorithm 2 impl — ``options`` is a ``repro.api.TCOptions`` with
    ``mode`` already resolved to ``"allgather"`` or ``"ring"`` (the
    ``"auto"`` hedge-mode policy lives in the engine,
    ``TriangleEngine.count_distributed_raw``).  Returns the result and
    the horizontal-round plan every device ran."""
    o = options
    if o.mode not in ("allgather", "ring"):
        raise ValueError(
            f"hedge mode must be resolved before the impl; got {o.mode!r}"
        )
    backend, interpret = resolve_backend(o.backend, o.interpret)
    root, slack, mode = int(o.root), float(o.slack), o.mode
    hedge_chunk, bucket_widths = o.hedge_chunk, o.bucket_widths
    frontier_dtype, d_pad = o.frontier_dtype, o.d_pad
    p = mesh.shape[axis_name]
    m2 = int(jax.device_get(g.n_edges_dir))
    if d_pad is None:
        d_pad = max(1, max_degree(g))
    # shard once: the same host-side pass feeds the shard_map inputs AND
    # the ring plan's per-shard degree bounds
    cap_edges = _capacities(m2, p, slack)[0]
    sharding = NamedSharding(mesh, P(axis_name))
    with obs.span("tc.shard"):
        s_sh, d_sh, _, _ = shard_edges(g, p, capacity=cap_edges)
        s_dev = jax.device_put(s_sh.reshape(-1), sharding)
        d_dev = jax.device_put(d_sh.reshape(-1), sharding)
    with obs.span("tc.plan_layout"):
        hplan = plan_hedge_rounds(
            g, p, mode=mode, hedge_chunk=hedge_chunk, d_pad=d_pad,
            bucket_widths=bucket_widths, intersect_backend=backend,
            interpret=interpret, shards=(s_sh, d_sh),
        )
    # every resolved knob goes to the builder: with hplan given the
    # backend pair only seeds the (unused) fallback plan, but dropping
    # them here is exactly how a future fallback path would silently
    # ignore the caller's choice — plumb all three
    fn, _ = build_tc_shard_fn(
        n=g.n_nodes, m2=m2, p=p, axis_name=axis_name, root=root, slack=slack,
        d_pad=d_pad, mode=mode, hedge_chunk=hedge_chunk, hplan=hplan,
        intersect_backend=backend, interpret=interpret,
        frontier_dtype=frontier_dtype, per_vertex=bool(o.per_vertex),
    )
    with obs.span("tc.probe"):
        res = _tc_distributed(s_dev, d_dev, mesh=mesh,
                              body=tuple(sorted(fn.keywords.items())))
    return res, hplan


def parallel_triangle_count(
    g: Graph,
    mesh: Mesh,
    *,
    axis_name: str = "p",
    root: int = 0,
    slack: float = 4.0,
    d_pad: int | None = None,
    mode: str = "allgather",
    hedge_chunk: int | None = None,
    bucket_widths: tuple[int, ...] = DEFAULT_BUCKET_WIDTHS,
    intersect_backend: str = "auto",
    interpret: bool | None = None,
    frontier_dtype: str = "int32",
) -> ParallelTCResult:
    """DEPRECATED shim — use ``repro.api.TriangleEngine.count`` with
    ``route="distributed"`` (or ``count_distributed_raw`` for this raw
    result type).

    Count triangles of ``g`` on every device of ``mesh``'s ``axis_name``
    axis (the paper's p processors), probing through the shared
    intersection engine (``intersect_backend`` as in ``triangle_count``).
    ``frontier_dtype`` is the BFS frontier exchange's wire dtype
    (``"uint8"`` moves 4x fewer BFS bytes per sweep — visible in the
    result's ``comm`` tally)."""
    from repro import api

    api._warn_shim(
        "parallel_triangle_count", "TriangleEngine.count_distributed_raw"
    )
    o = api.TCOptions(
        backend=intersect_backend, interpret=interpret,
        bucket_widths=tuple(int(w) for w in bucket_widths),
        root=root, mode=mode, slack=slack, d_pad=d_pad,
        hedge_chunk=hedge_chunk, frontier_dtype=frontier_dtype,
    )
    return api.default_engine().count_distributed_raw(
        g, mesh=mesh, axis_name=axis_name, options=o
    )
