"""Algorithm 1 — sequential cover-edge triangle counting (and finding).

    1. BFS from an arbitrary root -> levels L(v)
    2. mark horizontal edges  (L(u) == L(w))
    3. for each horizontal edge, intersect N(u) and N(w)
       c1 += apexes on a different level      (counted once)
       c2 += apexes on the same level         (counted thrice, Lemma 2)
    4. T = c1 + c2 / 3                        (Theorem 1)

Since PR 3 the whole pipeline is **batched** (DESIGN.md §4): the unit of
execution is a ``GraphBatch`` — B budget-padded graphs vmapped lane-wise
through BFS → horizontal compaction (descending by small-endpoint
degree) → the shared intersection engine (``core/intersect.py``), with
ONE ``IntersectPlan`` covering every lane.  Two planning modes feed the
same executor:

* **exact** (``triangle_count_batch`` default): a jitted plan pass
  produces each lane's degree profile, the per-row max over lanes is
  pulled to the host once (descending profiles stay descending under a
  row-wise max — the reason for the desc layout), and ``plan_buckets``
  lays out exact contiguous degree buckets;
* **bounded** (``plan=batch_plan_for(gb)``): a sync-free plan from the
  batch's quantized degree metadata (``BatchDegreeMeta``), memoized in a
  host-side plan cache — the serving hot path (``launch/serve_tc.py``)
  runs BFS + compaction + probing as a single fused jit per batch with
  zero host round-trips.

The single-graph path (``_triangle_count``) is a thin B=1 wrapper over
the same code path (``to_batch`` is an ``expand_dims``, not a repack),
so the single-graph results — including ``probe_rows``/``probe_cells``
work accounting — are bit-identical to the pre-batch pipeline.
Algorithm 2 (``core/parallel_tc.py``) executes the same engine against
its transposed pair lists.

Since PR 5 the public way in is ``repro.api.TriangleEngine`` (typed
``TCOptions``, unified ``TriangleReport``, routing); the impls here
(``_triangle_count`` / ``_triangle_count_batch`` / ``_find_triangles``)
take a ``TCOptions`` directly, and the historical entry points
(``triangle_count`` / ``triangle_count_batch`` / ``find_triangles``)
remain as bit-identical ``DeprecationWarning`` shims over the engine.

* ``triangle_count_dense`` / ``find_triangles_dense`` — the seed
  single-jit reference: every directed edge slot probed at the global
  ``d_max``, non-horizontal rows sentinel-masked.  Kept as the golden
  oracle for equivalence tests and as the ``compact=False`` escape hatch.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.bfs import bfs_levels
from repro.core.edges import horizontal_mask, horizontal_queries, k_fraction
from repro.core.intersect import (
    DEFAULT_BUCKET_WIDTHS,
    CsrAdjacency,
    IntersectPlan,
    _chunk_credit,
    band_order,
    plan_buckets,
    plan_buckets_bounded,
    probe_block,
    probe_common_neighbors,
    resolve_backend,
    run_plan,
)
from repro.graph.csr import (
    Graph,
    GraphBatch,
    max_degree,
    to_batch,
    undirected_edges,
)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class TCResult:
    triangles: jnp.ndarray  # int64-exact count held in float64-safe int32/int
    c1: jnp.ndarray
    c2: jnp.ndarray
    num_horizontal: jnp.ndarray
    k: jnp.ndarray
    levels: jnp.ndarray
    probe_rows: jnp.ndarray   # query rows actually intersected (padded)
    probe_cells: jnp.ndarray  # float32 Σ rows × candidate width (a work
    #   metric — float so Graph500-scale products can't overflow int32)
    peak_rows: jnp.ndarray    # largest single probed block (peak-memory rows)
    h_overflow: jnp.ndarray   # True iff real horizontal queries were dropped
    #   (cap_h truncation, a foreign plan's short row coverage) or a
    #   width clamp truncated candidate lists (d_max / a violated
    #   bounded-plan bound) — any way a count can be less than exact
    per_vertex: jnp.ndarray | None = None  # int32[(B,) n] exactly-once
    #   triangle credit per vertex (sum == 3 * triangles); None unless
    #   requested via TCOptions(per_vertex=True) — budget-padding rows
    #   carry zero credit by construction (sentinel slot dropped)


def _lane_plan(g: Graph, *, root: int):
    """Plan pass for ONE lane: BFS levels + desc-compacted, degree-sorted
    horizontal queries + the paper's k.  Shape-polymorphic — the batched
    pipeline vmaps it over ``GraphBatch.lane_view()``."""
    level = bfs_levels(
        g.src, g.dst, g.n_nodes, root=root, row_offsets=g.row_offsets
    )
    qu, qw, d_small, d_large, n_h = horizontal_queries(g, level, order="desc")
    k = k_fraction(g.src, g.dst, level, g.n_nodes)
    return level, qu, qw, d_small, d_large, n_h, k


@functools.partial(jax.jit, static_argnames=("root",))
def _plan_batch(gview: Graph, root: int):
    """Vmapped plan pass + on-device profile pooling.

    The per-row max over descending lane profiles is itself descending,
    so ``(ds_pool, dl_pool)`` is a single profile that upper-bounds every
    lane row-wise — the host pulls just these two vectors (not B of
    them) to lay out one exact shared plan."""
    level, qu, qw, ds, dl, n_h, k = jax.vmap(
        functools.partial(_lane_plan, root=root)
    )(gview)
    return level, qu, qw, jnp.max(ds, 0), jnp.max(dl, 0), n_h, k


@functools.partial(jax.jit, static_argnames=("plan", "per_vertex"))
def _run_batch(gview: Graph, qu, qw, level, plan: IntersectPlan,
               per_vertex: bool = False):
    """Stage 2 of the exact path: vmapped ``run_plan`` over the lanes
    with the (static) shared plan closed over."""
    def lane(g, u, w, lev):
        return run_plan(
            CsrAdjacency.from_graph(g), u, w, plan, level=lev,
            per_vertex=per_vertex,
        )

    return jax.vmap(lane)(gview, qu, qw, level)


@functools.partial(jax.jit, static_argnames=("plan", "root", "per_vertex"))
def _tc_batch_fused(gview: Graph, plan: IntersectPlan, root: int,
                    per_vertex: bool = False):
    """The serving hot path: BFS + compaction + probing in ONE jit.

    Valid only with a plan known before trace time (the bounded
    plan-cache path) — no host sync anywhere in the batch."""
    def lane(g):
        # same plan pass as the exact path (_lane_plan) — one source of
        # truth; the unused degree profile is dead-code-eliminated by XLA
        level, qu, qw, _, _, n_h, k = _lane_plan(g, root=root)
        eng = run_plan(
            CsrAdjacency.from_graph(g), qu, qw, plan, level=level,
            per_vertex=per_vertex,
        )
        return level, n_h, k, eng

    return jax.vmap(lane)(gview)


def _slice_pad(
    x: jnp.ndarray, start: int, count: int, rows: int, fill: int
) -> jnp.ndarray:
    """``rows`` entries starting at ``start``: the ``count`` real ones,
    then sentinel padding (never rows of the next bucket)."""
    part = x[start:start + count]
    if count < rows:
        part = jnp.concatenate(
            [part, jnp.full((rows - count,), fill, x.dtype)]
        )
    return part


def _exact_batch_plan(
    gview, root, cap_h, bucket_widths, d_max, row_mult, backend, interpret,
    query_chunk,
):
    """Shared host orchestration of the exact path (counting and
    finding): run the vmapped plan pass, pull the pooled degree profile
    to the host in one sync, lay out the shared ``IntersectPlan``.

    Returns ``(level, qu, qw, n_h, k, h_used, h_dropped, plan)`` — the
    per-lane compacted query arrays plus the static plan covering their
    first ``h_used = min(cap_h, max_lane_km)`` rows (``h_dropped`` is
    True iff ``cap_h`` cut real queries in some lane)."""
    level, qu, qw, ds_pool, dl_pool, n_h, k = _plan_batch(gview, root)
    with obs.span("tc.plan_sync"):
        ds_h, dl_h, H = jax.device_get((ds_pool, dl_pool, jnp.max(n_h)))
    H = int(H)
    h_used = H if cap_h is None else min(int(cap_h), H)
    with obs.span("tc.plan_layout"):
        plan = plan_buckets(
            np.asarray(ds_h[:h_used]),
            np.asarray(dl_h[:h_used]),
            bucket_widths=bucket_widths,
            d_cap=d_max,
            row_mult=row_mult,
            backend=backend,
            interpret=interpret,
            query_chunk=query_chunk,
            layout="desc",
        )
    if qu.shape[0] == 1:
        _count_gather_entries(plan, ds_h, dl_h)
    return level, qu, qw, n_h, k, h_used, h_used < H, plan


def _count_gather_entries(plan, ds_h, dl_h):
    """Add a one-lane exact plan's dense-gather volume to the ``probe.*``
    counters: each bucket gathers ``rows × d_cand`` candidates and, on a
    backend that compares against dense target lists (not ``jnp``,
    which searches the CSR), ``rows × d_targ`` targets; of those, the
    real entries are the planned rows' degrees, the smaller clipped to
    the candidate width (an exact plan's ``d_targ`` covers every larger
    degree).  Real entries are summed per candidate bucket: a banded
    bucket's rows are in ``run_plan``'s order only on the device, and
    within the bucket each row's band clips its smaller degree as the
    bucket's widest candidate width does (``ds <= dl <= d_targ``)."""
    targ = plan.backend != "jnp"
    ends = plan.band_ends
    width = {}  # (lo, hi) of each candidate bucket -> candidate width
    gathered = real = 0
    for b in plan.buckets:
        gathered += b.rows * (b.d_cand + (b.d_targ if targ else 0))
        if ends:
            i = int(np.searchsorted(ends, b.start, "right"))
            rows = (ends[i - 1] if i else 0, ends[i])
        else:
            rows = (b.start, b.start + b.count)
        width[rows] = max(width.get(rows, 0), b.d_cand)
    for (lo, hi), w in width.items():
        real += int(np.minimum(ds_h[lo:hi], w).sum())
        if targ:
            real += int(dl_h[lo:hi].sum())
    obs.incr("probe.entries_gathered", gathered)
    obs.incr("probe.entries_real", real)


# ----------------------------------------------------- batch plan cache

#: default bound on a plan cache — far above any sane serving compile
#: grid (budgets x widths x chunking), low enough that an autotuner
#: sweeping thousands of (meta, plan_view) combinations through one
#: engine cannot grow the host dict without bound
DEFAULT_PLAN_CACHE_CAPACITY = 256


class PlanCache:
    """Bounded LRU mapping for bounded ``IntersectPlan``s.

    Drop-in for the plain dict ``batch_plan_for`` historically used
    (``get`` + ``__setitem__`` + ``len``), plus recency tracking and a
    capacity: inserting past ``capacity`` evicts the least-recently-used
    plan (``evictions`` counts them).  Eviction is only a performance
    event, never a correctness one — a re-planned key produces an equal
    plan (planning is a pure function of the key) and at worst one extra
    jit trace.  ``capacity=None`` restores the unbounded behavior.
    """

    def __init__(self, capacity: int | None = DEFAULT_PLAN_CACHE_CAPACITY):
        if capacity is not None and int(capacity) <= 0:
            raise ValueError(f"capacity must be positive; got {capacity}")
        self.capacity = int(capacity) if capacity is not None else None
        self.evictions = 0
        self._d: dict = {}  # insertion-ordered; re-insert marks recency

    def get(self, key):
        plan = self._d.get(key)
        if plan is not None:  # touch: move to the recent end
            del self._d[key]
            self._d[key] = plan
        return plan

    def __setitem__(self, key, plan) -> None:
        self._d.pop(key, None)
        self._d[key] = plan
        while self.capacity is not None and len(self._d) > self.capacity:
            self._d.pop(next(iter(self._d)))
            self.evictions += 1

    def __len__(self) -> int:
        return len(self._d)

    def __contains__(self, key) -> bool:
        return key in self._d

    def clear(self) -> None:
        self._d.clear()
        self.evictions = 0


_BATCH_PLAN_CACHE = PlanCache()
_BATCH_PLAN_STATS = {"hits": 0, "misses": 0}


def batch_plan_for(
    gb: GraphBatch,
    *,
    options=None,
    intersect_backend: str = "auto",
    bucket_widths: tuple[int, ...] = DEFAULT_BUCKET_WIDTHS,
    interpret: bool | None = None,
    query_chunk: int | None = None,
    row_mult: int = 64,
    cache: "dict | PlanCache | None" = None,
    stats: dict | None = None,
) -> IntersectPlan:
    """Sync-free bounded plan for a packed batch, memoized host-side.

    The plan is laid out by ``plan_buckets_bounded`` from the batch's
    quantized ``BatchDegreeMeta`` (true upper bounds on every lane's
    horizontal-query degree profile, known at pack time — no BFS, no
    device round-trip), so it is exact: no lane can overflow its bucket.
    The cache key is ``(budget, meta, options.plan_view())`` — the
    typed ``repro.api.TCOptions`` projection of the plan-relevant knobs
    (``options`` directly, or one built from the legacy kwargs);
    metadata quantization (``META_ROW_QUANT``, pow2 ``d_pad``) is what
    makes same-scale traffic collide onto the same key, skip planning
    entirely, and share one fused jit entry.  ``cache``/``stats`` let a
    ``TriangleEngine`` own its plan cache; the module-global default
    (reported by ``batch_plan_cache_stats``) serves legacy callers.
    """
    from repro.api import TCOptions  # deferred: api imports this module

    if options is None:
        options = TCOptions(
            backend=intersect_backend,
            bucket_widths=tuple(int(w) for w in bucket_widths),
            interpret=interpret, query_chunk=query_chunk,
            row_mult=int(row_mult),
        )
    key_opts = options.plan_view()
    if gb.meta is None:
        raise ValueError(
            "GraphBatch carries no degree metadata; pack it with "
            "from_edges_batch(with_meta=True) or plan exact "
            "(triangle_count_batch(gb) without a plan)"
        )
    cache = _BATCH_PLAN_CACHE if cache is None else cache
    stats = _BATCH_PLAN_STATS if stats is None else stats
    key = (gb.budget, gb.meta, key_opts)
    plan = cache.get(key)
    if plan is None:
        stats["misses"] += 1
        plan = plan_buckets_bounded(
            gb.meta.h_rows,
            d_pad=gb.meta.d_pad,
            exceed=gb.meta.exceed,
            bucket_widths=key_opts.bucket_widths,
            row_mult=key_opts.row_mult,
            backend=key_opts.backend,
            interpret=key_opts.interpret,
            query_chunk=key_opts.query_chunk,
            sort_queries=False,  # lanes arrive desc-sorted from compaction
        )
        cache[key] = plan
    else:
        stats["hits"] += 1
    return plan


def batch_plan_cache_stats(reset: bool = False) -> dict:
    """``{"hits", "misses", "size", "evictions", "capacity"}`` of the
    module-global bounded-plan cache (engine-owned caches report via
    ``TriangleEngine.plan_cache_stats``)."""
    out = dict(
        _BATCH_PLAN_STATS,
        size=len(_BATCH_PLAN_CACHE),
        evictions=_BATCH_PLAN_CACHE.evictions,
        capacity=_BATCH_PLAN_CACHE.capacity,
    )
    if reset:
        _BATCH_PLAN_STATS.update(hits=0, misses=0)
    return out


def _triangle_count_batch(
    gb: GraphBatch, o, *, plan: IntersectPlan | None = None
) -> TCResult:
    """Batched count impl — ``o`` is a ``repro.api.TCOptions`` (every
    knob validated there, in one place).  See ``triangle_count_batch``
    for the semantics; the engine (``repro.api.TriangleEngine``) and the
    legacy shim both execute exactly this."""
    backend, interpret = resolve_backend(o.backend, o.interpret)
    gview = gb.lane_view()
    root = int(o.root)
    if plan is not None:
        if o.d_max is not None or o.cap_h is not None:
            raise ValueError(
                "d_max/cap_h only apply to exact planning; a precomputed "
                "plan fixes coverage and widths"
            )
        with obs.span("tc.probe"):
            level, n_h, k, eng = _tc_batch_fused(
                gview, plan, root, per_vertex=bool(o.per_vertex)
            )
        # coverage is the plan's contract: a lane with more horizontal
        # queries than the plan probes must flag, not silently undercount
        # (can't happen with a plan from THIS batch's true-bound meta,
        # but the plan= parameter is public and plans get reused)
        h_ovf = (n_h > plan.total_rows) | eng.overflow
    else:
        row_mult = int(o.query_chunk) if o.query_chunk else o.row_mult
        level, qu, qw, n_h, k, h_used, _, plan = _exact_batch_plan(
            gview, root, o.cap_h, o.bucket_widths, o.d_max, row_mult,
            backend, interpret, o.query_chunk,
        )
        with obs.span("tc.probe"):
            eng = _run_batch(
                gview, qu, qw, level, plan, per_vertex=bool(o.per_vertex)
            )
        h_ovf = (n_h > h_used) | eng.overflow
    return TCResult(
        triangles=eng.c1 + eng.c2 // 3,
        c1=eng.c1,
        c2=eng.c2,
        num_horizontal=n_h,
        k=k,
        levels=level,
        probe_rows=jnp.asarray(plan.probe_rows, jnp.int32),
        probe_cells=jnp.asarray(plan.probe_cells, jnp.float32),
        peak_rows=jnp.asarray(plan.peak_rows, jnp.int32),
        h_overflow=h_ovf,
        # drop the engine's sentinel slot: [B, n_budget + 1] -> [B, n_budget]
        per_vertex=(
            eng.per_vertex[:, :-1] if eng.per_vertex is not None else None
        ),
    )


def triangle_count_batch(
    gb: GraphBatch,
    *,
    plan: IntersectPlan | None = None,
    root: int = 0,
    intersect_backend: str = "auto",
    bucket_widths: tuple[int, ...] = DEFAULT_BUCKET_WIDTHS,
    d_max: int | None = None,
    cap_h: int | None = None,
    query_chunk: int | None = None,
    interpret: bool | None = None,
) -> TCResult:
    """DEPRECATED shim — use ``repro.api.TriangleEngine.count_batch``.

    Cover-edge triangle count of every lane of a ``GraphBatch``.

    All ``TCResult`` array fields gain a leading batch axis (``levels``
    is ``[B, n_budget]``); the plan-derived work accounting
    (``probe_rows``/``probe_cells``/``peak_rows``) stays scalar — it is
    per-lane by construction (every lane runs the same plan).  Lane
    results are bit-identical to running ``triangle_count`` on each
    graph alone (isolated budget-padding vertices change nothing).

    Without ``plan``, the exact two-stage path runs: one jitted plan
    pass, one small host sync for the pooled degree profile, one jitted
    execution pass.  With ``plan`` (see ``batch_plan_for``), the whole
    batch runs as a single fused jit with no host round-trip — the
    serving hot path; the plan's own backend/interpret/chunk settings
    apply, and ``d_max``/``cap_h`` must be left unset (coverage is the
    plan's contract).  ``h_overflow[i]`` is True iff ``cap_h`` dropped
    real queries of lane ``i`` or lane ``i`` overflowed a bucket width
    (impossible under true-bound plans, flagged rather than miscounted
    otherwise).
    """
    from repro import api

    api._warn_shim("triangle_count_batch", "TriangleEngine.count_batch")
    o = api.TCOptions(
        backend=intersect_backend, interpret=interpret,
        bucket_widths=tuple(int(w) for w in bucket_widths),
        query_chunk=query_chunk, d_max=d_max, cap_h=cap_h, root=root,
    )
    return api.default_engine().count_batch_raw(gb, options=o, plan=plan)


def _squeeze_lane(res: TCResult) -> TCResult:
    """Drop the batch axis of a B=1 result (plan-derived scalars pass
    through untouched)."""
    return TCResult(
        triangles=res.triangles[0], c1=res.c1[0], c2=res.c2[0],
        num_horizontal=res.num_horizontal[0], k=res.k[0],
        levels=res.levels[0], probe_rows=res.probe_rows,
        probe_cells=res.probe_cells, peak_rows=res.peak_rows,
        h_overflow=res.h_overflow[0],
        per_vertex=(
            res.per_vertex[0] if res.per_vertex is not None else None
        ),
    )


def _triangle_count(g: Graph, o) -> TCResult:
    """Single-graph count impl — ``o`` is a ``repro.api.TCOptions``.
    A thin B=1 wrapper over ``_triangle_count_batch`` (the graph rides
    the batched engine as a single lane; ``to_batch`` adds the lane axis
    without repacking), so counts AND work accounting are bit-identical
    to the batch path's lane results.  ``o.compact=False`` falls back to
    the dense seed reference."""
    if not o.compact:
        dm = o.d_max if o.d_max is not None else max(1, max_degree(g))
        return triangle_count_dense(g, d_max=dm, root=int(o.root))
    return _squeeze_lane(_triangle_count_batch(to_batch(g), o))


def triangle_count(
    g: Graph,
    *,
    d_max: int | None = None,
    root: int = 0,
    intersect_backend: str = "auto",
    bucket_widths: tuple[int, ...] = DEFAULT_BUCKET_WIDTHS,
    cap_h: int | None = None,
    query_chunk: int | None = None,
    interpret: bool | None = None,
    compact: bool = True,
) -> TCResult:
    """DEPRECATED shim — use ``repro.api.TriangleEngine.count``.

    Cover-edge triangle count via the compacted, degree-bucketed
    pipeline.

    Args:
      d_max: candidate-width clamp.  ``None`` (default) sizes every bucket
        exactly; passing the seed-style global max degree is accepted and
        changes nothing (small-endpoint degrees never exceed it).  A
        *smaller* value lossily truncates candidate lists — and is NOT
        equivalent to ``triangle_count_dense`` with the same ``d_max``,
        whose membership tests additionally under-search large endpoints
        (a seed artifact kept for reference fidelity).
      intersect_backend: ``"auto"`` | ``"jnp"`` | ``"pallas"`` — see
        ``repro.core.intersect.resolve_backend``.
      bucket_widths: small-endpoint-degree bucket boundaries; queries with
        ``d_small <= w`` probe at width ``w``.
      cap_h: optional cap on the compacted query block (k·m rows when
        ``None``).  Dropped queries set ``h_overflow``.  NOTE: since the
        batch refactor the block is sorted *descending* by
        small-endpoint degree, so the retained ``cap_h`` rows are the
        highest-degree (hub) queries and the dropped ones the cheap
        tail — the opposite truncation set from the pre-batch ascending
        layout, and the retained block buckets at hub widths.  Use
        ``query_chunk`` to bound peak probe memory; ``cap_h`` only
        bounds the row count.
      query_chunk: probe rows in fori-loop chunks of this size to bound
        peak memory (also the row-padding multiple; default 64).
      interpret: Pallas interpret override; ``None`` = auto from backend.
      compact: ``False`` falls back to the dense seed reference
        (``triangle_count_dense``; jnp only).

    This is a thin B=1 wrapper over the batched pipeline (the graph
    rides the batched engine as a single lane; ``to_batch`` adds the
    lane axis without repacking), so counts AND work accounting are
    bit-identical to the batch path's lane results.
    """
    from repro import api

    api._warn_shim("triangle_count", "TriangleEngine.count")
    o = api.TCOptions(
        backend=intersect_backend, interpret=interpret,
        bucket_widths=tuple(int(w) for w in bucket_widths),
        query_chunk=query_chunk, d_max=d_max, cap_h=cap_h, root=root,
        compact=compact,
    )
    return api.default_engine().count_raw(g, options=o)


@functools.partial(jax.jit, static_argnames=("d_max", "root"))
def triangle_count_dense(g: Graph, *, d_max: int, root: int = 0) -> TCResult:
    """Seed reference: probe ALL ``num_slots`` directed edge slots at the
    global ``d_max`` width, non-horizontal rows sentinel-masked."""
    level = bfs_levels(g.src, g.dst, g.n_nodes, root=root)
    horiz = horizontal_mask(g.src, g.dst, level, g.n_nodes)
    eu, ew, und = undirected_edges(g)
    use = und & horiz
    qu = jnp.where(use, eu, g.n_nodes)
    qw = jnp.where(use, ew, g.n_nodes)
    cand, found = probe_common_neighbors(g, qu, qw, d_max=d_max)
    lev_ext = jnp.concatenate([level, jnp.full((1,), -1, jnp.int32)])
    lev_apex = lev_ext[jnp.clip(cand, 0, g.n_nodes)]
    lev_u = lev_ext[jnp.clip(qu, 0, g.n_nodes)]
    same = found & (lev_apex == lev_u[:, None])
    diff = found & (lev_apex != lev_u[:, None])
    c1 = jnp.sum(diff, dtype=jnp.int32)
    c2 = jnp.sum(same, dtype=jnp.int32)
    # the dense reference computes attribution unconditionally (it IS a
    # reference): same exactly-once rule as the compacted engine — the
    # probe's sentinel-padded apexes (n) and sentinel queries land in
    # slot n and are dropped
    credit = _chunk_credit(
        g.n_nodes, cand, found,
        jnp.sum(diff, axis=1, dtype=jnp.int32), qu, qw,
    )
    return TCResult(
        triangles=c1 + c2 // 3,
        c1=c1,
        c2=c2,
        num_horizontal=jnp.sum(use, dtype=jnp.int32),
        k=k_fraction(g.src, g.dst, level, g.n_nodes),
        levels=level,
        probe_rows=jnp.int32(g.num_slots),
        probe_cells=jnp.float32(float(g.num_slots) * d_max),
        peak_rows=jnp.int32(g.num_slots),
        h_overflow=jnp.asarray(False),
        per_vertex=credit[: g.n_nodes],
    )


def _emit_mask(qu, qw, cand, found, level, n):
    """Emission mask for triangle finding: apex-on-different-level hits
    appear once naturally; all-same-level triangles {u, w, v} have three
    horizontal edges, so keep only the emission where v > max(u, w) AND
    u < w — exactly the smallest-pair edge, since all three pairs occur."""
    lev_ext = jnp.concatenate([level, jnp.full((1,), -1, jnp.int32)])
    lev_apex = lev_ext[jnp.clip(cand, 0, n)]
    lev_u = lev_ext[jnp.clip(qu, 0, n)]
    same = found & (lev_apex == lev_u[:, None])
    diff = found & (lev_apex != lev_u[:, None])
    keep_same = same & (cand > jnp.maximum(qu, qw)[:, None])
    return diff | keep_same


@functools.partial(
    jax.jit,
    static_argnames=("d_cand", "d_targ", "backend", "interpret",
                     "max_triangles"),
)
def _find_block(
    g: Graph,
    qu: jnp.ndarray,
    qw: jnp.ndarray,
    level: jnp.ndarray,
    *,
    d_cand: int,
    d_targ: int,
    backend: str,
    interpret: bool,
    max_triangles: int,
):
    """Probe one bucket and compact its emitted triangles by cumsum
    (prefix-sum scatter — O(q·d) instead of the dense path's full argsort
    over q·d_max booleans).  Returns ``(tri int32[max_triangles, 3], cnt)``
    where ``cnt`` is the total emitted (may exceed the buffer)."""
    cand, found = probe_block(
        g, qu, qw, d_cand=d_cand, d_targ=d_targ, backend=backend,
        interpret=interpret,
    )
    emit = _emit_mask(qu, qw, cand, found, level, g.n_nodes)
    flat = emit.reshape(-1)
    pos = jnp.cumsum(flat, dtype=jnp.int32) - 1
    write = jnp.where(flat & (pos < max_triangles), pos, max_triangles)
    tri_flat = jnp.stack(
        [
            jnp.broadcast_to(qu[:, None], cand.shape).reshape(-1),
            jnp.broadcast_to(qw[:, None], cand.shape).reshape(-1),
            cand.reshape(-1),
        ],
        axis=1,
    )
    buf = jnp.full((max_triangles + 1, 3), -1, jnp.int32)
    buf = buf.at[write].set(tri_flat)  # row max_triangles is the spill row
    cnt = jnp.sum(emit, dtype=jnp.int32)
    return buf[:max_triangles], cnt


def _find_triangles(g: Graph, o, *, max_triangles: int):
    """Triangle-finding impl — ``o`` is a ``repro.api.TCOptions``.  See
    ``find_triangles`` for the semantics.

    ``o.query_chunk`` shapes the bucket layout exactly as in counting
    (rows quantized to chunk multiples), keeping the plan consistent
    across an engine's count/find calls — but the finding executor
    dispatches each bucket's probe whole (``_find_block``), so the
    peak-memory bound that chunking gives the counting path does not
    apply here."""
    backend, interpret = resolve_backend(o.backend, o.interpret)
    if not o.compact:
        dm = o.d_max if o.d_max is not None else max(1, max_degree(g))
        return find_triangles_dense(
            g, d_max=dm, max_triangles=max_triangles, root=int(o.root)
        )
    gview = to_batch(g).lane_view()
    row_mult = int(o.query_chunk) if o.query_chunk else o.row_mult
    level, qu, qw, _, _, _, h_dropped, plan = _exact_batch_plan(
        gview, int(o.root), o.cap_h, o.bucket_widths, o.d_max, row_mult,
        backend, interpret, o.query_chunk,
    )
    if h_dropped:
        warnings.warn(
            f"find_triangles: cap_h={o.cap_h} dropped horizontal queries — "
            "the returned triangle list is incomplete",
            stacklevel=2,
        )
    level, qu, qw = level[0], qu[0], qw[0]
    if plan.band_ends:
        adj = CsrAdjacency.from_graph(g)
        order = band_order(
            plan, jnp.maximum(adj.bounds(qu)[1], adj.bounds(qw)[1])
        )
        qu, qw = qu[order], qw[order]
    # dispatch EVERY bucket's jitted probe before the first fetch: the
    # device works through the blocks back-to-back while the host copies
    # results out, instead of stalling on a device_get per bucket
    pending = []
    for b in plan.buckets:
        qu_b = _slice_pad(qu, b.start, b.count, b.rows, g.n_nodes)
        qw_b = _slice_pad(qw, b.start, b.count, b.rows, g.n_nodes)
        pending.append(_find_block(
            g, qu_b, qw_b, level,
            d_cand=b.d_cand, d_targ=b.d_targ, backend=backend,
            interpret=interpret, max_triangles=max_triangles,
        ))
    out = np.full((max_triangles, 3), -1, np.int32)
    off = 0
    total = 0
    for tri_b, cnt_b in pending:
        c = int(jax.device_get(cnt_b))
        total += c
        take = min(c, max_triangles - off)
        if take > 0:
            out[off:off + take] = np.asarray(jax.device_get(tri_b))[:take]
            off += take
    return jnp.asarray(out), jnp.asarray(total, jnp.int32)


def find_triangles(
    g: Graph,
    *,
    max_triangles: int,
    d_max: int | None = None,
    root: int = 0,
    intersect_backend: str = "auto",
    bucket_widths: tuple[int, ...] = DEFAULT_BUCKET_WIDTHS,
    cap_h: int | None = None,
    interpret: bool | None = None,
    compact: bool = True,
):
    """DEPRECATED shim — use ``repro.api.TriangleEngine.find``.

    Triangle *finding* through the same compacted/bucketed pipeline:
    returns ``(tri int32[max_triangles, 3], count)``; rows past ``count``
    (or past the buffer, on overflow) are -1.  Triangles are unique (see
    ``_emit_mask``); their order depends on the bucket layout.  A
    ``cap_h`` that drops real horizontal queries truncates the result and
    raises a ``UserWarning`` (counting surfaces the same condition as
    ``TCResult.h_overflow``)."""
    from repro import api

    api._warn_shim("find_triangles", "TriangleEngine.find")
    o = api.TCOptions(
        backend=intersect_backend, interpret=interpret,
        bucket_widths=tuple(int(w) for w in bucket_widths),
        d_max=d_max, cap_h=cap_h, root=root, compact=compact,
    )
    return api.default_engine().find_raw(
        g, max_triangles=int(max_triangles), options=o
    )


@functools.partial(jax.jit, static_argnames=("d_max", "max_triangles", "root"))
def find_triangles_dense(
    g: Graph, *, d_max: int, max_triangles: int, root: int = 0
):
    """Seed reference for triangle finding (dense probe + full argsort
    compaction); see ``find_triangles``."""
    level = bfs_levels(g.src, g.dst, g.n_nodes, root=root)
    horiz = horizontal_mask(g.src, g.dst, level, g.n_nodes)
    eu, ew, und = undirected_edges(g)
    use = und & horiz
    qu = jnp.where(use, eu, g.n_nodes)
    qw = jnp.where(use, ew, g.n_nodes)
    cand, found = probe_common_neighbors(g, qu, qw, d_max=d_max)
    emit = _emit_mask(qu, qw, cand, found, level, g.n_nodes)
    u_mat = jnp.broadcast_to(qu[:, None], cand.shape)
    w_mat = jnp.broadcast_to(qw[:, None], cand.shape)
    flat_emit = emit.reshape(-1)
    order = jnp.argsort(~flat_emit)  # emitted entries first, stable
    take = order[:max_triangles]
    tri = jnp.stack(
        [u_mat.reshape(-1)[take], w_mat.reshape(-1)[take], cand.reshape(-1)[take]],
        axis=1,
    )
    cnt = jnp.sum(emit, dtype=jnp.int32)
    tri = jnp.where((jnp.arange(max_triangles) < cnt)[:, None], tri, -1)
    return tri, cnt
