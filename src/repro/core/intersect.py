"""The neighborhood-intersection engine: plan once, execute many.

The paper intersects the adjacency lists of a horizontal edge's endpoints
with hash tables.  Pointer-chasing hash probes are hostile to the TPU
VPU, so the framework's strategy is *probe-from-the-smaller-side +
branch-free membership tests* (same O(d_small · log d_large) bound as the
paper's binary-search variant, §III-A) — and both the sequential
Algorithm 1 and the distributed Algorithm 2 run their probing through the
single engine in this module (DESIGN.md §2–§3):

* **Adjacency views.**  ``CsrAdjacency`` reads a ``Graph``'s CSR arrays;
  ``PairListAdjacency`` reads the lex-sorted ``(owner, value)`` pair list
  a device holds after Algorithm 2's sample-sort transpose.  Both expose
  the same ``bounds(v) -> (starts, lens)`` view into one flat sorted
  array, which is all the probe math needs.

* **Plans.**  ``plan_buckets`` (exact, host-side, from a degree profile)
  and ``plan_buckets_bounded`` (safe static caps when the profile is only
  known as an upper bound — the shard_map case) both produce an
  ``IntersectPlan``: a tuple of contiguous query-row buckets, each with a
  static row count and candidate/target widths.  A plan is hashable and
  jit-/shard_map-static.  A plan that gathers dense targets splits a
  candidate bucket whose larger degrees straddle ``TARGET_BAND_EDGE``
  (or its ×4 multiples) into target-width bands, so rows next to a hub
  no longer pay the hub's target width: an exact plan from the degrees
  it knows, a bounded one from exceedance bounds on them.

* **Execution.**  ``run_plan`` slices the (degree-sorted) query block at
  the plan's static boundaries and probes each bucket at its own padded
  width through ``backend="jnp" | "pallas"``.  Shapes depend only on the
  plan, never on the data, so the same call is valid under ``jit`` and
  inside ``shard_map`` — every kernel improvement lands in both
  algorithms at once.

``kernels/intersect`` provides the Pallas VMEM-tiled membership/count
kernels; the ``jnp`` backend is their ``ref``-equivalent and the
small-graph path.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.graph.csr import (
    Graph,
    _ceil_to,
    _next_pow2,
    bounded_binary_search,
    gather_rows,
)
from repro.kernels.intersect.intersect import CAND_PAD, TARG_PAD

#: Default small-endpoint-degree bucket boundaries: queries whose smaller
#: endpoint has degree <= w probe at candidate width w (plus an implicit
#: top bucket at the max/capped width).
DEFAULT_BUCKET_WIDTHS = (32, 256)

#: First target-width band boundary of an exact plan; the next ones are
#: its ×4 multiples (512, 2048, 8192, ...).  A candidate bucket whose
#: larger-endpoint degrees fall in more than one band is probed band by
#: band, each at its own target width.
TARGET_BAND_EDGE = 512


# --------------------------------------------------------------- views


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class CsrAdjacency:
    """Adjacency view over a ``Graph``'s CSR arrays (Algorithm 1).

    ``flat`` is the CSR neighbor array (``g.dst``); vertex ``v``'s sorted
    neighbor list is ``flat[row_offsets[v] : row_offsets[v] + deg[v]]``.
    """

    flat: jnp.ndarray
    row_offsets: jnp.ndarray
    deg: jnp.ndarray
    n_nodes: int = dataclasses.field(metadata=dict(static=True))

    @classmethod
    def from_graph(cls, g: Graph) -> "CsrAdjacency":
        return cls(flat=g.dst, row_offsets=g.row_offsets, deg=g.deg,
                   n_nodes=g.n_nodes)

    def bounds(self, v: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
        """``(starts, lens)`` of each vertex's slice of ``flat``; any
        ``v >= n_nodes`` (sentinel) gets length 0."""
        n = self.n_nodes
        vc = jnp.clip(v, 0, n)
        deg_ext = jnp.concatenate([self.deg, jnp.zeros((1,), jnp.int32)])
        return self.row_offsets[vc], jnp.where(v < n, deg_ext[vc], 0)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PairListAdjacency:
    """Adjacency view over lex-sorted ``(owner, value)`` pairs — the shard
    Algorithm 2 receives from its all-to-all transpose.

    ``owners`` is sorted ascending (padding owners sort last because the
    sentinel exceeds every real vertex id) and ``values`` is co-sorted, so
    the sublist of vertex ``v`` is a contiguous, sorted slice found by two
    ``searchsorted`` probes.  No CSR materialization, no extra memory.
    """

    owners: jnp.ndarray
    values: jnp.ndarray
    n_nodes: int = dataclasses.field(metadata=dict(static=True))

    @property
    def flat(self) -> jnp.ndarray:
        return self.values

    def bounds(self, v: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
        """``(starts, lens)`` of each vertex's sublist; any ``v >=
        n_nodes`` (sentinel or transpose padding) gets length 0."""
        lo = jnp.searchsorted(self.owners, v, side="left").astype(jnp.int32)
        hi = jnp.searchsorted(self.owners, v, side="right").astype(jnp.int32)
        return lo, jnp.where(v < self.n_nodes, hi - lo, 0)


# --------------------------------------------------------------- plans


@dataclasses.dataclass(frozen=True)
class PlanBucket:
    """One contiguous query-row range probed at one static width pair.

    ``[start, start + rows)`` are the rows sliced from the query block;
    the first ``count`` are real queries, rows past ``count`` are masked
    (they may alias the next bucket's rows — padding never re-probes
    them).  ``d_cand`` is the candidate gather width (smaller endpoint),
    ``d_targ`` the target width / binary-search depth (larger endpoint).
    """

    start: int
    count: int
    rows: int
    d_cand: int
    d_targ: int


@dataclasses.dataclass(frozen=True)
class IntersectPlan:
    """A static, hashable execution plan for one query-block layout.

    Produced host-side once (``plan_buckets`` / ``plan_buckets_bounded``)
    and executed many times (``run_plan``) — under jit the plan is a
    static argument, inside shard_map it is a closure constant, so all
    shapes are fixed per plan.
    """

    buckets: tuple[PlanBucket, ...]
    backend: str = "jnp"
    interpret: bool = True
    query_chunk: int | None = None
    #: sort the query block by ascending-rank = descending min-degree
    #: in-trace before slicing buckets (the shard_map path, where the
    #: host could not pre-sort).  Exact plans pre-sorted on the host
    #: leave this False.
    sort_queries: bool = False
    #: row offsets where an exact plan's candidate buckets end, set only
    #: when a bucket was split into target-width bands: ``run_plan``
    #: then sorts each candidate bucket's rows by larger degree,
    #: descending, before slicing the bands (``band_order``).
    band_ends: tuple[int, ...] = ()

    @property
    def total_rows(self) -> int:
        return max((b.start + b.rows for b in self.buckets), default=0)

    @property
    def probe_rows(self) -> int:
        return sum(b.rows for b in self.buckets)

    @property
    def probe_cells(self) -> float:
        return float(sum(float(b.rows) * b.d_cand for b in self.buckets))

    @property
    def gather_entries(self) -> int:
        """List entries the plan's dense gathers read: ``rows × d_cand``
        candidates a bucket, plus ``rows × d_targ`` targets where the
        backend compares against dense target lists (not ``"jnp"``,
        which searches the adjacency)."""
        targ = self.backend != "jnp"
        return sum(b.rows * (b.d_cand + (b.d_targ if targ else 0))
                   for b in self.buckets)

    @property
    def peak_rows(self) -> int:
        return max(
            (min(b.rows, self.query_chunk or b.rows) for b in self.buckets),
            default=0,
        )


def plan_buckets(
    ds_h,
    dl_h,
    *,
    bucket_widths: tuple[int, ...] = DEFAULT_BUCKET_WIDTHS,
    d_cap: int | None = None,
    row_mult: int = 64,
    backend: str = "jnp",
    interpret: bool = True,
    query_chunk: int | None = None,
    layout: str = "asc",
) -> IntersectPlan:
    """Exact host-side plan from a known per-query degree profile.

    ``ds_h``/``dl_h`` are the small/large endpoint degrees of the real
    queries, sorted by ``ds_h`` in the direction named by ``layout`` —
    ``"asc"`` (``horizontal_queries`` order="asc") or ``"desc"`` (the
    batched layout; the profile may then be a per-row *max* over the
    lanes of a batch, which preserves descending order, so one plan
    covers every lane exactly).  Buckets are contiguous ``searchsorted``
    ranges; ``d_cand`` is the bucket's width boundary (clamped to
    ``d_cap`` if given — a lossy candidate-list cap, see
    ``triangle_count``), ``d_targ`` the widest larger-endpoint list in
    the bucket, 128-aligned.  Widths are rounded (pow2 top, 128-aligned
    ``d_targ``, ``row_mult``-padded rows) so same-scale graphs with
    different degree profiles share jit cache entries.

    Target bands: where the backend gathers dense targets (not
    ``"jnp"``) and some bucket's larger degrees fall in more than one
    band (``TARGET_BAND_EDGE``, ×4 each), every bucket is emitted as one
    bucket per non-empty band, widest band first, with ``d_targ`` the
    band's widest list and ``d_cand = min(width, d_targ)`` (the probe
    reads candidates from the smaller list, so ``ds <= dl <= d_targ``).
    ``band_ends`` then tells ``run_plan`` to sort each bucket's rows by
    larger degree, descending.  Under a pooled batch profile this stays
    exact: each lane's k-th largest degree in a bucket is at most the
    profile's k-th largest.  Otherwise the plan is the unbanded one.
    """
    if layout not in ("asc", "desc"):
        raise ValueError(f"layout must be 'asc' or 'desc'; got {layout!r}")
    ds_h = np.asarray(ds_h)
    dl_h = np.asarray(dl_h)
    H = int(ds_h.shape[0])
    segments = []  # (lo, hi, width) of each non-empty candidate bucket
    if H:
        d_top = int(ds_h[-1] if layout == "asc" else ds_h[0])
        top = _next_pow2(max(d_top, 1))
        if d_cap is not None:
            top = min(top, int(d_cap))
        widths = sorted(
            w for w in {int(w) for w in bucket_widths} if 0 < w < top
        )
        widths.append(top)
        if layout == "asc":
            bounds = [
                int(np.searchsorted(ds_h, w, side="right")) for w in widths[:-1]
            ] + [H]
        else:
            # rows with d_small > w form a prefix of the descending block
            asc = ds_h[::-1]
            bounds = [
                H - int(np.searchsorted(asc, w, side="right"))
                for w in widths[:-1]
            ] + [0]
        start = H if layout == "desc" else 0
        for w, b in zip(widths, bounds):
            lo, hi = (b, start) if layout == "desc" else (start, b)
            start = b
            if hi > lo:
                segments.append((lo, hi, w))
    bands = [
        _target_bands(dl_h[lo:hi]) if backend != "jnp"
        else [(hi - lo, int(dl_h[lo:hi].max()))]
        for lo, hi, _ in segments
    ]
    banded = any(len(b) > 1 for b in bands)
    buckets = []
    for (lo, hi, w), seg_bands in zip(segments, bands):
        for count, dl_max in seg_bands:
            d_targ = _ceil_to(dl_max, 128)
            buckets.append(PlanBucket(
                start=lo,
                count=count,
                rows=_ceil_to(count, row_mult),
                d_cand=min(w, d_targ) if banded else w,
                d_targ=d_targ,
            ))
            lo += count
    ends = tuple(sorted(hi for _, hi, _ in segments)) if banded else ()
    return IntersectPlan(
        buckets=tuple(buckets), backend=backend, interpret=interpret,
        query_chunk=query_chunk, sort_queries=False, band_ends=ends,
    )


def _target_bands(dl) -> list[tuple[int, int]]:
    """``(rows, widest list)`` of one candidate bucket's larger degrees
    in each non-empty target band, widest band first."""
    top = int(dl.max())
    edges = [TARGET_BAND_EDGE]
    while edges[-1] < top:
        edges.append(4 * edges[-1])
    if len(edges) == 1:
        return [(dl.size, top)]
    # a band is a range of degrees, so its widest list is the widest at
    # most its upper edge
    upto = [0] + [int(np.count_nonzero(dl <= e)) for e in edges]
    return [
        (upto[i + 1] - upto[i], int(np.max(dl, where=dl <= e, initial=0)))
        for i, e in reversed(list(enumerate(edges)))
        if upto[i + 1] > upto[i]
    ]


def target_band_edges(top: int) -> tuple[int, ...]:
    """The target-band edges below ``top``, ascending: ``TARGET_BAND_EDGE``
    and its ×4 multiples.  Each is the lower edge of a band; the
    narrowest band lies below the first."""
    edges = []
    e = TARGET_BAND_EDGE
    while e < top:
        edges.append(e)
        e *= 4
    return tuple(edges)


def plan_buckets_bounded(
    total_rows: int,
    *,
    d_pad: int,
    exceed: tuple[tuple[int, int], ...] | None = None,
    exceed_large: tuple[tuple[int, int], ...] | None = None,
    bucket_widths: tuple[int, ...] = DEFAULT_BUCKET_WIDTHS,
    row_mult: int = 1,
    backend: str = "jnp",
    interpret: bool = True,
    query_chunk: int | None = None,
    sort_queries: bool | None = None,
) -> IntersectPlan:
    """Safe static plan when the per-query degree profile is unknown at
    trace time — the shard_map case, where Algorithm 2's horizontal
    rounds arrive as data-dependent gathers, and the sync-free batched
    serving path, where the bounds come from a ``BatchDegreeMeta``
    degree histogram instead (``core.sequential.batch_plan_for``).

    ``sort_queries=None`` (default) lets ``run_plan`` degree-sort each
    block in-trace whenever the plan has more than one candidate bucket
    or ends before the block; pass ``False`` when the caller's query
    blocks are already laid out descending by min-degree
    (``horizontal_queries(order="desc")``), so the executor skips the
    second argsort.

    ``exceed`` is a tuple of ``(width, bound)`` pairs: for each candidate
    bucket width, an upper bound on how many queries of *any* block this
    plan will run can have min-endpoint degree above that width (e.g.
    ``core.edges.degree_exceedance`` — the whole graph's histogram bounds
    every BFS's horizontal subset).  Buckets are laid out widest-first
    and sized from those bounds, and ``run_plan`` sorts the block by
    descending min-degree (``sort_queries=True``), so by construction
    every query lands in a bucket at least as wide as its candidate
    list.  If a bound is violated (only possible when the caller's
    ``exceed`` was not actually an upper bound, or ``d_pad`` undersizes
    the max degree) the run flags ``overflow`` instead of miscounting
    silently.  ``exceed=None`` degenerates to one ``d_pad``-wide bucket —
    always safe, no host knowledge needed (the dry-run path).

    ``exceed_large`` is the same kind of table for the *larger* endpoint:
    ``(edge, bound)`` pairs, each bounding how many queries of any block
    can have their larger list longer than ``edge``.  It sizes the plan
    in its other dimension, as ``plan_buckets`` does from a known
    profile.  A bound at edge 0 bounds the queries that can hold a
    common neighbour at all, so the plan ends there, rounded to
    ``row_mult``: the in-trace sort ranks them first, and the rows past
    the plan are never probed.  Where the backend gathers dense targets
    (not ``"jnp"``), the bounds at ``target_band_edges(d_pad)`` split
    each candidate bucket into target bands, widest first: the widest at
    ``d_targ = d_pad``, each one below at ``d_targ`` equal to its upper
    edge, ``d_cand = min(width, d_targ)``, and rows such that the
    bucket's rows up to the band's end cover ``min(bucket rows, bound at
    its lower edge)``.  Only that minimum is safe: a query whose smaller
    list is short may still have a long larger list, so a bucket's rows
    are bounded by the bucket and by the whole table, not by its width.
    ``band_ends`` then tells ``run_plan`` to sort each bucket's rows by
    larger list, descending.  A violated bound flags ``overflow`` as
    above.  Without ``exceed_large`` the plan is the one above.
    """
    def rows_for(n):  # whole row_mult steps that hold n rows
        return _ceil_to(int(n), row_mult) if n > 0 else 0

    T = rows_for(total_rows)
    large = dict(exceed_large or ())
    if 0 in large:
        T = min(T, rows_for(large[0]))
    if T == 0:
        return IntersectPlan((), backend, interpret, query_chunk, False)
    if sort_queries is None:
        sort_queries = True  # resolved below
    top = int(d_pad)
    bound = dict(exceed or ())
    widths = sorted(
        w for w in {int(w) for w in bucket_widths}
        if 0 < w < top and w in bound
    )
    widths.append(top)  # ascending, widest last
    segments = []  # (start, rows, width) of each candidate bucket
    used = 0
    for i in range(len(widths) - 1, -1, -1):  # allocate widest-first
        w = widths[i]
        if i == 0:
            rows = T - used  # narrowest bucket absorbs the remainder
        else:
            # every query with min-degree > widths[i-1] must rank before
            # this bucket's end — size it so cumulative rows cover the bound
            need_rows = rows_for(bound[widths[i - 1]])
            rows = min(T - used, max(0, need_rows - used))
        if rows <= 0:
            continue
        segments.append((used, rows, w))
        used += rows
    # target bands widest first, as (lower edge, d_targ); the narrowest
    # band (lower edge None) takes what is left of its bucket
    lower = ([] if backend == "jnp"
             else [e for e in target_band_edges(top) if e in large])
    bands = list(zip(lower[::-1], [top] + lower[:0:-1])) + [
        (None, lower[0] if lower else top)
    ]
    buckets = []
    banded = False
    for start, rows, w in segments:
        band_rows = 0
        emitted = 0
        for edge, d_targ in bands:
            if edge is None:
                r = rows - band_rows
            else:
                r = min(rows, rows_for(large[edge])) - band_rows
            if r <= 0:
                continue
            buckets.append(PlanBucket(
                start=start + band_rows, count=r, rows=r,
                d_cand=min(w, d_targ), d_targ=d_targ,
            ))
            band_rows += r
            emitted += 1
        banded |= emitted > 1
    return IntersectPlan(
        buckets=tuple(buckets), backend=backend, interpret=interpret,
        query_chunk=query_chunk,
        sort_queries=bool(sort_queries)
        and (len(segments) > 1 or T < int(total_rows)),
        band_ends=(tuple(start + rows for start, rows, _ in segments)
                   if banded else ()),
    )


# ----------------------------------------------------------- execution


class EngineCounts(NamedTuple):
    """``run_plan`` result.  Without ``level``, ``c1`` is the total hit
    count and ``c2`` is 0; with ``level``, ``(c1, c2)`` are the paper's
    diff-level / same-level apex splits.  ``overflow`` is True iff some
    real query's candidate (or target) list exceeded its bucket width —
    bounded plans set it instead of silently undercounting, and exact
    plans only set it under an explicit ``d_cap``/``d_max`` clamp (the
    documented lossy candidate truncation, where it marks the clipped
    hub queries).

    ``per_vertex`` is ``None`` unless the run was asked for attribution
    (``run_plan(..., per_vertex=True)``): an int32[n_nodes + 1] credit
    vector under the exactly-once rule (see ``run_plan``), slot
    ``n_nodes`` being the sentinel bucket that real vertices never
    receive credit in."""

    c1: jnp.ndarray
    c2: jnp.ndarray
    overflow: jnp.ndarray
    per_vertex: jnp.ndarray | None = None


def _swapped_bounds(su, lu, sw, lw, row_ok):
    """Per-query (small-side, large-side) slice bounds from the two
    endpoints' precomputed bounds, probing from the smaller list; masked
    rows gather nothing."""
    swap = lw < lu
    s_s = jnp.where(swap, sw, su)
    l_s = jnp.where(row_ok, jnp.where(swap, lw, lu), 0)
    s_l = jnp.where(swap, su, sw)
    l_l = jnp.where(row_ok, jnp.where(swap, lu, lw), 0)
    return s_s, l_s, s_l, l_l


def _gather_cand_targ(flat, s_s, l_s, s_l, l_l, *, d_cand, d_targ,
                      need_targ):
    """The engine's one dense-gather site: ``(cand, targ | None,
    overflow)``.  Every probing path routes through here so the pad
    conventions and the width-overflow predicate cannot diverge."""
    overflow = jnp.any((l_s > d_cand) | (l_l > d_targ))
    cand = gather_rows(
        flat, s_s, jnp.minimum(l_s, d_cand), width=d_cand, pad=CAND_PAD
    )
    targ = None
    if need_targ:
        targ = gather_rows(
            flat, s_l, jnp.minimum(l_l, d_targ), width=d_targ, pad=TARG_PAD
        )
    return cand, targ, overflow


def _probe_rows(adj, qu, qw, row_ok, *, d_cand, d_targ, backend, interpret,
                bounds=None):
    """One fixed-width block probe: ``(cand int32[q, d_cand] (pad -1),
    found bool[q, d_cand], overflow)``.  Both backends share this gather,
    so their outputs are bit-identical elementwise.  ``bounds`` are the
    precomputed ``(su, lu, sw, lw)`` endpoint bounds (``run_plan`` passes
    them to avoid recomputing the searchsorted passes per bucket)."""
    if bounds is None:
        bounds = (*adj.bounds(qu), *adj.bounds(qw))
    s_s, l_s, s_l, l_l = _swapped_bounds(*bounds, row_ok)
    with jax.named_scope("gather"):
        cand, targ, overflow = _gather_cand_targ(
            adj.flat, s_s, l_s, s_l, l_l,
            d_cand=d_cand, d_targ=d_targ, need_targ=(backend != "jnp"),
        )
    if backend == "jnp":
        # search depth sized by d_targ over the UNclamped list — for exact
        # plans (d_targ >= every large degree) the search converges; for a
        # too-small d_targ it under-searches, reproducing the seed's
        # d_max-truncation semantics bit-for-bit (and overflow is set)
        num_steps = max(1, math.ceil(math.log2(d_targ + 1)))
        starts = jnp.broadcast_to(s_l[:, None], cand.shape)
        lens = jnp.broadcast_to(l_l[:, None], cand.shape)
        with jax.named_scope("compare"):
            found = bounded_binary_search(
                adj.flat, starts, lens, cand, num_steps=num_steps
            )
        return cand, found & (cand >= 0) & row_ok[:, None], overflow
    from repro.kernels.intersect.intersect import intersect_pallas_hits

    with jax.named_scope("compare"):
        found = intersect_pallas_hits(cand, targ, interpret=interpret)
    return cand, found & row_ok[:, None], overflow


def _chunk_credit(n, cand, found, end_rows, qu_c, qw_c):
    """int32[n + 1] per-vertex triangle credit for one probed chunk.

    Exactly-once rule: every hit credits its apex (the witness vertex in
    ``cand``); ``end_rows`` — the per-row count of hits whose triangle is
    seen ONLY at this horizontal edge (diff-level hits under Algorithm 1,
    all hits under Algorithm 2's N-hat dedup) — additionally credits the
    edge endpoints ``qu``/``qw``.  Same-level hits credit the apex alone
    because an all-same-level triangle surfaces once per corner across
    its three horizontal edges.  Scatters go through
    ``repro.graph.segment.segment_sum``: ``CAND_PAD`` (-1) apex slots
    are out-of-range and dropped natively, sentinel endpoints (``n``)
    land in the throwaway slot ``n``.

    This element-wise scatter is the dense reference path
    (``core.sequential.triangle_count_dense``); ``run_plan`` itself uses
    the slot-accumulator formulation below (``_ends_credit`` +
    windowed apex adds), which is an order of magnitude cheaper on the
    padded probe volume but needs the adjacency's flat layout."""
    from repro.graph.segment import segment_sum

    apex = segment_sum(
        found.astype(jnp.int32).reshape(-1), cand.reshape(-1), n + 1
    )
    ends = (
        segment_sum(end_rows, qu_c, n + 1)
        + segment_sum(end_rows, qw_c, n + 1)
    )
    return apex + ends


def _ends_credit(n, end_rows, qu_c, qw_c):
    """Endpoint half of the exactly-once rule: ``end_rows`` hits per row
    credit both edge endpoints (tiny scatters — one element per query
    row).  Sentinel endpoints (``n``) land in the throwaway slot."""
    from repro.graph.segment import segment_sum

    return segment_sum(end_rows, qu_c, n + 1) + segment_sum(
        end_rows, qw_c, n + 1
    )


_APEX_SCATTER_DIMS = jax.lax.ScatterDimensionNumbers(
    update_window_dims=(1,),
    inserted_window_dims=(),
    scatter_dims_to_operand_dims=(0,),
)


def _apex_window_add(acc, s_s, found):
    """Accumulate one chunk's hit mask into the flat-slot accumulator.

    Candidates are gathered in adjacency order — ``cand[r, j] ==
    adj.flat[s_s[r] + j]`` — so each row's hits map onto one contiguous
    window of ``adj.flat`` slots.  A windowed ``scatter_add`` (one index
    per ROW, not per cell) is what makes attribution cheap: XLA applies
    each window as a vectorized slice-add, ~30x faster than the naive
    per-cell scatter over the padded probe volume.  Padding cells carry
    ``found == False`` (the probe masks ``cand < 0`` and rows past
    ``count``), so over-wide windows add zeros; ``acc`` is padded by the
    plan's max candidate width so no window is out of bounds."""
    return jax.lax.scatter_add(
        acc, s_s[:, None], found.astype(jnp.int32), _APEX_SCATTER_DIMS,
        indices_are_sorted=False, unique_indices=False,
        mode=jax.lax.GatherScatterMode.FILL_OR_DROP,
    )


def _apex_from_slots(adj, slot_acc):
    """Fold the flat-slot accumulator into per-vertex apex credit: slot
    ``e`` of ``adj.flat`` holds the hit count of the neighbor stored
    there, so one ``m``-element segment-sum by neighbor id finishes the
    job (~m elements, vs the ~sum(rows * width) padded probe volume).
    Out-of-range flat entries (transpose/batch padding) route to the
    sentinel slot ``n``; they can only ever carry zero anyway (no real
    probe window covers them with a hit)."""
    from repro.graph.segment import segment_sum

    n = adj.n_nodes
    m = adj.flat.shape[0]
    ids = adj.flat[:m]
    ids = jnp.where((ids >= 0) & (ids < n), ids, n)
    return segment_sum(slot_acc[:m], ids, n + 1)


def _count_chunk(
    adj, qu_c, qw_c, bounds_c, base, count,
    *, d_cand, d_targ, level, backend, interpret, per_vertex=False,
    acc=None,
):
    """Summed (c1, c2, overflow, ends, acc) for one chunk of bucket rows.
    ``base`` is the chunk's offset within the bucket (masks rows past
    ``count``); ``bounds_c`` the chunk's precomputed endpoint bounds.
    With ``per_vertex``, ``ends`` is the chunk's endpoint credit
    (``_ends_credit``) and ``acc`` is returned with the chunk's apex hits
    window-added (``_apex_window_add``); both are ``None``/passed-through
    otherwise."""
    n = adj.n_nodes
    pos = base + jnp.arange(qu_c.shape[0], dtype=jnp.int32)
    row_ok = (pos < count) & (qu_c < n) & (qw_c < n)
    # data-derived zero: keeps fori_loop carries device-varying in shard_map
    zero = (qu_c[0] ^ qu_c[0]).astype(jnp.int32)
    if backend == "pallas" and not per_vertex:
        # counting stays fully on-kernel: no per-candidate mask leaves VMEM
        from repro.kernels.intersect.intersect import (
            intersect_pallas,
            intersect_pallas_count,
        )

        s_s, l_s, s_l, l_l = _swapped_bounds(*bounds_c, row_ok)
        with jax.named_scope("gather"):
            cand, targ, overflow = _gather_cand_targ(
                adj.flat, s_s, l_s, s_l, l_l,
                d_cand=d_cand, d_targ=d_targ, need_targ=True,
            )
        if level is None:
            with jax.named_scope("compare"):
                cnt = intersect_pallas_count(cand, targ, interpret=interpret)
            return jnp.sum(cnt, dtype=jnp.int32), zero, overflow, None, acc
        lev_ext = jnp.concatenate([level, jnp.full((1,), -7, jnp.int32)])
        lev_c = jnp.where(cand >= 0, lev_ext[jnp.clip(cand, 0, n)], -7)
        lev_u = jnp.where(qu_c < n, lev_ext[jnp.clip(qu_c, 0, n)], -9)
        with jax.named_scope("compare"):
            c1, c2 = intersect_pallas(
                cand, targ, lev_c, lev_u, interpret=interpret
            )
        return (
            jnp.sum(c1, dtype=jnp.int32),
            jnp.sum(c2, dtype=jnp.int32),
            overflow,
            None,
            acc,
        )
    # attribution needs the hit mask, so the pallas backend routes through
    # its mask kernel (intersect_pallas_hits) here; counts derived from the
    # mask are the same integer sums the count kernels produce
    cand, found, overflow = _probe_rows(
        adj, qu_c, qw_c, row_ok,
        d_cand=d_cand, d_targ=d_targ, backend=backend, interpret=interpret,
        bounds=bounds_c,
    )
    if per_vertex:
        # cand rows are windows of adj.flat starting at the small side's
        # slice start — recompute it (cheap row-vector math) and add the
        # hit mask into the slot accumulator
        s_s = _swapped_bounds(*bounds_c, row_ok)[0]
        acc = _apex_window_add(acc, s_s, found)
    if level is None:
        hit_rows = jnp.sum(found, axis=1, dtype=jnp.int32)
        ends = (
            _ends_credit(n, hit_rows, qu_c, qw_c) if per_vertex else None
        )
        return jnp.sum(hit_rows, dtype=jnp.int32), zero, overflow, ends, acc
    lev_ext = jnp.concatenate([level, jnp.full((1,), -1, jnp.int32)])
    lev_apex = lev_ext[jnp.clip(cand, 0, n)]
    lev_u = lev_ext[jnp.clip(qu_c, 0, n)]
    same = found & (lev_apex == lev_u[:, None])
    c2 = jnp.sum(same, dtype=jnp.int32)
    c1 = jnp.sum(found, dtype=jnp.int32) - c2
    ends = None
    if per_vertex:
        diff_rows = jnp.sum(found, axis=1, dtype=jnp.int32) - jnp.sum(
            same, axis=1, dtype=jnp.int32
        )
        ends = _ends_credit(n, diff_rows, qu_c, qw_c)
    return c1, c2, overflow, ends, acc


def band_order(plan: IntersectPlan, d_large: jnp.ndarray) -> jnp.ndarray:
    """Row permutation that lays a query block out for a banded plan:
    the rows of each candidate bucket (``plan.band_ends``) sorted by
    larger-endpoint degree ``d_large``, descending and stable, so each
    target band is a contiguous range, widest first.  No row leaves its
    bucket; rows past the last bucket stay where they are."""
    end = plan.band_ends[-1]
    pos = jnp.arange(end, dtype=jnp.int32)
    seg = jnp.searchsorted(
        np.asarray(plan.band_ends, np.int32), pos, side="right"
    ).astype(jnp.int32)
    _, _, order = jax.lax.sort(
        (seg, -d_large[:end], pos), num_keys=2, is_stable=True
    )
    tail = jnp.arange(end, d_large.shape[-1], dtype=jnp.int32)
    return jnp.concatenate([order, tail])


def run_plan(
    adj, qu, qw, plan: IntersectPlan, *, level=None, per_vertex=False
) -> EngineCounts:
    """Execute a bucket plan against an adjacency view.

    ``qu``/``qw`` are the query endpoints (entries ``>= adj.n_nodes`` are
    sentinels and never counted); the block is padded to the plan's total
    rows and, for ``sort_queries`` plans, degree-sorted descending
    in-trace (``band_ends`` plans: each candidate bucket by larger
    degree, ``band_order``).  Coverage is the *planner's* contract: rows
    beyond ``plan.total_rows`` are deliberately not probed (that is how
    the sequential pipeline skips the non-horizontal compacted tail and how
    ``cap_h`` truncates — the pipeline flags the latter as
    ``h_overflow``); a caller that wants full coverage must plan the full
    block.  Shapes depend only on ``(plan, len(qu))`` — never on the
    data — so the same call is valid under ``jit`` (pass the plan as a
    static arg, as ``core.sequential``'s jitted wrappers do) and inside
    ``shard_map`` (close over the plan) — and, because every op here has
    a batching rule, the
    same call is the batched executor too: ``core.sequential`` vmaps it
    over a ``GraphBatch``'s lanes with the plan closed over, one shared
    plan covering every lane (DESIGN.md §4).  With ``level``, hits are
    split into the paper's
    (c1, c2) by apex level; without, every hit counts once (Algorithm 2's
    exactly-once semantics after N-hat dedup).

    With ``per_vertex=True`` the probe additionally scatter-adds triangle
    credit in-trace (no second pass): every hit credits its apex, and
    hits whose triangle is visible only at this edge (diff-level hits
    under ``level``; all hits without it) also credit both edge
    endpoints.  The result's ``per_vertex`` is int32[n + 1] — slot ``n``
    absorbs sentinel-row credit and must be dropped by the caller — and
    satisfies ``sum(per_vertex[:n]) == 3 * triangles`` exactly (each
    triangle's three corners each earn exactly one credit; DESIGN.md
    "Per-vertex attribution").  The pallas backend switches from its
    count kernels to the hit-mask kernel for this, keeping integer
    parity with the jnp probe.
    """
    if qu.shape[0] == 0 or not plan.buckets:
        z = jnp.int32(0)
        pv = (
            jnp.zeros((adj.n_nodes + 1,), jnp.int32) if per_vertex else None
        )
        return EngineCounts(z, z, jnp.zeros((), bool), pv)
    n = adj.n_nodes
    need = plan.total_rows
    if qu.shape[0] < need:
        fill = jnp.full((need - qu.shape[0],), n, qu.dtype)
        qu = jnp.concatenate([qu, fill])
        qw = jnp.concatenate([qw, fill])
    # endpoint bounds are computed ONCE per block (they feed the sort key
    # AND every bucket's probe — in ring mode this runs p times per device,
    # so the searchsorted passes are worth hoisting), then permuted and
    # sliced alongside the queries
    su, lu = adj.bounds(qu)
    sw, lw = adj.bounds(qw)
    if plan.sort_queries:
        valid = (qu < n) & (qw < n)
        key = jnp.where(valid, jnp.minimum(lu, lw), -1)
        order = jnp.argsort(-key)  # descending; invalid rows sort last
        qu, qw = qu[order], qw[order]
        su, lu, sw, lw = su[order], lu[order], sw[order], lw[order]
    if plan.band_ends:
        order = band_order(plan, jnp.maximum(lu, lw))
        qu, qw = qu[order], qw[order]
        su, lu, sw, lw = su[order], lu[order], sw[order], lw[order]
    zero = (qu[0] ^ qu[0]).astype(jnp.int32)  # device-varying under shard_map
    c1, c2, ovf = zero, zero, zero != 0
    # note: both sorts permute the credit *scatter indices* along with
    # the queries — values travel with the sort, so attribution is
    # permutation-invariant
    credit = acc = None
    if per_vertex:
        credit = jnp.zeros((n + 1,), jnp.int32) + zero
        # apex hits land in adjacency-slot space (see _apex_window_add);
        # the tail pad keeps every probe window in bounds
        w_max = max(b.d_cand for b in plan.buckets)
        acc = jnp.zeros((adj.flat.shape[0] + w_max,), jnp.int32) + zero
    for b in plan.buckets:
        sliced = tuple(
            jax.lax.slice_in_dim(x, b.start, b.start + b.rows)
            for x in (qu, qw, su, lu, sw, lw)
        )
        chunk = min(plan.query_chunk or b.rows, b.rows)
        if b.rows % chunk:
            raise ValueError(
                f"bucket rows={b.rows} not a multiple of "
                f"query_chunk={chunk} (plan the rows with row_mult=chunk)"
            )
        if chunk == b.rows:
            with jax.named_scope(f"probe_w{b.d_cand}"):
                d1, d2, do, dc, acc = _count_chunk(
                    adj, sliced[0], sliced[1], sliced[2:], 0, b.count,
                    d_cand=b.d_cand, d_targ=b.d_targ, level=level,
                    backend=plan.backend, interpret=plan.interpret,
                    per_vertex=per_vertex, acc=acc,
                )
            c1, c2, ovf = c1 + d1, c2 + d2, ovf | do
            if per_vertex:
                credit = credit + dc
        else:
            def body(c, carry, sliced=sliced, b=b, chunk=chunk):
                a1, a2, o = carry[:3]
                sl = tuple(
                    jax.lax.dynamic_slice(x, (c * chunk,), (chunk,))
                    for x in sliced
                )
                d1, d2, do, dc, a_out = _count_chunk(
                    adj, sl[0], sl[1], sl[2:], c * chunk, b.count,
                    d_cand=b.d_cand, d_targ=b.d_targ, level=level,
                    backend=plan.backend, interpret=plan.interpret,
                    per_vertex=per_vertex,
                    acc=carry[4] if per_vertex else None,
                )
                out = (a1 + d1, a2 + d2, o | do)
                return out + (
                    (carry[3] + dc, a_out) if per_vertex else ()
                )

            init = (c1, c2, ovf) + ((credit, acc) if per_vertex else ())
            with jax.named_scope(f"probe_w{b.d_cand}"):
                res = jax.lax.fori_loop(0, b.rows // chunk, body, init)
            c1, c2, ovf = res[:3]
            if per_vertex:
                credit, acc = res[3], res[4]
    if per_vertex:
        credit = credit + _apex_from_slots(adj, acc)
    return EngineCounts(c1, c2, ovf, credit)


# ------------------------------------------------- probe-level wrappers


def resolve_backend(
    intersect_backend: str = "auto", interpret: bool | None = None
) -> tuple[str, bool]:
    """Normalize the ``intersect_backend`` switch shared by the counting
    entry points.

    ``"auto"`` picks the Pallas kernel on real TPU and the jnp
    binary-search probe elsewhere (interpret-mode Pallas on CPU is a
    correctness path, not a fast path).  ``interpret=None`` likewise
    auto-selects from ``jax.default_backend()``.
    """
    backend = intersect_backend
    if backend == "auto":
        backend = "pallas" if jax.default_backend() == "tpu" else "jnp"
    if backend not in ("jnp", "pallas"):
        raise ValueError(
            f"intersect_backend must be 'auto', 'jnp' or 'pallas'; "
            f"got {intersect_backend!r}"
        )
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return backend, bool(interpret)


@functools.partial(
    jax.jit,
    static_argnames=("d_cand", "d_targ", "backend", "interpret"),
)
def probe_block(
    g: Graph,
    qu: jnp.ndarray,
    qw: jnp.ndarray,
    *,
    d_cand: int,
    d_targ: int | None = None,
    backend: str = "jnp",
    interpret: bool = True,
):
    """Backend-dispatched probe: ``(apexes int32[q, d_cand], found bool)``.

    Both backends gather candidates from the smaller-degree endpoint in
    CSR order through the engine's shared gather, so their outputs are
    bit-identical; ``"jnp"`` tests membership by branch-free binary
    search in CSR, ``"pallas"`` by the VMEM-tiled all-pairs compare
    kernel (``intersect_pallas_hits``).  ``d_targ`` bounds the larger
    side's dense width and search depth.  Returned apexes are
    sentinel-padded with ``n`` (the finding pipeline's convention).
    """
    adj = CsrAdjacency.from_graph(g)
    row_ok = (qu < g.n_nodes) & (qw < g.n_nodes)
    cand, found, _ = _probe_rows(
        adj, qu, qw, row_ok,
        d_cand=d_cand, d_targ=d_targ or d_cand,
        backend=backend, interpret=interpret,
    )
    return jnp.where(cand >= 0, cand, g.n_nodes), found


def probe_common_neighbors(
    g: Graph,
    eu: jnp.ndarray,
    ew: jnp.ndarray,
    *,
    d_max: int,
    d_search: int | None = None,
):
    """For query edges ``(eu, ew)`` (sentinel-padded with ``n``), return
    ``(apexes int32[q, d_max], found bool[q, d_max])`` — the candidate
    common neighbors and the intersection membership mask.

    ``d_max`` bounds the *candidate* width (smaller endpoint's list);
    ``d_search`` bounds the binary-search depth over the *larger*
    endpoint's list and must be >= its degree for exact results.  The
    planned pipeline passes the bucket's max large-endpoint degree;
    ``None`` falls back to ``d_max`` (the seed convention — only safe
    when ``d_max`` is the global max degree).
    """
    return probe_block(
        g, eu, ew, d_cand=d_max, d_targ=d_search, backend="jnp",
        interpret=True,
    )


@functools.partial(
    jax.jit,
    static_argnames=("d_cand", "d_targ", "backend", "interpret", "query_chunk"),
)
def count_common_neighbors(
    g: Graph,
    qu: jnp.ndarray,
    qw: jnp.ndarray,
    level: jnp.ndarray,
    *,
    d_cand: int,
    d_targ: int | None = None,
    backend: str = "jnp",
    interpret: bool = True,
    query_chunk: int | None = None,
):
    """Summed ``(c1, c2)`` (diff-level / same-level apex hits) over one
    fixed-width query block — a single-bucket ``run_plan`` in disguise,
    kept as the stable block-level API (kernel tests, external callers).

    ``query_chunk`` bounds peak memory by probing the rows in
    ``query_chunk``-sized fori-loop slices (rows must be a multiple);
    ``None`` probes the whole block at once.
    """
    rows = qu.shape[0]
    chunk = rows if query_chunk is None else min(query_chunk, rows)
    if rows % chunk:
        raise ValueError(f"rows={rows} not a multiple of query_chunk={chunk}")
    plan = IntersectPlan(
        buckets=(PlanBucket(0, rows, rows, d_cand, d_targ or d_cand),),
        backend=backend, interpret=interpret, query_chunk=chunk,
    )
    eng = run_plan(CsrAdjacency.from_graph(g), qu, qw, plan, level=level)
    return eng.c1, eng.c2


def edge_exists(g: Graph, qu: jnp.ndarray, qv: jnp.ndarray) -> jnp.ndarray:
    """Vectorized membership: is (qu, qv) an edge?  Used by the wedge
    baseline (the closing-edge check prior algorithms communicate for)."""
    n = g.n_nodes
    num_steps = max(1, math.ceil(math.log2(g.num_slots + 1)))
    deg_ext = jnp.concatenate([g.deg, jnp.zeros((1,), jnp.int32)])
    qu_c = jnp.clip(qu, 0, n)
    starts = g.row_offsets[qu_c]
    lens = deg_ext[qu_c]
    hit = bounded_binary_search(g.dst, starts, lens, jnp.where(qv < n, qv, -1),
                                num_steps=num_steps)
    return hit & (qu < n) & (qv < n)
