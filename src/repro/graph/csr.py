"""Static-shape graph container.

The whole framework moves graphs around as a ``Graph`` pytree whose array
fields have *static* shapes (a hard TPU requirement).  A graph is stored as

  * a symmetrized directed edge list ``(src, dst)`` sorted by ``(src, dst)``
    — i.e. CSR order — optionally padded with the sentinel vertex ``n`` so
    different graphs of the same budget share one compiled program, and
  * CSR ``row_offsets`` / ``deg`` derived from it.

Construction happens host-side in numpy (it is data loading, not traced
compute); every downstream algorithm (BFS, cover-edge TC, GNN aggregation)
consumes only the jnp arrays.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.dtypes import index_dtype, jnp_index_dtype


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Graph:
    """Symmetrized graph in CSR-ordered edge-list form.

    Attributes:
      src, dst:     int32[num_slots] directed edges, CSR-sorted; padded
                    entries have ``src == dst == n`` (the sentinel vertex).
      row_offsets:  int32[n + 2] CSR offsets (the extra row is the sentinel
                    vertex, so ``row_offsets[n+1] == num_slots``).
      deg:          int32[n] vertex degrees.
      n_nodes:      static python int, number of real vertices.
      n_edges_dir:  int32 scalar — number of *real* directed edges (2m).
    """

    src: jnp.ndarray
    dst: jnp.ndarray
    row_offsets: jnp.ndarray
    deg: jnp.ndarray
    n_edges_dir: jnp.ndarray
    n_nodes: int = dataclasses.field(metadata=dict(static=True))

    @property
    def num_slots(self) -> int:
        return self.src.shape[0]

    @property
    def sentinel(self) -> int:
        return self.n_nodes

    def neighbors_padded(self, max_degree: int) -> tuple[jnp.ndarray, jnp.ndarray]:
        """Dense ``int32[n, max_degree]`` adjacency, sentinel-padded, sorted."""
        n = self.n_nodes
        starts = self.row_offsets[:n]
        idx = starts[:, None] + jnp.arange(max_degree)[None, :]
        valid = jnp.arange(max_degree)[None, :] < self.deg[:, None]
        idx = jnp.where(valid, idx, self.num_slots - 1)
        nbrs = self.dst[jnp.clip(idx, 0, self.num_slots - 1)]
        return jnp.where(valid, nbrs, n), valid


def _normalize_edges(edges: np.ndarray, n_nodes: int) -> tuple[np.ndarray, np.ndarray]:
    """Shared host-side packing step: dedup, drop self-loops, symmetrize,
    CSR-sort.  Returns ``(src, dst)`` int64 directed arrays of length 2m.

    **Duplicate-edge semantics** (the multiplicity contract every layer
    above inherits): the graph is a simple undirected SET of edges.
    Duplicate rows — repeats of ``(u, v)``, its reverse ``(v, u)``, or
    both — collapse to ONE undirected edge via ``np.unique`` over the
    packed ``lo * n + hi`` keys, and self-loops are dropped, silently:
    an edge is either present or absent, never counted with
    multiplicity.  The streaming subsystem (``repro.stream``) makes the
    same rule *observable* per update instead of silent: inserting a
    present edge / deleting an absent one is an idempotent no-op with a
    structured ``noop-present`` / ``noop-absent`` status, so a mutable
    session and a fresh ``from_edges`` pack of its edge list can never
    disagree on the edge set.

    Handles the degenerate inputs the batched serving path must accept —
    an empty edge array and/or ``n_nodes == 0`` (the empty-graph padding
    lanes of a partial batch) — without tripping the ``// n_nodes``
    packed-key arithmetic on a zero divisor.
    """
    edges = np.asarray(edges, dtype=np.int64)
    if edges.size == 0 or n_nodes <= 0:
        z = np.zeros(0, dtype=np.int64)
        return z, z
    edges = edges.reshape(-1, 2)
    edges = edges[edges[:, 0] != edges[:, 1]]
    if edges.shape[0] == 0:
        z = np.zeros(0, dtype=np.int64)
        return z, z
    lo = np.minimum(edges[:, 0], edges[:, 1])
    hi = np.maximum(edges[:, 0], edges[:, 1])
    und = np.unique(lo * np.int64(n_nodes) + hi)
    lo, hi = und // n_nodes, und % n_nodes
    s = np.concatenate([lo, hi])
    d = np.concatenate([hi, lo])
    order = np.lexsort((d, s))
    return s[order], d[order]


def from_edges(
    edges: np.ndarray,
    n_nodes: int,
    *,
    num_slots: Optional[int] = None,
) -> Graph:
    """Build a ``Graph`` from an undirected edge array ``int[any, 2]``.

    Deduplicates, drops self-loops, symmetrizes and CSR-sorts (see
    ``_normalize_edges`` for the duplicate-edge contract: edges form a
    set — duplicates and orientation flips collapse to one undirected
    edge, so ``from_edges(g_edges + g_edges, n)`` is ``from_edges(
    g_edges, n)`` exactly).  ``num_slots`` pads the directed edge list
    to a fixed budget (>= 2m).
    """
    s, d = _normalize_edges(edges, n_nodes)
    m2 = s.shape[0]
    slots = int(num_slots) if num_slots is not None else m2
    if slots < m2:
        raise ValueError(f"num_slots={slots} < 2m={m2}")
    # index-dtype policy (analysis/dtypes): vertex ids are bounded by the
    # sentinel (n), CSR offsets by the slot count.  Past 2**31 slots the
    # historical unconditional int32 cast wrapped every high offset
    # negative SILENTLY (np.int64 cumsum -> jnp int32); now the bound
    # picks the dtype and an un-representable graph fails loudly here,
    # before any multi-GiB buffer is materialized.
    vid_dt = jnp_index_dtype(n_nodes, site="csr.from_edges vertex ids")
    off_dt = jnp_index_dtype(slots, site="csr.from_edges row_offsets")
    pad = slots - m2
    s = np.concatenate([s, np.full(pad, n_nodes, dtype=np.int64)])
    d = np.concatenate([d, np.full(pad, n_nodes, dtype=np.int64)])
    counts = np.bincount(s[:m2], minlength=n_nodes + 1)
    row_offsets = np.zeros(n_nodes + 2, dtype=np.int64)
    np.cumsum(counts, out=row_offsets[1 : n_nodes + 2])
    row_offsets[n_nodes + 1] = slots
    return Graph(
        src=jnp.asarray(s, dtype=vid_dt),
        dst=jnp.asarray(d, dtype=vid_dt),
        row_offsets=jnp.asarray(row_offsets, dtype=off_dt),
        deg=jnp.asarray(counts[:n_nodes], dtype=vid_dt),
        n_edges_dir=jnp.asarray(m2, dtype=off_dt),
        n_nodes=int(n_nodes),
    )


def abstract_graph(n_nodes: int, num_slots: int) -> Graph:
    """A :class:`Graph` pytree of ``jax.ShapeDtypeStruct`` leaves at the
    index-dtype policy's dtypes — the form ``jax.eval_shape`` /
    ``jax.make_jaxpr`` consume, so Graph500-scale graphs (scale 26:
    2**31 slots; scale 36: 2**36 vertices) can be *reasoned about*
    (bounds audit, dtype regression tests) without materializing a
    single element."""
    vid = index_dtype(n_nodes)
    off = index_dtype(num_slots)
    return Graph(
        src=jax.ShapeDtypeStruct((num_slots,), vid),
        dst=jax.ShapeDtypeStruct((num_slots,), vid),
        row_offsets=jax.ShapeDtypeStruct((n_nodes + 2,), off),
        deg=jax.ShapeDtypeStruct((n_nodes,), vid),
        n_edges_dir=jax.ShapeDtypeStruct((), off),
        n_nodes=int(n_nodes),
    )


# ---------------------------------------------------------------- batching
#
# The batched pipeline packs B graphs into one ``GraphBatch`` of a shared
# static ``(n_budget, slot_budget)`` shape and vmaps the single-graph
# algorithms over the lanes.  Each lane IS a valid ``Graph`` whose static
# vertex count is the budget: vertices ``n_nodes[i] .. n_budget-1`` are
# merely isolated (degree 0), and isolated vertices change neither BFS
# levels of real vertices, nor horizontal marking, nor any triangle count
# — so lane results are bit-identical to the unpadded single-graph run.

#: Candidate-width grid the packer's exceedance metadata is computed on
#: (a superset of ``DEFAULT_BUCKET_WIDTHS`` so bounded batch plans can
#: bucket at any of these without re-reading the graph).
META_WIDTHS = (8, 32, 64, 256, 1024)

#: Quantization step for the static degree metadata (row counts are
#: rounded up to this multiple so same-scale traffic shares pytree
#: treedefs, plan-cache keys and jit cache entries).
META_ROW_QUANT = 64


def _next_pow2(x: int) -> int:
    return 1 << max(0, (int(x) - 1).bit_length())


def _ceil_to(x: int, mult: int) -> int:
    return max(mult, -(-int(x) // mult) * mult)


@dataclasses.dataclass(frozen=True, order=True)
class ShapeBudget:
    """One cell of the static-shape grid a request is rounded onto:
    ``n_budget`` vertex slots and ``slot_budget`` directed edge slots."""

    n_budget: int
    slot_budget: int


@dataclasses.dataclass(frozen=True)
class BudgetGrid:
    """Rounds arbitrary request sizes onto a fixed geometric grid of
    ``ShapeBudget``s so the number of distinct compiled programs (and
    plan-cache entries) stays logarithmic in the largest request, not
    linear in the number of distinct request shapes.

    The geometry — base cell ``(min_nodes, min_slots)``, geometric
    ``factor``, top-cell extent ``(max_nodes, max_slots)`` — is a frozen,
    hashable, validated value: the autotuner (``repro.tune``) sweeps it
    like any other plan knob, and a tuned grid round-trips through a
    ``TunedProfile`` unchanged.  Coarser geometry trades padding waste
    for fewer distinct cells (queues fill faster, fewer compiled
    programs); the default is the finest PR-3 grid.

    ``max_nodes``/``max_slots`` cap the grid at a top cell: requests
    whose rounded cell would exceed either cap do not ``fit`` and make
    ``budget_for`` raise — the serving layer routes those to the
    distributed (Algorithm 2) backend instead of padding one sequential
    lane to an arbitrarily large static shape.  ``None`` (default)
    leaves the grid unbounded, the pre-PR-4 behavior.
    """

    min_nodes: int = 64
    min_slots: int = 256
    factor: float = 2.0
    max_nodes: Optional[int] = None
    max_slots: Optional[int] = None

    def __post_init__(self):
        object.__setattr__(self, "min_nodes", int(self.min_nodes))
        object.__setattr__(self, "min_slots", int(self.min_slots))
        object.__setattr__(self, "factor", float(self.factor))
        for name in ("max_nodes", "max_slots"):
            v = getattr(self, name)
            object.__setattr__(self, name, int(v) if v is not None else None)
        if self.min_nodes <= 0 or self.min_slots <= 0:
            raise ValueError(
                f"grid base cell must be positive; got min_nodes="
                f"{self.min_nodes}, min_slots={self.min_slots}"
            )
        if not self.factor > 1.0:
            raise ValueError(f"factor must be > 1; got {self.factor}")
        if self.max_nodes is not None and self.max_nodes < self.min_nodes:
            raise ValueError(
                f"max_nodes={self.max_nodes} < min_nodes={self.min_nodes}"
            )
        if self.max_slots is not None and self.max_slots < self.min_slots:
            raise ValueError(
                f"max_slots={self.max_slots} < min_slots={self.min_slots}"
            )

    def _round(self, x: int, lo: int) -> int:
        """The smallest cell ``ceil(lo * factor**k)`` holding ``x``.  The
        log only guesses ``k``; the walk settles it on the integer cells
        themselves, so every cell rounds onto itself."""
        if x <= lo:
            return lo

        def cell(k: int) -> int:
            return int(math.ceil(lo * self.factor ** k))

        k = max(0, math.ceil(math.log(x / lo) / math.log(self.factor)))
        while k > 0 and cell(k - 1) >= x:
            k -= 1
        while cell(k) < x:
            k += 1
        return cell(k)

    def _cell(self, n_nodes: int, n_edges_und: int) -> ShapeBudget:
        return ShapeBudget(
            n_budget=self._round(int(n_nodes), self.min_nodes),
            slot_budget=self._round(2 * int(n_edges_und), self.min_slots),
        )

    def fits(self, n_nodes: int, n_edges_und: int) -> bool:
        """True iff the request's grid cell is within the top cell."""
        b = self._cell(n_nodes, n_edges_und)
        return (self.max_nodes is None or b.n_budget <= self.max_nodes) and (
            self.max_slots is None or b.slot_budget <= self.max_slots
        )

    def budget_for(self, n_nodes: int, n_edges_und: int) -> ShapeBudget:
        """Smallest grid cell fitting ``n_nodes`` vertices and
        ``n_edges_und`` undirected edges (2 directed slots each).
        Raises for requests over the top cell — callers owning an
        overflow path (``launch.serve_tc``) check ``fits`` first."""
        if not self.fits(n_nodes, n_edges_und):
            raise ValueError(
                f"request ({n_nodes} nodes, {n_edges_und} edges) exceeds "
                f"the grid's top cell (max_nodes={self.max_nodes}, "
                f"max_slots={self.max_slots}); route it to the "
                f"distributed backend"
            )
        return self._cell(n_nodes, n_edges_und)


DEFAULT_BUDGET_GRID = BudgetGrid()


@dataclasses.dataclass(frozen=True)
class BatchDegreeMeta:
    """Quantized host-side degree metadata of one packed batch — all the
    planner needs to lay out a safe bounded ``IntersectPlan`` without a
    device sync (see ``core.sequential.batch_plan_for``).

    ``d_pad``: pow2-rounded max degree over the batch.  ``h_rows``:
    row-quantized upper bound on any lane's horizontal-query count (its
    undirected edge count).  ``exceed``: per ``META_WIDTHS`` width ``w``,
    a row-quantized upper bound on any lane's number of undirected edges
    whose smaller endpoint has degree > ``w``.  All bounds are rounded
    *up*, so plans built from them stay exact; the rounding exists so
    same-scale batches hash to the same plan-cache / jit-cache keys.
    """

    d_pad: int
    h_rows: int
    exceed: tuple[tuple[int, int], ...]

    def union(self, other: "BatchDegreeMeta") -> "BatchDegreeMeta":
        """Elementwise max of two metas — a valid upper bound for any
        batch either one bounds.  The serving layer pools each flush's
        meta up to a per-cell high-water mark with this, so every batch
        in a cell shares one plan per lane count (a finite, warmable
        compile set) instead of one plan per timing-dependent grouping.
        """
        if [w for w, _ in self.exceed] != [w for w, _ in other.exceed]:
            raise ValueError("cannot union metas over different width grids")
        return BatchDegreeMeta(
            d_pad=max(self.d_pad, other.d_pad),
            h_rows=max(self.h_rows, other.h_rows),
            exceed=tuple(
                (w, max(c, oc))
                for (w, c), (_, oc) in zip(self.exceed, other.exceed)
            ),
        )


def degree_meta(edges: np.ndarray, n_nodes: int) -> BatchDegreeMeta:
    """Quantized ``BatchDegreeMeta`` of ONE request — the same host-side
    statistics ``from_edges_batch`` pools over a batch's lanes, computed
    for a single ``(edges, n_nodes)`` pair.

    The quantizers (pow2 ``d_pad``, ``META_ROW_QUANT`` rows) commute
    with elementwise max, so the ``BatchDegreeMeta.union`` of per-request
    metas upper-bounds the meta of ANY batch packed from those requests
    — which is exactly what the trace recorder (``repro.tune.trace``)
    relies on: a profile's per-cell meta ceiling, unioned from the
    trace's request metas, makes every serving flush of covered traffic
    collide onto the pre-warmed plan-cache key.
    """
    s, d = _normalize_edges(edges, n_nodes)
    m2 = s.shape[0]
    d_max, h_count = 0, 0
    exceed = {w: 0 for w in META_WIDTHS}
    if m2:
        counts = np.bincount(s, minlength=n_nodes + 1)[: max(n_nodes, 1)]
        d_max = int(counts.max())
        h_count = m2 // 2
        und = s < d
        mind = np.minimum(counts[s[und]], counts[d[und]])
        for w in META_WIDTHS:
            exceed[w] = int((mind > w).sum())
    return BatchDegreeMeta(
        d_pad=_next_pow2(max(d_max, 1)),
        h_rows=_ceil_to(max(h_count, 1), META_ROW_QUANT),
        exceed=tuple(
            (w, _ceil_to(c, META_ROW_QUANT) if c else 0)
            for w, c in sorted(exceed.items())
        ),
    )


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class GraphBatch:
    """B budget-padded graphs with a shared static shape.

    Attributes:
      src, dst:     int32[B, slot_budget] per-lane CSR-sorted directed
                    edges; padding has ``src == dst == n_budget``.
      row_offsets:  int32[B, n_budget + 2] per-lane CSR offsets.
      deg:          int32[B, n_budget] per-lane degrees.
      n_nodes:      int32[B] — *real* vertex count of each lane.
      n_edges_dir:  int32[B] — real directed edge count of each lane.
      n_budget:     static shared vertex budget (= the lane sentinel).
      meta:         optional static ``BatchDegreeMeta`` (attached by
                    ``from_edges_batch``; ``None`` on hand-built views).
    """

    src: jnp.ndarray
    dst: jnp.ndarray
    row_offsets: jnp.ndarray
    deg: jnp.ndarray
    n_nodes: jnp.ndarray
    n_edges_dir: jnp.ndarray
    n_budget: int = dataclasses.field(metadata=dict(static=True))
    meta: Optional[BatchDegreeMeta] = dataclasses.field(
        default=None, metadata=dict(static=True)
    )

    @property
    def batch_size(self) -> int:
        return self.src.shape[0]

    @property
    def slot_budget(self) -> int:
        return self.src.shape[1]

    @property
    def budget(self) -> ShapeBudget:
        return ShapeBudget(self.n_budget, self.slot_budget)

    @property
    def sentinel(self) -> int:
        return self.n_budget

    def lane_view(self) -> Graph:
        """The batch as a ``Graph`` pytree with a leading lane axis on
        every array leaf and the *budget* as the static vertex count —
        the form ``jax.vmap`` maps the single-graph algorithms over."""
        return Graph(
            src=self.src, dst=self.dst, row_offsets=self.row_offsets,
            deg=self.deg, n_edges_dir=self.n_edges_dir,
            n_nodes=self.n_budget,
        )


def from_edges_batch(
    graphs: Sequence[tuple[np.ndarray, int]],
    *,
    budget: Optional[ShapeBudget] = None,
    grid: Optional[BudgetGrid] = None,
    batch_size: Optional[int] = None,
    with_meta: bool = True,
) -> GraphBatch:
    """Pack ``(edges, n_nodes)`` requests into one ``GraphBatch``.

    Each request goes through the same host-side normalization as
    ``from_edges`` (dedup / self-loop drop / symmetrize / CSR-sort) and
    is padded onto ``budget`` — by default the smallest ``grid`` cell
    fitting the largest request.  ``batch_size`` pads the batch with
    empty lanes (the serving layer's fixed-B contract); ``with_meta``
    attaches the quantized ``BatchDegreeMeta`` the sync-free bounded
    planner consumes.
    """
    if batch_size is not None and len(graphs) > batch_size:
        raise ValueError(f"{len(graphs)} graphs > batch_size={batch_size}")
    norm = [(_normalize_edges(e, n), int(n)) for e, n in graphs]
    if budget is None:
        grid = grid or DEFAULT_BUDGET_GRID
        budget = grid.budget_for(
            max((n for _, n in norm), default=0),
            max((s.shape[0] for (s, _), _ in norm), default=0) // 2,
        )
    nb, slots = budget.n_budget, budget.slot_budget
    # same index-dtype policy as from_edges: the lane sentinel bounds
    # vertex ids, the slot budget bounds offsets
    vid_dt = jnp_index_dtype(nb, site="csr.from_edges_batch vertex ids")
    off_dt = jnp_index_dtype(slots,
                             site="csr.from_edges_batch row_offsets")
    B = int(batch_size) if batch_size is not None else max(1, len(norm))
    src = np.full((B, slots), nb, dtype=np.int64)
    dst = np.full((B, slots), nb, dtype=np.int64)
    row = np.zeros((B, nb + 2), dtype=np.int64)
    row[:, nb + 1] = slots  # sentinel row closes at the slot budget on
    #   EVERY lane (empty padding lanes included) — the Graph invariant
    deg = np.zeros((B, nb), dtype=np.int64)
    n_nodes = np.zeros(B, dtype=np.int64)
    m2s = np.zeros(B, dtype=np.int64)
    d_max = 0
    h_count = 0
    exceed = {w: 0 for w in META_WIDTHS}
    for i, ((s, d), n) in enumerate(norm):
        m2 = s.shape[0]
        if n > nb:
            raise ValueError(f"graph {i}: n_nodes={n} > n_budget={nb}")
        if m2 > slots:
            raise ValueError(f"graph {i}: 2m={m2} > slot_budget={slots}")
        src[i, :m2] = s
        dst[i, :m2] = d
        counts = np.bincount(s, minlength=nb + 1)[:nb]
        deg[i] = counts
        np.cumsum(counts, out=row[i, 1:nb + 1])
        n_nodes[i] = n
        m2s[i] = m2
        if with_meta and m2:
            d_max = max(d_max, int(counts.max()))
            h_count = max(h_count, m2 // 2)
            und = s < d
            mind = np.minimum(counts[s[und]], counts[d[und]])
            for w in META_WIDTHS:
                exceed[w] = max(exceed[w], int((mind > w).sum()))
    meta = None
    if with_meta:
        meta = BatchDegreeMeta(
            d_pad=_next_pow2(max(d_max, 1)),
            h_rows=_ceil_to(max(h_count, 1), META_ROW_QUANT),
            exceed=tuple(
                (w, _ceil_to(c, META_ROW_QUANT) if c else 0)
                for w, c in sorted(exceed.items())
            ),
        )
    return GraphBatch(
        src=jnp.asarray(src, vid_dt),
        dst=jnp.asarray(dst, vid_dt),
        row_offsets=jnp.asarray(row, off_dt),
        deg=jnp.asarray(deg, vid_dt),
        n_nodes=jnp.asarray(n_nodes, vid_dt),
        n_edges_dir=jnp.asarray(m2s, off_dt),
        n_budget=nb,
        meta=meta,
    )


def to_batch(g: Graph) -> GraphBatch:
    """A zero-copy B=1 ``GraphBatch`` view of a ``Graph`` (the budget is
    the graph's own shape) — how the single-graph API rides the batched
    code path."""
    return GraphBatch(
        src=g.src[None], dst=g.dst[None],
        row_offsets=g.row_offsets[None], deg=g.deg[None],
        n_nodes=jnp.asarray([g.n_nodes], jnp.int32),
        n_edges_dir=g.n_edges_dir[None],
        n_budget=g.n_nodes,
    )


def undirected_edges(g: Graph) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Unique undirected edges as ``(u, w, valid)`` with ``u < w``.

    Returned arrays have ``num_slots`` entries; exactly ``m`` are valid
    (marked by ``valid``), the rest are sentinel-padded.  Order matches the
    CSR edge order restricted to ``src < dst``.
    """
    keep = g.src < g.dst
    u = jnp.where(keep, g.src, g.n_nodes)
    w = jnp.where(keep, g.dst, g.n_nodes)
    return u, w, keep


def gather_rows(
    flat: jnp.ndarray, starts: jnp.ndarray, lens: jnp.ndarray,
    *, width: int, pad: int
) -> jnp.ndarray:
    """Dense ``int32[len(starts), width]`` view of the variable-length
    slices ``flat[starts[i] : starts[i] + lens[i]]``, ``pad``-filled past
    each slice's length.

    The flat-array-plus-bounds form is the common denominator of every
    adjacency source in the repo — CSR ``(dst, row_offsets, deg)`` and the
    lex-sorted pair lists Algorithm 2 receives from its transpose — so the
    intersection engine's dense gathers all route through here.
    """
    if flat.shape[0] == 0:
        return jnp.full((starts.shape[0], width), pad, jnp.int32)
    pos = jnp.arange(width, dtype=jnp.int32)
    idx = jnp.clip(starts[:, None] + pos[None, :], 0, flat.shape[0] - 1)
    ok = pos[None, :] < lens[:, None]
    return jnp.where(ok, flat[idx], pad)


def gather_neighbors(
    g: Graph, v: jnp.ndarray, *, width: int, pad: int
) -> jnp.ndarray:
    """Dense ``int32[len(v), width]`` adjacency rows for vertices ``v``.

    Rows of sentinel vertices (``v == n``) and slots past each vertex's
    degree are filled with ``pad``.  Shared by the Pallas intersect
    front-end (ops.py) and the intersection engine (core/intersect.py)
    so every consumer gathers candidate lists the same way — neighbor
    order is CSR order, i.e. sorted ascending.
    """
    n = g.n_nodes
    deg_ext = jnp.concatenate([g.deg, jnp.zeros((1,), jnp.int32)])
    vc = jnp.clip(v, 0, n)
    starts = g.row_offsets[vc]
    lens = jnp.where(v < n, deg_ext[vc], 0)
    return gather_rows(g.dst, starts, lens, width=width, pad=pad)


def bounded_binary_search(
    sorted_arr: jnp.ndarray,
    starts: jnp.ndarray,
    lengths: jnp.ndarray,
    queries: jnp.ndarray,
    *,
    num_steps: int,
) -> jnp.ndarray:
    """Branch-free membership test of ``queries[i]`` in the sorted slice
    ``sorted_arr[starts[i] : starts[i] + lengths[i]]``.

    Runs ``num_steps`` halving iterations (pass ``ceil(log2(max_len + 1))``).
    This avoids 64-bit packed edge keys entirely (JAX runs x32): an edge
    ``(v, w)`` exists iff ``w`` is found in the CSR slice of ``v``.

    Returns bool[...] of ``queries``' shape.
    """
    lo = starts
    hi = starts + lengths  # exclusive; lower-bound search
    last = sorted_arr.shape[0] - 1
    for _ in range(num_steps):
        cont = lo < hi
        mid = (lo + hi) // 2
        val = sorted_arr[jnp.clip(mid, 0, last)]
        less = (val < queries) & cont
        lo = jnp.where(less, mid + 1, lo)
        hi = jnp.where(cont & ~less, mid, hi)
    found = (lo < starts + lengths) & (
        sorted_arr[jnp.clip(lo, 0, last)] == queries
    )
    return found


def max_degree(g: Graph) -> int:
    """Host-side max degree (static for kernel padding decisions)."""
    return int(jax.device_get(jnp.max(g.deg))) if g.n_nodes else 0
