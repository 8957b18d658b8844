"""One front door for cover-edge triangle counting.

The paper presents ONE algorithm family — sequential Algorithm 1 and the
communication-efficient parallel Algorithm 2 — and this module exposes it
through ONE typed surface (DESIGN.md §6):

* :class:`TCOptions` — every execution knob of every route in a single
  frozen, hashable dataclass, validated in one place.  The plan-relevant
  subset (:meth:`TCOptions.plan_view`) is the bounded-plan cache key.
* :class:`TriangleEngine` — owns routing (``auto`` | ``local`` | ``batch``
  | ``distributed``), the bounded-plan cache, the budget grid, and the
  lazily-built device mesh.  Methods: :meth:`~TriangleEngine.count`,
  :meth:`~TriangleEngine.count_batch`, :meth:`~TriangleEngine.find`,
  :meth:`~TriangleEngine.serve`.
* :class:`TriangleReport` — the unified result contract: ``triangles``
  and ``k`` always present; ``c1``/``c2`` are ``None`` on the distributed
  route (Algorithm 2 counts each triangle exactly once, without the
  apex-level split — no ``-1`` sentinel); every capacity flag normalized
  into one :class:`Overflow` struct; provenance (route taken, plan id,
  resolved backend, the run's ``CommTally`` when distributed).

The historical entry points (``core.sequential.triangle_count`` /
``triangle_count_batch`` / ``find_triangles`` and
``core.parallel_tc.parallel_triangle_count``) remain available as thin
deprecation shims over this engine with bit-identical outputs.

    from repro.api import TriangleEngine

    engine = TriangleEngine()
    report = engine.count((edges, n_nodes))   # or a packed Graph
    print(report.triangles, report.k, report.route)
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Optional, Sequence, Union

import jax
import numpy as np

from repro import obs
from repro.core import parallel_tc as _ptc
from repro.core import sequential as _seq
from repro.core.approx import ApproxEstimate, wedge_sample_estimate
from repro.core.comm_instrument import CommTally, choose_hedge_mode
from repro.core.intersect import (
    DEFAULT_BUCKET_WIDTHS,
    IntersectPlan,
    resolve_backend,
)
from repro.graph.csr import (
    DEFAULT_BUDGET_GRID,
    BudgetGrid,
    Graph,
    GraphBatch,
    from_edges,
    from_edges_batch,
)
from repro.stream.session import StreamSession, StreamStats, StreamUpdate

__all__ = [
    "ROUTES",
    "ApproxEstimate",
    "Overflow",
    "StreamSession",
    "StreamStats",
    "StreamUpdate",
    "TCOptions",
    "TriangleEngine",
    "TriangleReport",
    "default_engine",
]

#: The engine's dispatch targets.  ``auto`` resolves per call: requests
#: whose grid cell fits the engine's ``BudgetGrid`` run locally (a
#: single lane, or the server's batched queue), everything larger goes
#: to the distributed Algorithm 2 backend — the one policy that used to
#: live inside ``TriangleServer.submit``.  ``approx`` is the explicit
#: degraded lane: a host-side wedge-sampled estimate with error bars
#: (``auto`` never picks it — the serving layer degrades to it only
#: under overload or after the exact routes failed, and says so in the
#: report's provenance).  ``stream`` is the mutable-graph route: a
#: session handle (``TriangleEngine.stream()``) that maintains counts
#: incrementally under edge mutations — ``auto`` never picks it either
#: (a stream is a *stateful* conversation, not a one-shot request;
#: ``count(route="stream")`` answers through a fresh one-shot session).
ROUTES = ("auto", "local", "batch", "distributed", "approx", "stream")

_BACKENDS = ("auto", "jnp", "pallas")
_HEDGE_MODES = ("auto", "allgather", "ring")
_FRONTIER_DTYPES = ("int32", "uint8")

#: edge-list input: ``(edges int[any, 2], n_nodes)``
EdgeList = tuple  # noqa: UP006 — runtime-friendly alias, see _as_graph


@dataclasses.dataclass(frozen=True)
class TCOptions:
    """Every execution knob of every route, in one frozen hashable place.

    Shared engine knobs
      backend:        ``"auto" | "jnp" | "pallas"`` intersection backend
                      (``auto`` = Pallas on real TPU, jnp elsewhere).
      interpret:      Pallas interpret override; ``None`` auto-selects.
      bucket_widths:  degree-bucket boundaries of the intersection plans.
      query_chunk:    fori-loop probe-chunk rows (bounds peak memory);
                      also overrides ``row_mult`` when set.
      row_mult:       bucket-row quantization of bounded plans.
      per_vertex:     also return per-vertex triangle attribution
                      (``TriangleReport.per_vertex`` + derived
                      clustering/transitivity/top-k) — computed in-trace
                      during the probe, no second pass; exact on the
                      local, batch and distributed routes (``None`` on
                      approx).  Plan-irrelevant: it never changes the
                      bounded-plan cache key.

    Local / batch route knobs (Algorithm 1)
      d_max:          lossy candidate-width clamp (``None`` = exact).
      cap_h:          cap on the compacted horizontal-query block.
      root:           BFS root.
      compact:        ``False`` = the dense seed reference path.

    Distributed route knobs (Algorithm 2)
      mode:           hedge exchange — ``"auto"`` picks allgather vs ring
                      by live-buffer size (``choose_hedge_mode``).
      slack:          transpose sample-sort capacity slack.
      d_pad:          adjacency pad width (``None`` = graph max degree).
      hedge_chunk:    per-round probe slice / bucket granularity.
      frontier_dtype: BFS frontier wire dtype (``"uint8"`` = 4x fewer
                      BFS bytes per sweep).
      gather_buffer_limit_bytes: allgather live-buffer bound for
                      ``mode="auto"``.

    Routing policy
      route:          default dispatch of ``TriangleEngine.count`` —
                      one of :data:`ROUTES`.
      grid:           :class:`~repro.graph.csr.BudgetGrid` geometry for
                      the batch route / serving queues (``None`` = the
                      module default grid; an explicit
                      ``TriangleEngine(budgets=...)`` outranks it).  The
                      autotuner sweeps this.  Plan-irrelevant: the
                      resulting *cell* is already in the plan-cache key,
                      so ``plan_view()`` resets it.

    Serving robustness (``launch.serve_tc`` — DESIGN.md §7)
      deadline_s:     default per-request deadline (relative seconds);
                      a partially-filled lane flushes when the oldest
                      pending request's slack drops below the budget's
                      measured (EWMA) flush cost.  ``None`` = no
                      deadline — only size/drain flushes (legacy).
      admission_tokens: bound on pending + in-flight requests per
                      ``ShapeBudget`` cell; when a cell is full the
                      server walks the degradation ladder (approx lane,
                      then shed).  ``None`` = unbounded (legacy).
      approx_samples: wedge samples of the approximate lane's estimator.
      approx_on_overload: ``False`` skips the approx rung — overload
                      and failed requests shed immediately with a
                      structured rejection.
      distributed_timeout_s: wall-clock timeout on the blocking
                      distributed path; a timed-out request retries once
                      at a smaller hedge buffer, then degrades.
                      ``None`` = block forever (legacy).

    Streaming route knobs (``repro.stream`` — DESIGN.md §13)
      stream_buffer:  mutation buffer capacity — an ``apply`` stream
                      longer than this is split into buffer-sized
                      batches, each applied and delta-probed
                      independently (bounds per-batch probe width and
                      host work).
      stream_staleness: cover-set staleness threshold — the fraction of
                      vertices touched since the last refresh beyond
                      which the session re-derives BFS levels and the
                      cover classification with one full count (in
                      between, the session answers exactly in the
                      level-free N-hat regime: ``c1``/``c2`` ``None``).
      stream_exact_edges: per-batch exact budget — a batch changing more
                      edges than this skips the exact delta probes and
                      answers through the reservoir-sampled approximate
                      lane (error bars) until the next refresh.
                      ``None`` = always exact.
      stream_approx_rate: the approximate lane's edge-reservoir sampling
                      rate (reservoir capacity ≈ rate × initial edge
                      count, floor 64).
    """

    # -- shared engine knobs ------------------------------------------
    backend: str = "auto"
    interpret: Optional[bool] = None
    bucket_widths: tuple = DEFAULT_BUCKET_WIDTHS
    query_chunk: Optional[int] = None
    row_mult: int = 64
    per_vertex: bool = False
    # -- local / batch route (Algorithm 1) ----------------------------
    d_max: Optional[int] = None
    cap_h: Optional[int] = None
    root: int = 0
    compact: bool = True
    # -- distributed route (Algorithm 2) ------------------------------
    mode: str = "auto"
    slack: float = 4.0
    d_pad: Optional[int] = None
    hedge_chunk: Optional[int] = None
    frontier_dtype: str = "int32"
    gather_buffer_limit_bytes: int = 64 << 20
    # -- routing policy -----------------------------------------------
    route: str = "auto"
    grid: Optional[BudgetGrid] = None
    # -- serving robustness -------------------------------------------
    deadline_s: Optional[float] = None
    admission_tokens: Optional[int] = None
    approx_samples: int = 8192
    approx_on_overload: bool = True
    distributed_timeout_s: Optional[float] = None
    # -- streaming route ----------------------------------------------
    stream_buffer: int = 4096
    stream_staleness: float = 0.25
    stream_exact_edges: Optional[int] = None
    stream_approx_rate: float = 0.05

    def __post_init__(self):
        object.__setattr__(
            self, "bucket_widths",
            tuple(int(w) for w in self.bucket_widths),
        )
        if self.backend not in _BACKENDS:
            raise ValueError(
                f"backend must be one of {_BACKENDS}; got {self.backend!r}"
            )
        if self.mode not in _HEDGE_MODES:
            raise ValueError(
                f"mode must be one of {_HEDGE_MODES}; got {self.mode!r}"
            )
        if self.frontier_dtype not in _FRONTIER_DTYPES:
            raise ValueError(
                f"frontier_dtype must be one of {_FRONTIER_DTYPES}; "
                f"got {self.frontier_dtype!r}"
            )
        if self.route not in ROUTES:
            raise ValueError(
                f"route must be one of {ROUTES}; got {self.route!r}"
            )
        if self.grid is not None and not isinstance(self.grid, BudgetGrid):
            raise TypeError(
                f"grid must be a BudgetGrid or None; "
                f"got {type(self.grid).__name__}"
            )
        for name in ("query_chunk", "d_max", "cap_h", "d_pad",
                     "hedge_chunk"):
            v = getattr(self, name)
            if v is not None and int(v) <= 0:
                raise ValueError(f"{name} must be positive; got {v}")
        if any(w <= 0 for w in self.bucket_widths):
            raise ValueError(
                f"bucket_widths must be positive; got {self.bucket_widths}"
            )
        if self.row_mult <= 0:
            raise ValueError(f"row_mult must be positive; got {self.row_mult}")
        if self.slack <= 0:
            raise ValueError(f"slack must be positive; got {self.slack}")
        if self.gather_buffer_limit_bytes <= 0:
            raise ValueError("gather_buffer_limit_bytes must be positive")
        for name in ("deadline_s", "distributed_timeout_s"):
            v = getattr(self, name)
            if v is not None and float(v) <= 0:
                raise ValueError(f"{name} must be positive; got {v}")
        if self.admission_tokens is not None and int(self.admission_tokens) <= 0:
            raise ValueError(
                f"admission_tokens must be positive; got {self.admission_tokens}"
            )
        if self.approx_samples <= 0:
            raise ValueError(
                f"approx_samples must be positive; got {self.approx_samples}"
            )
        if self.stream_buffer <= 0:
            raise ValueError(
                f"stream_buffer must be positive; got {self.stream_buffer}"
            )
        if self.stream_staleness <= 0:
            raise ValueError(
                f"stream_staleness must be positive; "
                f"got {self.stream_staleness}"
            )
        if (self.stream_exact_edges is not None
                and int(self.stream_exact_edges) <= 0):
            raise ValueError(
                f"stream_exact_edges must be positive; "
                f"got {self.stream_exact_edges}"
            )
        if not 0.0 < self.stream_approx_rate <= 1.0:
            raise ValueError(
                f"stream_approx_rate must lie in (0, 1]; "
                f"got {self.stream_approx_rate}"
            )

    def resolved(self) -> "TCOptions":
        """``backend``/``interpret`` resolved against the current device
        platform (``auto``/``None`` eliminated)."""
        backend, interpret = resolve_backend(self.backend, self.interpret)
        return dataclasses.replace(self, backend=backend, interpret=interpret)

    def plan_view(self) -> "TCOptions":
        """The canonical plan-relevant projection: backend/interpret
        resolved, ``row_mult`` folded to ``query_chunk`` when chunking
        (bucket rows must be a chunk multiple), every field that cannot
        change a bounded plan reset to its default.  Two option sets that
        lay out the same plan project to the SAME value — this is the
        bounded-plan cache key (``core.sequential.batch_plan_for``)."""
        r = self.resolved()
        return TCOptions(
            backend=r.backend,
            interpret=r.interpret,
            bucket_widths=r.bucket_widths,
            query_chunk=r.query_chunk,
            row_mult=int(r.query_chunk) if r.query_chunk else r.row_mult,
        )


@dataclasses.dataclass(frozen=True)
class Overflow:
    """Every way a count can be less than exact, normalized into one
    struct — each flag marks the result invalid rather than silently
    wrong (the engine-wide contract).

    ``h``: horizontal queries dropped (``cap_h``), or a width clamp /
    violated bucket bound truncated candidate lists (local and batch
    routes).  ``transpose`` / ``hedge``: Algorithm 2's sample-sort and
    horizontal-edge-buffer capacity flags (distributed route).
    """

    h: bool = False
    transpose: bool = False
    hedge: bool = False

    @property
    def any(self) -> bool:
        return self.h or self.transpose or self.hedge

    def __bool__(self) -> bool:  # `if report.overflow:` reads naturally
        return self.any


@dataclasses.dataclass(frozen=True)
class TriangleReport:
    """The unified result contract of every route.

    Always present: ``triangles``, ``k`` (measured horizontal-edge
    fraction), ``num_horizontal``, ``overflow``, and the provenance
    fields (``route``, ``backend``, ``plan_id``, ``options``).

    Route-dependent: ``c1``/``c2`` (the apex-level split — ``None`` on
    the distributed and approx routes; there is NO ``-1`` sentinel),
    ``levels`` (BFS levels; local/batch only), ``comm`` (measured
    per-phase wire bytes) and ``per_device`` (per-device partial
    counts) — distributed only; ``approx`` (the wedge-sampling
    :class:`~repro.core.approx.ApproxEstimate` with its error bar) —
    approx route only.  An approx report's ``triangles`` is the rounded
    point estimate, its ``k`` is ``NaN`` and ``num_horizontal`` is 0:
    the estimator never runs the BFS pipeline, and the provenance
    (``route="approx"``, ``plan_id="wedge-sample/<k>"``, the ``approx``
    payload) says exactly that.

    With ``TCOptions(per_vertex=True)`` the exact routes additionally
    carry ``per_vertex`` (int array[n_nodes], each vertex's triangle
    count — ``sum(per_vertex) == 3 * triangles``) and ``degrees``
    (int array[n_nodes]), from which :meth:`local_clustering`,
    :meth:`transitivity` and :meth:`top_k` derive the classic analytics.
    The approx route answers ``per_vertex=None`` — an estimator has no
    attribution to stand behind.

    Stream-route reports (``route="stream"``) always carry ``stream``
    (the session's :class:`~repro.stream.session.StreamStats`:
    staleness metric, refresh/probe counters, exact-lane flag).  A
    freshly-refreshed session reports the full cover-edge payload
    (``levels``, ``c1``/``c2``, measured ``k``); a session with pending
    mutations answers exactly in the level-free N-hat regime
    (``c1``/``c2`` ``None``, ``k`` ``NaN``); an over-budget session
    answers like the approx route (``approx`` payload, no attribution)
    until its next refresh.
    """

    triangles: int
    k: float
    num_horizontal: int
    c1: Optional[int]
    c2: Optional[int]
    overflow: Overflow
    # -- provenance ---------------------------------------------------
    route: str            # the route that actually answered
    backend: str          # resolved intersection backend
    plan_id: str          # human-readable intersection-plan descriptor
    options: TCOptions    # the options the run executed with
    # -- route-dependent payloads -------------------------------------
    levels: Optional[np.ndarray] = None
    comm: Optional[CommTally] = None
    per_device: Optional[np.ndarray] = None
    approx: Optional[ApproxEstimate] = None
    per_vertex: Optional[np.ndarray] = None
    degrees: Optional[np.ndarray] = None
    stream: Optional[StreamStats] = None

    def _require_per_vertex(self) -> None:
        if self.per_vertex is None or self.degrees is None:
            raise ValueError(
                "this report carries no per-vertex attribution; run with "
                "TCOptions(per_vertex=True) on an exact route"
            )

    def local_clustering(self) -> np.ndarray:
        """Per-vertex local clustering coefficient ``t(v) / C(deg(v), 2)``
        (0 where ``deg(v) < 2``), float64[n_nodes]."""
        self._require_per_vertex()
        d = self.degrees.astype(np.int64)
        wedges = d * (d - 1) // 2
        out = np.zeros(d.shape, np.float64)
        np.divide(
            self.per_vertex.astype(np.float64), wedges,
            out=out, where=wedges > 0,
        )
        return out

    def transitivity(self) -> float:
        """Global transitivity ``3T / #wedges`` (0.0 on wedge-free
        graphs) — closed triples over connected triples."""
        self._require_per_vertex()
        d = self.degrees.astype(np.int64)
        wedges = int((d * (d - 1) // 2).sum())
        return 0.0 if wedges == 0 else 3.0 * self.triangles / wedges

    def top_k(self, k: int) -> np.ndarray:
        """Vertex ids of the ``k`` triangle-densest vertices, descending
        by ``per_vertex`` count (ties broken by lower id)."""
        self._require_per_vertex()
        pv = self.per_vertex.astype(np.int64)
        order = np.lexsort((np.arange(pv.shape[0]), -pv))
        return order[: max(0, min(int(k), pv.shape[0]))]


def _plan_id(plan: IntersectPlan, kind: str) -> str:
    """Stable human-readable provenance tag for an intersection plan."""
    shape = "+".join(f"{b.rows}x{b.d_cand}" for b in plan.buckets) or "empty"
    return f"{kind}/{plan.backend}/{shape}"


def _as_graph(graph_or_edges) -> Graph:
    """Accept a packed ``Graph`` or an ``(edges, n_nodes)`` pair."""
    if isinstance(graph_or_edges, Graph):
        return graph_or_edges
    if isinstance(graph_or_edges, GraphBatch):
        raise TypeError(
            "count() takes one graph; use count_batch() for a GraphBatch"
        )
    edges, n_nodes = graph_or_edges
    return from_edges(np.asarray(edges), int(n_nodes))


def _host_edges(g: Graph) -> tuple[np.ndarray, int]:
    """Pull a graph's unique undirected edges back to the host (the
    batch route re-packs onto a budget-grid cell)."""
    src, dst = np.asarray(g.src), np.asarray(g.dst)
    keep = (src < dst) & (dst < g.n_nodes)
    return np.stack([src[keep], dst[keep]], axis=1), g.n_nodes


class TriangleEngine:
    """The facade: one object that owns routing, planning, budgets and
    the mesh, in front of both of the paper's algorithms.

    Args:
      options: default :class:`TCOptions` for every call (per-call
        overrides via the ``options=`` / ``route=`` parameters).
        ``None`` with a ``profile`` adopts the profile's tuned options.
      budgets: the :class:`BudgetGrid` used by the ``batch`` route and
        by ``auto`` routing (its top cell is the local/distributed
        boundary).  ``None`` resolves ``options.grid``, then the
        profile's grid, then the module default grid.
      mesh: device mesh for the distributed route; ``None`` lazily
        builds a 1-D mesh over every local device on first use.
      profile: a :class:`~repro.tune.profile.TunedProfile` (or a path to
        one) from the autotuner — supplies tuned default options, grid
        geometry, per-cell option overrides (``options_for``) and the
        per-cell meta ceilings that make ``serve(prewarm=True)`` cover
        the whole trace.  A corrupt/unknown profile file degrades to
        defaults with a warning, never a construction failure.
      plan_cache_capacity: LRU bound of the engine's bounded-plan cache
        (``None`` = unbounded; default
        ``core.sequential.DEFAULT_PLAN_CACHE_CAPACITY``).
    """

    def __init__(
        self,
        options: Optional[TCOptions] = None,
        *,
        budgets: Optional[BudgetGrid] = None,
        mesh=None,
        profile=None,
        plan_cache_capacity: Optional[int] = (
            _seq.DEFAULT_PLAN_CACHE_CAPACITY
        ),
    ):
        if options is not None and not isinstance(options, TCOptions):
            raise TypeError(
                f"options must be a TCOptions, got {type(options).__name__}"
            )
        self.profile = self._resolve_profile(profile)
        if options is None and self.profile is not None:
            options = self.profile.options
        self.options = options or TCOptions()
        self.budgets = (
            budgets
            or self.options.grid
            or (self.profile.grid if self.profile is not None else None)
            or DEFAULT_BUDGET_GRID
        )
        self._mesh = mesh
        self._plan_cache = _seq.PlanCache(plan_cache_capacity)
        self._plan_stats = {"hits": 0, "misses": 0}
        self._meta_ceiling: dict = {}  # ShapeBudget -> BatchDegreeMeta
        if self.profile is not None:
            # seed the pooled-meta high-water marks with the profile's
            # per-cell ceilings: every flush the trace covered collides
            # onto the ceiling's plan key from request one (the quantizers
            # commute with max — csr.degree_meta), prewarmed or not
            for cell in self.profile.cells:
                if cell.meta is not None:
                    self.pool_meta(cell.budget, cell.meta)

    @staticmethod
    def _resolve_profile(profile):
        if profile is None:
            return None
        from repro.tune.profile import TunedProfile, load_profile

        if isinstance(profile, TunedProfile):
            return profile
        return load_profile(profile)  # None + warning when unusable

    # ------------------------------------------------------------ mesh
    @property
    def mesh(self):
        """The distributed route's mesh (built lazily over every local
        device so purely-local engines never touch the device topology)."""
        if self._mesh is None:
            from jax.sharding import Mesh

            devs = np.array(jax.devices())
            self._mesh = Mesh(devs.reshape(devs.size), ("p",))
        return self._mesh

    # --------------------------------------------------------- routing
    def route_for(
        self, n_nodes: int, n_edges_und: int, *, route: Optional[str] = None
    ) -> str:
        """Resolve ``auto`` for a request of this size: ``local`` while
        the request's grid cell fits the budget grid's top cell,
        ``distributed`` beyond — THE over-budget dispatch policy (the
        serving layer and ``count`` both call exactly this)."""
        r = route or self.options.route
        if r not in ROUTES:
            raise ValueError(f"route must be one of {ROUTES}; got {r!r}")
        if r != "auto":
            return r
        fits = self.budgets.fits(int(n_nodes), int(n_edges_und))
        return "local" if fits else "distributed"

    # -------------------------------------------------------- planning
    def options_for(self, budget) -> TCOptions:
        """Per-cell option resolution: a tuned profile's cell override
        when one covers ``budget``, this engine's default options
        otherwise.  Explicit constructor ``options`` outrank the
        profile's workload-wide default, but not its per-cell
        overrides — the overrides are what the sweep proved out."""
        if self.profile is not None:
            cell = self.profile.cell_for(budget)
            if cell is not None and cell.options is not None:
                return cell.options
        return self.options

    def plan_for(self, gb: GraphBatch) -> IntersectPlan:
        """The engine-owned bounded-plan cache, keyed on
        ``(budget, meta, options.plan_view())`` — the options resolved
        per cell (``options_for``)."""
        return self._plan(gb, None)

    def _plan(self, gb: GraphBatch, options: Optional[TCOptions]):
        """``plan_for``, but a call that gives its own options plans
        with them."""
        return _seq.batch_plan_for(
            gb, options=options or self.options_for(gb.budget),
            cache=self._plan_cache, stats=self._plan_stats,
        )

    def compile_space(self, *, batch_size: int = 8) -> list:
        """The engine's statically enumerated jit compile set: every
        fused-program cache key a ``serve(prewarm=True)`` server over
        this engine can reach from its tuned profile (budget cells ×
        pow2 lane ladder × per-cell plan options) — empty when there
        is no profile.  Pure host arithmetic; nothing compiles.  This
        is the set ``repro.analysis.audit`` asserts finite and the
        serving prewarm compiles verbatim."""
        from repro.analysis.compile_set import enumerate_compile_keys

        return enumerate_compile_keys(self, batch_size=batch_size)

    def pool_meta(self, budget, meta):
        """Pool a batch's degree meta up to the engine's per-cell
        high-water mark and return the pooled meta.

        The plan cache is keyed on the batch's quantized meta, so which
        requests happen to co-flush decides which plan (and which fused
        jit entry) a batch lands on — under continuous batching the
        groupings are timing-dependent, and a novel grouping mid-stream
        means a novel compile and a latency spike.  Serving flushes
        route their meta through here instead: the returned ceiling is
        still a true upper bound (``BatchDegreeMeta.union``), every
        batch a cell has already covered collides onto ONE plan per
        lane count, and the compile set stays finite and warmable.  The
        ceiling only ratchets up (a new per-cell maximum recompiles
        once, then covers everything beneath it).
        """
        prev = self._meta_ceiling.get(budget)
        pooled = meta if prev is None else prev.union(meta)
        self._meta_ceiling[budget] = pooled
        return pooled

    def plan_cache_stats(self, reset: bool = False) -> dict:
        """``{"hits", "misses", "size", "evictions", "capacity"}`` of
        this engine's (LRU-bounded) plan cache."""
        out = dict(
            self._plan_stats,
            size=len(self._plan_cache),
            evictions=self._plan_cache.evictions,
            capacity=self._plan_cache.capacity,
        )
        if reset:
            self._plan_stats.update(hits=0, misses=0)
        return out

    # ------------------------------------------------- raw-result API
    # The legacy entry points are deprecation shims over these: same
    # code paths as count()/count_batch()/find(), returning the legacy
    # device-array result types bit-for-bit.

    def count_raw(
        self, g: Graph, *, options: Optional[TCOptions] = None
    ) -> "_seq.TCResult":
        """Local (Algorithm 1) count returning the raw ``TCResult``."""
        return _seq._triangle_count(g, options or self.options)

    def count_batch_raw(
        self,
        gb: GraphBatch,
        *,
        options: Optional[TCOptions] = None,
        plan: Optional[IntersectPlan] = None,
    ) -> "_seq.TCResult":
        """Batched count returning the raw lane-axis ``TCResult``."""
        return _seq._triangle_count_batch(gb, options or self.options,
                                          plan=plan)

    def find_raw(
        self,
        g: Graph,
        *,
        max_triangles: int,
        options: Optional[TCOptions] = None,
    ):
        """Triangle finding: ``(tri int32[max_triangles, 3], count)``."""
        return _seq._find_triangles(g, options or self.options,
                                    max_triangles=int(max_triangles))

    def count_distributed_raw(
        self,
        g: Graph,
        *,
        mesh=None,
        axis_name: str = "p",
        options: Optional[TCOptions] = None,
    ) -> "_ptc.ParallelTCResult":
        """Distributed (Algorithm 2) count returning the raw
        ``ParallelTCResult``.  Resolves ``mode="auto"`` here — the hedge
        exchange choice is routing policy, and policy lives in the
        engine."""
        o = options or self.options
        mesh = mesh if mesh is not None else self.mesh
        o = self._resolve_hedge_mode(g, mesh, axis_name, o)
        return _ptc._parallel_triangle_count(g, mesh, axis_name=axis_name,
                                             options=o)[0]

    def _resolve_hedge_mode(
        self, g: Graph, mesh, axis_name: str, o: TCOptions
    ) -> TCOptions:
        """``mode="auto"`` -> allgather vs ring by live gathered-buffer
        size (``choose_hedge_mode``, DESIGN.md §5)."""
        if o.mode != "auto":
            return o
        m2 = int(jax.device_get(g.n_edges_dir))
        return dataclasses.replace(o, mode=choose_hedge_mode(
            m2, mesh.shape[axis_name],
            gather_buffer_limit_bytes=o.gather_buffer_limit_bytes,
            slack=o.slack,
        ))

    # ------------------------------------------------------ public API
    @obs.spanned("tc.count")
    def count(
        self,
        graph_or_edges: Union[Graph, EdgeList],
        *,
        route: Optional[str] = None,
        options: Optional[TCOptions] = None,
    ) -> TriangleReport:
        """Count the triangles of one graph — a packed :class:`Graph` or
        an ``(edges, n_nodes)`` pair — on the resolved route.

        ``local`` runs the graph at its own static shape; ``batch``
        rounds it onto the engine's budget grid and runs the cached-plan
        fused batch pipeline (the serving hot path — repeated same-scale
        traffic never replans or recompiles); ``distributed`` runs
        Algorithm 2 over the engine's mesh.  ``auto`` picks local vs
        distributed by the budget grid's top cell (``route_for``).
        Triangles and k are bit-identical across routes.

        Degenerate n=0 graphs are answered at the facade on every route
        (the pipelines index into empty arrays); such a report carries
        the resolved route and its contract (``c1``/``c2`` ``None`` on
        distributed) but no ``comm``/``per_device`` — nothing ran.
        """
        o = options or self.options
        if isinstance(graph_or_edges, GraphBatch):
            raise TypeError(
                "count() takes one graph; use count_batch() for a "
                "GraphBatch"
            )
        is_graph = isinstance(graph_or_edges, Graph)
        if is_graph:
            g, edges = graph_or_edges, None
            n_nodes = g.n_nodes
        else:
            edges, n_nodes = graph_or_edges
            g, edges, n_nodes = None, np.asarray(edges), int(n_nodes)
        m_und = 0
        if (route or o.route) == "auto":
            # the routing size: for an edge list, its (pre-dedup) row
            # count — exactly what the serving layer routes on; for a
            # packed Graph, num_slots/2 is a cheap upper bound (fits =>
            # the graph fits), refined to the true edge count only when
            # slot padding would spuriously overflow the grid
            if is_graph:
                m_und = g.num_slots // 2
                if not self.budgets.fits(n_nodes, m_und):
                    m_und = int(jax.device_get(g.n_edges_dir)) // 2
            elif edges.size:
                m_und = edges.reshape(-1, 2).shape[0]
        r = self.route_for(n_nodes, m_und, route=route)
        if r == "batch" and (o.d_max is not None or o.cap_h is not None):
            raise ValueError(
                "route='batch' uses cached bounded plans; d_max/cap_h "
                "only apply to the local route's exact planning"
            )
        if n_nodes == 0:
            backend, _ = resolve_backend(o.backend, o.interpret)
            no_split = r in ("distributed", "approx")
            empty_pv = (
                np.zeros((0,), np.int32)
                if (o.per_vertex and r != "approx") else None
            )
            return TriangleReport(
                triangles=0, k=0.0, num_horizontal=0,
                c1=None if no_split else 0, c2=None if no_split else 0,
                overflow=Overflow(), route=r, backend=backend,
                plan_id="empty", options=o,
                levels=None if no_split else np.zeros((0,), np.int32),
                per_vertex=empty_pv, degrees=empty_pv,
            )
        if r == "approx":
            return self.count_approx(
                (edges, n_nodes) if g is None else g, options=o
            )
        if r == "stream":
            # a fresh one-shot session: opening it runs the full local
            # count (the session's initial refresh), so this is the
            # zero-mutation streaming baseline — same numbers, stream
            # provenance (``report.stream``).  Long-lived sessions come
            # from ``stream()`` directly.
            return self.stream(
                (edges, n_nodes) if g is None else g, options=o
            ).count()
        if r == "batch":
            # pack the RAW edges once (a Graph input round-trips to the
            # host; an edge-list input never builds the intermediate CSR)
            with obs.span("tc.pack"):
                gb = from_edges_batch(
                    [_host_edges(g) if is_graph else (edges, n_nodes)],
                    grid=self.budgets,
                )
            plan = self._plan(gb, options)
            res = self.count_batch_raw(gb, options=o, plan=plan)
            res = _seq._squeeze_lane(res)
            # the lane is budget-padded: slice attribution (and degrees)
            # back to the request's real vertex count
            return self._report_local(res, o, route="batch",
                                      plan_id=_plan_id(plan, "bounded"),
                                      deg=gb.deg[0], n=n_nodes)
        if g is None:
            with obs.span("tc.ingest"):
                g = from_edges(edges, n_nodes)
        if r == "local":
            res = self.count_raw(g, options=o)
            return self._report_local(res, o, route="local", plan_id=None,
                                      deg=g.deg, n=g.n_nodes)
        if r == "distributed":
            # resolve the hedge mode BEFORE building the report so the
            # provenance (options.mode, plan_id) records the mode that ran
            o = self._resolve_hedge_mode(g, self.mesh, "p", o)
            res, plan = _ptc._parallel_triangle_count(g, self.mesh,
                                                      options=o)
            return self._report_distributed(res, o, plan=plan, deg=g.deg)
        raise ValueError(f"unroutable request (route={r!r})")

    def count_batch(
        self,
        graphs: Union[GraphBatch, Sequence],
        *,
        options: Optional[TCOptions] = None,
    ) -> list:
        """Count every graph of a batch — a packed :class:`GraphBatch`
        or a sequence of ``(edges, n_nodes)`` pairs (packed here onto
        the engine's budget grid) — returning one
        :class:`TriangleReport` per real graph.

        Batches packed with degree metadata run the sync-free cached
        bounded plan (one fused jit, the serving path); metadata-less
        batches fall back to the exact two-stage path.  Lane results are
        bit-identical to ``count(..., route="local")`` per graph.
        """
        o = options or self.options
        if isinstance(graphs, GraphBatch):
            gb, n_real = graphs, graphs.batch_size
        else:
            graphs = list(graphs)
            with obs.span("tc.pack"):
                gb = from_edges_batch(
                    [(np.asarray(e), int(n)) for e, n in graphs],
                    grid=self.budgets,
                )
            n_real = len(graphs)
        plan = None
        can_plan = (gb.meta is not None and o.d_max is None
                    and o.cap_h is None)
        if can_plan:
            plan = self._plan(gb, options)
        res = self.count_batch_raw(gb, options=o, plan=plan)
        backend, _ = resolve_backend(o.backend, o.interpret)
        pid = (_plan_id(plan, "bounded") if plan is not None
               else f"exact/{backend}")
        tri, c1, c2, nh, k, ovf, lev, n_lane = jax.device_get(
            (res.triangles, res.c1, res.c2, res.num_horizontal, res.k,
             res.h_overflow, res.levels, gb.n_nodes)
        )
        pv_b = deg_b = None
        if o.per_vertex and res.per_vertex is not None:
            pv_b, deg_b = (
                np.asarray(x)
                for x in jax.device_get((res.per_vertex, gb.deg))
            )
        return [
            TriangleReport(
                triangles=int(tri[i]), k=float(k[i]),
                num_horizontal=int(nh[i]),
                c1=int(c1[i]), c2=int(c2[i]),
                overflow=Overflow(h=bool(ovf[i])),
                route="batch", backend=backend, plan_id=pid, options=o,
                levels=np.asarray(lev[i]),
                # each lane sliced to ITS real vertex count — padding
                # vertices are isolated and carry zero credit by
                # construction, so nothing is lost in the slice
                per_vertex=(
                    pv_b[i, : int(n_lane[i])] if pv_b is not None else None
                ),
                degrees=(
                    deg_b[i, : int(n_lane[i])] if deg_b is not None else None
                ),
            )
            for i in range(n_real)
        ]

    def count_approx(
        self,
        graph_or_edges: Union[Graph, EdgeList],
        *,
        samples: Optional[int] = None,
        seed: int = 0,
        options: Optional[TCOptions] = None,
    ) -> TriangleReport:
        """The degraded lane: a host-side wedge-sampled estimate
        (``core.approx``) wrapped in the unified report contract.

        ``triangles`` is the rounded point estimate, ``approx`` carries
        the full :class:`ApproxEstimate` (stderr, 95% CI), ``k`` is
        ``NaN`` and ``c1``/``c2`` are ``None`` — nothing about the
        answer pretends the exact pipeline ran.  Deliberately compile-
        free: this is what the server answers with when the device
        pipeline is saturated, failing, or over budget."""
        o = options or self.options
        if isinstance(graph_or_edges, Graph):
            edges, n_nodes = _host_edges(graph_or_edges)
        else:
            edges, n_nodes = graph_or_edges
            edges, n_nodes = np.asarray(edges), int(n_nodes)
        est = wedge_sample_estimate(
            edges, n_nodes,
            samples=int(samples) if samples else o.approx_samples,
            seed=seed,
        )
        backend, _ = resolve_backend(o.backend, o.interpret)
        return TriangleReport(
            triangles=int(round(est.triangles)), k=float("nan"),
            num_horizontal=0, c1=None, c2=None, overflow=Overflow(),
            route="approx", backend=backend,
            plan_id=f"wedge-sample/{est.samples}", options=o,
            approx=est,
        )

    def stream(
        self,
        graph_or_edges: Union[Graph, EdgeList],
        *,
        options: Optional[TCOptions] = None,
        seed: int = 0,
    ) -> StreamSession:
        """Open a live :class:`~repro.stream.session.StreamSession` over
        this engine (DESIGN.md §13).

        The session ingests edge mutation streams in capacity-budgeted
        batches (``stream_buffer``), keeps the exact triangle total (and
        per-vertex credit, with ``per_vertex=True``) current via the
        batch delta rule — every probe runs through this engine's
        ``run_plan`` pipeline — and re-derives the cover-edge state
        lazily once staleness passes ``stream_staleness``.  Batches
        whose net change exceeds ``stream_exact_edges`` flip the session
        to the reservoir-sampled approximate lane until its next
        refresh.  ``session.count()`` answers a ``route="stream"``
        :class:`TriangleReport` at any point; ``seed`` drives only the
        approximate lane's reservoir."""
        return StreamSession(
            self, graph_or_edges, options=options or self.options,
            seed=seed,
        )

    def find(
        self,
        graph_or_edges: Union[Graph, EdgeList],
        *,
        max_triangles: int,
        options: Optional[TCOptions] = None,
    ):
        """Triangle *finding* (local route): the triangles themselves,
        ``(tri int32[max_triangles, 3], count)``; rows past ``count``
        are ``-1``.  Same pipeline, same options, as ``count``."""
        return self.find_raw(_as_graph(graph_or_edges),
                             max_triangles=max_triangles, options=options)

    def serve(self, *, batch_size: int = 8, max_inflight: int = 8,
              strict: bool = False, faults=None, prewarm: bool = False,
              recorder=None):
        """A :class:`~repro.launch.serve_tc.TriangleServer` wired to
        THIS engine: its budget grid buckets the queues, its plan cache
        feeds every flush, its mesh answers over-budget requests, and
        its options govern every lane (incl. the deadline / admission /
        degradation knobs — DESIGN.md §7).  ``strict=True`` restores
        raise-on-malformed ``submit``; ``faults`` injects a
        :class:`~repro.launch.robust.FaultPlan` (chaos testing);
        ``prewarm=True`` compiles the tuned profile's grid and fills the
        plan cache before the first request (DESIGN.md §11);
        ``recorder`` attaches a :class:`~repro.tune.trace.TraceRecorder`
        that captures the workload for offline autotuning."""
        from repro.launch.serve_tc import TriangleServer

        return TriangleServer(engine=self, batch_size=batch_size,
                              max_inflight=max_inflight, strict=strict,
                              faults=faults, prewarm=prewarm,
                              recorder=recorder)

    # -------------------------------------------------- report builders
    def _report_local(
        self,
        res: "_seq.TCResult",
        o: TCOptions,
        *,
        route: str,
        plan_id: Optional[str],
        deg=None,
        n: Optional[int] = None,
    ) -> TriangleReport:
        with obs.span("tc.fetch"):
            tri, c1, c2, nh, k, ovf, lev = jax.device_get(
                (res.triangles, res.c1, res.c2, res.num_horizontal, res.k,
                 res.h_overflow, res.levels)
            )
        backend, _ = resolve_backend(o.backend, o.interpret)
        plan_id = plan_id or f"exact/{backend}"
        pv = degs = None
        if o.per_vertex and res.per_vertex is not None and deg is not None:
            pv, degs = (
                np.asarray(x) for x in jax.device_get((res.per_vertex, deg))
            )
            if n is not None:  # budget-padded lane -> real vertex count
                pv, degs = pv[:n], degs[:n]
        return TriangleReport(
            triangles=int(tri), k=float(k), num_horizontal=int(nh),
            c1=int(c1), c2=int(c2), overflow=Overflow(h=bool(ovf)),
            route=route, backend=backend, plan_id=plan_id, options=o,
            levels=np.asarray(lev), per_vertex=pv, degrees=degs,
        )

    def _report_distributed(
        self,
        res: "_ptc.ParallelTCResult",
        o: TCOptions,
        *,
        plan: IntersectPlan,
        deg=None,
    ) -> TriangleReport:
        with obs.span("tc.fetch"):
            tri, nh, k, t_ovf, h_ovf, pd, comm = jax.device_get(
                (res.triangles, res.num_horizontal, res.k,
                 res.transpose_overflow, res.hedge_overflow, res.per_device,
                 res.comm)
            )
        backend, _ = resolve_backend(o.backend, o.interpret)
        p = pd.shape[0]
        # every device probes ``plan`` once a round: one round after the
        # all-gather, p in the ring; each horizontal edge once per device
        rounds = p if o.mode == "ring" else 1
        obs.incr("dist.counts")
        obs.incr("dist.rows_planned", p * rounds * plan.probe_rows)
        obs.incr("dist.entries_planned", rounds * plan.gather_entries)
        obs.incr("dist.rows_real", p * int(nh))
        obs.incr("dist.wire_bytes", comm.total)
        pv = degs = None
        if res.per_vertex is not None and deg is not None:
            pv, degs = (
                np.asarray(x) for x in jax.device_get((res.per_vertex, deg))
            )
        return TriangleReport(
            triangles=int(tri), k=float(k), num_horizontal=int(nh),
            c1=None, c2=None,  # Alg 2 has no apex-level split — no sentinel
            overflow=Overflow(transpose=bool(t_ovf), hedge=bool(h_ovf)),
            route="distributed", backend=backend,
            plan_id=f"hedge/{o.mode}/p{p}", options=o,
            comm=comm, per_device=np.asarray(pd),
            per_vertex=pv, degrees=degs,
        )


# ------------------------------------------------------- default engine

_DEFAULT_ENGINE: Optional[TriangleEngine] = None


def default_engine() -> TriangleEngine:
    """The process-wide default engine (default options, default grid,
    lazy all-device mesh) — what the legacy deprecation shims run on."""
    global _DEFAULT_ENGINE
    if _DEFAULT_ENGINE is None:
        _DEFAULT_ENGINE = TriangleEngine()
    return _DEFAULT_ENGINE


def _warn_shim(old: str, new: str) -> None:
    """The legacy entry points' deprecation notice (they keep working,
    bit-identically, as shims over the default engine)."""
    warnings.warn(
        f"{old}() is deprecated; call repro.api.{new} on a TriangleEngine "
        "instead (the legacy entry point remains a bit-identical shim)",
        DeprecationWarning,
        stacklevel=3,
    )
