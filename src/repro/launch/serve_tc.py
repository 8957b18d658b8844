"""Triangle-analytics serving: the batched cover-edge pipeline as a
request/response front-end.

The server is a batching front-end over a ``repro.api.TriangleEngine``:
it accepts a stream of edge-list requests (the per-community /
per-ego-net query shape that motivates cover-edge counting), rounds each
onto the engine's ``BudgetGrid`` cell, assembles fixed-B batches per
budget, and runs every batch as ONE fused jit — BFS + horizontal
compaction + planned intersection with a plan from the engine's cache:
no host round-trip inside a batch, a bounded compile grid across the
stream (DESIGN.md §4).

Requests too big for the grid's top cell don't pad a sequential lane to
an arbitrary static shape — ``engine.route_for`` sends them to the
distributed Algorithm 2 route over the engine's mesh, with the exchange
mode picked from the analytic hedge-phase volume (DESIGN.md §5); those
responses follow the unified ``TriangleReport`` contract (``c1``/``c2``
= ``None``, full report attached — DESIGN.md §6).

Production hardening (DESIGN.md §7): every request can carry a
*deadline* — a partially-filled lane flushes the moment the oldest
pending request's slack drops below the budget's measured (EWMA) flush
cost, so p99 no longer depends on a lucky stream mix filling batches;
*admission control* bounds pending + in-flight requests per budget cell
and walks a degradation ladder when a cell is full (queue →
wedge-sampled approximate answer with error bars → structured shed);
the blocking distributed path gets a *wall-clock timeout* and one retry
at a smaller hedge buffer before degrading; and malformed requests come
back as structured :class:`RejectedRequest` results instead of
exceptions mid-stream.  The invariant all of it serves: every submitted
request id receives exactly one structured result — exact, approx, or
rejected — and ``submit``/``drain`` never raise on bad input or device
failure (``strict=True`` restores the old raise-on-malformed contract).
``launch.robust`` supplies the fault-injection plans and the open-loop
bursty load generator that prove the invariant under chaos.

  PYTHONPATH=src python -m repro.launch.serve_tc --smoke
  PYTHONPATH=src python -m repro.launch.serve_tc --requests 96 --batch-sizes 1 2 8 16
"""
from __future__ import annotations

import argparse
import concurrent.futures
import dataclasses
import json
import math
import os
import time
from collections import defaultdict, deque
from typing import Optional, Sequence, Union

import jax
import numpy as np

from repro.core import sequential as seq
from repro.core.intersect import DEFAULT_BUCKET_WIDTHS
from repro.graph import generators as gen
from repro.graph.csr import (
    BudgetGrid,
    ShapeBudget,
    from_edges,
    from_edges_batch,
)


@dataclasses.dataclass
class TriangleAnalytics:
    """One request's serving response: the paper's per-graph analytics
    plus the latency from submit to batch completion.

    ``route`` records which backend answered: ``"batched"`` (a lane of
    the fused batch jit) or ``"distributed"`` (an over-budget graph
    served by Algorithm 2 over the device mesh).  The distributed
    algorithm counts every triangle exactly once without the c1/c2
    apex-level split, so those responses carry ``c1 is None`` and
    ``c2 is None`` — the unified ``repro.api.TriangleReport`` contract
    (the pre-PR-5 ``-1`` sentinel no longer leaks to clients) — plus the
    full report in ``report`` for provenance (plan id, comm tally)."""

    request_id: int
    n_nodes: int
    triangles: int
    c1: Optional[int]
    c2: Optional[int]
    num_horizontal: int
    k: float
    latency_s: float
    budget: Optional[ShapeBudget]
    #: engine width-overflow flag for this lane — False whenever the
    #: bounded plan's bounds were true upper bounds (always, unless a
    #: custom grid/widths setup violates them); True marks the count as
    #: invalid rather than silently wrong.  On the distributed route it
    #: ORs the transpose/hedge capacity flags — same contract: flagged,
    #: never silently wrong.
    overflow: bool = False
    route: str = "batched"
    #: the full ``TriangleReport`` on the distributed and approx routes
    #: (``None`` on batched lanes — the hot path stays lean; every field
    #: a batched response carries is already above)
    report: Optional[object] = None
    #: the wedge-sampling ``ApproxEstimate`` (point estimate, stderr,
    #: 95% CI) when ``route == "approx"`` — the error bar IS the answer
    approx: Optional[object] = None
    #: per-vertex triangle counts (int array[n_nodes], the request's own
    #: vertices — batched lanes are sliced out of the budget-padded
    #: batch) when the engine ran with ``TCOptions(per_vertex=True)``;
    #: ``None`` otherwise, and ALWAYS ``None`` on the approx route — an
    #: estimate carries no attribution
    per_vertex: Optional[object] = None


@dataclasses.dataclass
class RejectedRequest:
    """The shed rung of the degradation ladder — a *structured* answer
    for a request the server could not serve (malformed input, an
    admission-full cell with the approx lane disabled, or an exact path
    that failed beyond retry with no degraded lane left).  Carries the
    request id so one bad client request never aborts a batch of good
    ones, and a machine-readable ``reason``:

      ``"malformed"``   — the request never parsed/validated;
      ``"overloaded"``  — admission control shed it (cell full);
      ``"failed"``      — every serving rung, exact and degraded, failed.
    """

    request_id: int
    reason: str
    detail: str
    latency_s: float = 0.0
    route: str = "rejected"


#: everything ``TriangleServer.results`` may hold — exactly one entry
#: per submitted request id, always
ServeResult = Union[TriangleAnalytics, RejectedRequest]


class FaultInjected(RuntimeError):
    """A deterministic injected failure (``launch.robust.FaultPlan``) —
    a distinct type so chaos tests can tell injected faults from real
    bugs in the recovery paths they exercise."""


@dataclasses.dataclass
class _Pending:
    request_id: int
    edges: np.ndarray
    n_nodes: int
    t_submit: float
    #: absolute ``perf_counter`` deadline (``None`` = no deadline: the
    #: request only flushes on batch-size or drain, the legacy policy)
    deadline: Optional[float] = None


class TriangleServer:
    """Budget-bucketed batching front-end over a ``TriangleEngine``.

    Every policy object lives on the engine: its ``BudgetGrid`` buckets
    the queues AND decides the local/distributed boundary
    (``engine.route_for`` — the one routing policy), its plan cache
    feeds every flush, its options govern every lane, and its mesh
    answers the over-budget requests.  Construct via
    ``TriangleEngine.serve()`` (or pass ``engine=``); the legacy kwargs
    (``intersect_backend``/``grid``/``mesh``/...) build a private engine
    for backward compatibility.

    ``submit`` routes a request to its budget's queue and flushes the
    queue as one batch when it reaches ``batch_size``; ``drain`` flushes
    the partial queues.  Each flush dispatches ONE fused jit keyed on
    ``(budget, lanes, plan)`` — the plan comes from the engine's
    bounded-plan cache, so a repeated traffic mix never replans, never
    resyncs mid-batch, and compiles once per grid cell.

    Two throughput mechanics on top of the batching itself:

    * **pipelining** — XLA dispatch is asynchronous, so a flush only
      *launches* the batch; results are fetched when the in-flight queue
      exceeds ``max_inflight`` (or at ``drain``), letting host-side
      packing of batch k+1 overlap device compute of batch k;
    * **drain right-sizing** — a partial queue is flushed at the
      smallest power-of-two lane count that fits it (padded with empty
      lanes) instead of the full ``batch_size``, so stragglers don't pay
      an 8-lane program for 1 graph.  The compile grid stays bounded:
      budgets x the pow2 ladder up to ``batch_size``.

    Robustness mechanics (all governed by the engine's ``TCOptions``,
    DESIGN.md §7):

    * **deadline-driven continuous batching** — when a request carries a
      deadline (per-submit ``deadline_s`` or ``options.deadline_s``),
      ``_pump_deadlines`` flushes its budget's partial lane as soon as
      the oldest pending deadline's slack falls below the budget's
      measured flush cost (an EWMA of recent flush→completion walls),
      right-sized like drain.  The server is poll-driven, no background
      thread: ``submit``/``drain`` pump automatically; open-loop drivers
      call :meth:`pump` between arrivals.
    * **admission ladder** — with ``options.admission_tokens`` set, a
      full budget cell degrades the incoming request to the compile-free
      wedge-sampled approximate lane (``engine.count_approx``, answer
      with error bars, ``route="approx"``), or sheds it with a
      :class:`RejectedRequest` when ``approx_on_overload=False``.
    * **failure degradation** — a flush or fetch that raises (device
      failure, injected fault) answers every lane of that batch through
      the same approx-or-shed ladder; the distributed path gets
      ``options.distributed_timeout_s`` and one retry at a smaller
      (ring) hedge buffer before degrading.  No exception escapes
      ``submit``/``drain``; every id is answered exactly once.
    """

    #: flush-cost prior (seconds) used for a budget cell before its
    #: first measured flush — deliberately conservative so the first
    #: deadline-carrying request in a cold cell flushes early, not late
    EWMA_PRIOR_S = 0.05
    #: EWMA smoothing factor for per-budget flush-cost tracking
    EWMA_ALPHA = 0.3

    def __init__(
        self,
        engine=None,
        *,
        batch_size: int = 8,
        max_inflight: int = 8,
        strict: bool = False,
        faults=None,
        prewarm: bool = False,
        recorder=None,
        intersect_backend: str = "auto",
        bucket_widths: Sequence[int] = DEFAULT_BUCKET_WIDTHS,
        grid: Optional[BudgetGrid] = None,
        query_chunk: Optional[int] = None,
        root: int = 0,
        mesh=None,
        distributed_mode: str = "auto",
        gather_buffer_limit_bytes: int = 64 << 20,
    ):
        from repro.api import TCOptions, TriangleEngine

        if engine is None:
            # legacy kwarg construction: fold every knob into the typed
            # options and let a private engine own them
            engine = TriangleEngine(
                TCOptions(
                    backend=intersect_backend,
                    bucket_widths=tuple(int(w) for w in bucket_widths),
                    query_chunk=query_chunk,
                    root=root,
                    mode=distributed_mode,
                    gather_buffer_limit_bytes=int(gather_buffer_limit_bytes),
                ),
                budgets=grid,
                mesh=mesh,
            )
        o = engine.options
        if o.d_max is not None or o.cap_h is not None:
            raise ValueError(
                "serving runs cached bounded plans; d_max/cap_h only "
                "apply to the local route's exact planning"
            )
        self.engine = engine
        self.batch_size = int(batch_size)
        self.max_inflight = int(max_inflight)
        self.strict = bool(strict)
        self.faults = faults
        self._pending: dict[ShapeBudget, list[_Pending]] = defaultdict(list)
        self._inflight: deque = deque()
        self._next_id = 0
        self.results: list[ServeResult] = []
        self.batches_run = 0
        self.distributed_requests = 0
        # -- robustness state ------------------------------------------
        #: pending + in-flight request count per budget cell (the
        #: admission-control token ledger)
        self._tokens: dict[ShapeBudget, int] = defaultdict(int)
        #: measured flush→completion cost per budget cell (EWMA seconds)
        self._flush_ewma_s: dict[ShapeBudget, float] = {}
        self.deadline_flushes = 0
        self.size_flushes = 0
        self.approx_answers = 0
        self.rejected_requests = 0
        self.failed_batches = 0
        self.distributed_timeouts = 0
        self.distributed_retries = 0
        #: distributed calls abandoned after timeout — the computation
        #: keeps running on its worker thread (a running jax dispatch
        #: cannot be cancelled); this counts the leak we chose over
        #: blocking the serving loop
        self.abandoned_distributed = 0
        # -- streaming sessions (DESIGN.md §13) ------------------------
        #: named live :class:`~repro.stream.session.StreamSession`
        #: handles — mutation requests address graphs by name
        self._sessions: dict[str, object] = {}
        self.stream_mutations = 0
        # -- autotuning hooks (DESIGN.md §11) --------------------------
        #: optional ``repro.tune.trace.TraceRecorder`` capturing every
        #: well-formed request (shape signature + replayable payload)
        self.recorder = recorder
        if prewarm:
            self.prewarm()
        # summary()'s plan_hit / jit_compiles are measured from AFTER
        # construction (and pre-warm): the warm-up's own misses and
        # compiles are the point of pre-warming, not serving cost
        _ps = self.engine.plan_cache_stats()
        self._plan_baseline = (_ps["hits"], _ps["misses"])
        self._jit_baseline = _jit_cache_size()

    def prewarm(self) -> None:
        """Compile the serving grid and fill the plan cache BEFORE the
        first request, from the engine's tuned profile (DESIGN.md §11).

        For every profile cell that carries a meta ceiling: pool the
        ceiling into the engine's high-water mark, plan at the ceiling,
        and run one empty batch per power-of-two lane count of the drain
        ladder — exactly the ``(budget, lanes, plan)`` jit keys serving
        flushes will use.  Because the meta quantizers commute with
        ``max``, every flush of trace-covered traffic then lands on a
        cached plan and a compiled program: the first real request never
        pays a compile stall.  A profile-less engine pre-warms nothing
        (there is no trace to predict the traffic with).
        """
        profile = getattr(self.engine, "profile", None)
        if profile is None:
            return
        for cell in profile.cells:
            if cell.meta is None:
                continue  # no ceiling — nothing to key the warm plan on
            pooled = self.engine.pool_meta(cell.budget, cell.meta)
            for lanes in lanes_ladder(self.batch_size):
                gb = from_edges_batch(
                    [], budget=cell.budget, batch_size=lanes
                )
                gb = dataclasses.replace(gb, meta=pooled)
                plan = self.engine.plan_for(gb)
                res = self.engine.count_batch_raw(gb, plan=plan)
                jax.block_until_ready(res.triangles)

    @property
    def grid(self) -> BudgetGrid:
        return self.engine.budgets

    def submit(
        self,
        edges: np.ndarray,
        n_nodes: int,
        *,
        deadline_s: Optional[float] = None,
        strict: Optional[bool] = None,
    ) -> int:
        """Enqueue one graph; returns its request id.  Flushes the
        budget's batch when full, or earlier when a pending deadline's
        slack runs out (results land in ``self.results``).  Requests
        over the grid's top cell are answered immediately by the
        distributed backend instead of a batched lane.

        Malformed input (unparseable edge array, negative ``n_nodes``,
        out-of-range endpoints — the packer's packed-key arithmetic
        would silently alias ``id >= n_nodes`` onto fabricated edges)
        is answered with a structured :class:`RejectedRequest` carrying
        this request's id, so one bad client request cannot abort a
        stream of good ones.  ``strict=True`` (per call or server-wide)
        restores the legacy raise-on-malformed behavior.

        ``deadline_s`` is relative to now; ``None`` falls back to
        ``options.deadline_s`` (which may itself be ``None`` = no
        deadline)."""
        self._poll_inflight()  # stamp finished batches BEFORE new host work
        self._pump_deadlines()  # expiring lanes flush BEFORE new admits
        rid = self._next_id
        self._next_id += 1
        strict = self.strict if strict is None else bool(strict)
        t_submit = time.perf_counter()
        try:
            edges = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
            n_nodes = int(n_nodes)
            if n_nodes < 0:
                raise ValueError(f"n_nodes must be >= 0; got {n_nodes}")
            if edges.size and (edges.min() < 0 or edges.max() >= n_nodes):
                raise ValueError(
                    f"edge endpoints must lie in [0, {n_nodes}); "
                    f"got [{edges.min()}, {edges.max()}]"
                )
        except (ValueError, TypeError) as exc:
            if strict:
                raise ValueError(f"request {rid}: {exc}") from exc
            self._reject(rid, "malformed", str(exc), t_submit)
            return rid
        o = self.engine.options
        rel = deadline_s if deadline_s is not None else o.deadline_s
        deadline = t_submit + float(rel) if rel is not None else None
        # the server IS the batch route, so its only dispatch decision is
        # batch-queue vs distributed: force the size policy (route="auto")
        # — an engine whose default route is "local"/"batch" must still
        # have its over-budget requests answered, not crash on budget_for
        route = self.engine.route_for(n_nodes, edges.shape[0], route="auto")
        if route == "distributed":
            self._record_trace(rid, edges, n_nodes, "distributed", None, rel)
            self._serve_distributed(rid, edges, n_nodes, t_submit)
            return rid
        budget = self.grid.budget_for(n_nodes, edges.shape[0])
        self._record_trace(rid, edges, n_nodes, "batch", budget, rel)
        if (o.admission_tokens is not None
                and self._tokens[budget] >= o.admission_tokens):
            # cell full: the ladder's degrade rung (shed if disabled)
            self._degrade(rid, edges, n_nodes, t_submit,
                          budget=budget, why="overloaded",
                          detail=f"budget cell {budget} at "
                                 f"{self._tokens[budget]} tokens")
            return rid
        self._tokens[budget] += 1
        q = self._pending[budget]
        q.append(_Pending(rid, edges, n_nodes, t_submit, deadline))
        if len(q) >= self.batch_size:
            self._flush(budget, cause="size")
        return rid

    # ------------------------------------- streaming sessions (§13)
    def stream_session(
        self, name: str, graph_or_edges=None, *, options=None, seed: int = 0
    ):
        """Open (or fetch) the named live streaming session.

        With ``graph_or_edges`` given, opens a fresh
        :class:`~repro.stream.session.StreamSession` over this server's
        engine and registers it under ``name`` (re-opening a live name
        raises — silently dropping a session's exact state would be a
        correctness bug, close it first).  With ``graph_or_edges``
        omitted, returns the already-open session of that name.
        """
        if graph_or_edges is None:
            try:
                return self._sessions[name]
            except KeyError:
                raise KeyError(
                    f"no open stream session named {name!r}; open one "
                    "with stream_session(name, (edges, n_nodes))"
                ) from None
        if name in self._sessions:
            raise ValueError(
                f"stream session {name!r} is already open; "
                "close_session() it before re-opening the name"
            )
        sess = self.engine.stream(graph_or_edges, options=options,
                                  seed=seed)
        self._sessions[name] = sess
        return sess

    def mutate(self, name: str, updates, *, refresh=None):
        """Apply one edge mutation request to the named session and
        return its :class:`~repro.stream.session.StreamUpdate` (statuses
        per update, exact delta when the batch stayed under budget, the
        session's running total).  Mutations are synchronous host+probe
        work — they never enter the batched device queues."""
        sess = self.stream_session(name)
        up = sess.apply(updates, refresh=refresh)
        self.stream_mutations += len(up.statuses)
        return up

    def stream_count(self, name: str):
        """The named session's current ``route="stream"``
        :class:`~repro.api.TriangleReport` — exact (with per-vertex
        credit when enabled) unless the session is on its approximate
        lane, and always carrying the session's ``StreamStats``."""
        return self.stream_session(name).count()

    def close_session(self, name: str):
        """Close the named session and return its final
        :class:`~repro.stream.session.StreamStats`."""
        sess = self.stream_session(name)
        del self._sessions[name]
        return sess.stats()

    def _record_trace(self, rid, edges, n_nodes, route, budget, rel) -> None:
        """Feed one validated, routed request to the attached trace
        recorder.  Recording is observability, not serving: a recorder
        failure is warned about, never raised into ``submit``'s
        never-raise contract."""
        if self.recorder is None:
            return
        try:
            self.recorder.record(
                request_id=rid, edges=edges, n_nodes=n_nodes,
                route=route, budget=budget, deadline_s=rel,
            )
        except Exception as exc:  # noqa: BLE001 — tracing must not kill serving
            import warnings

            warnings.warn(f"trace recorder failed on request {rid}: {exc}")

    # -------------------------------------------- degradation ladder
    def _reject(self, rid: int, reason: str, detail: str,
                t_submit: float) -> None:
        self.rejected_requests += 1
        self.results.append(RejectedRequest(
            request_id=rid, reason=reason, detail=detail,
            latency_s=time.perf_counter() - t_submit,
        ))

    def _degrade(
        self,
        rid: int,
        edges: np.ndarray,
        n_nodes: int,
        t_submit: float,
        *,
        budget: Optional[ShapeBudget],
        why: str,
        detail: str,
    ) -> None:
        """Rungs 2–3 of the ladder: answer through the compile-free
        wedge-sampled approximate lane (error bars attached, provenance
        honest), else shed with a structured rejection.  Never raises —
        an estimator failure falls through to the shed rung."""
        o = self.engine.options
        if o.approx_on_overload:
            try:
                report = self.engine.count_approx(
                    (edges, n_nodes), seed=rid, options=o
                )
                self.approx_answers += 1
                self.results.append(TriangleAnalytics(
                    request_id=rid, n_nodes=n_nodes,
                    triangles=report.triangles,
                    c1=None, c2=None, num_horizontal=0, k=float("nan"),
                    latency_s=time.perf_counter() - t_submit,
                    budget=budget, overflow=False, route="approx",
                    report=report, approx=report.approx,
                ))
                return
            except Exception as exc:  # noqa: BLE001 — ladder must not raise
                detail = f"{detail}; approx lane failed: {exc}"
        self._reject(rid, why, detail, t_submit)

    def pump(self) -> None:
        """One poll step for open-loop drivers: finalize every finished
        in-flight batch and fire any due deadline flushes.  Safe to call
        at any time, any state, any frequency."""
        self._poll_inflight()
        self._pump_deadlines()

    def _pump_deadlines(self) -> None:
        """Flush every partial lane whose oldest pending deadline has
        less slack left than the budget's measured flush cost — the
        continuous-batching rule that makes p99 a function of deadlines
        instead of stream mix."""
        now = time.perf_counter()
        for budget in [b for b, q in self._pending.items() if q]:
            dls = [p.deadline for p in self._pending[budget]
                   if p.deadline is not None]
            if not dls:
                continue
            cost = self._flush_ewma_s.get(budget, self.EWMA_PRIOR_S)
            if min(dls) - now <= cost:
                self._flush(budget, cause="deadline")

    def _serve_distributed(
        self, rid: int, edges: np.ndarray, n_nodes: int, t_submit: float
    ) -> None:
        """Answer one over-budget request through the engine's
        distributed route (Algorithm 2 over the engine's mesh) — same
        never-silently-wrong overflow contract as the batched lanes,
        same unified result contract: the response carries ``c1 is
        None``/``c2 is None`` (Algorithm 2 has no apex-level split; the
        old ``-1`` sentinel no longer leaks to clients) and the full
        ``TriangleReport`` for provenance.

        The graph keeps its natural (un-budgeted) static shape: each
        distinct over-budget size compiles its own program and plans its
        own hedge buckets, the right trade for rare big-graph traffic —
        the point of the route is answering at all, where a batched lane
        would need an unbounded static budget.

        Robustness: with ``options.distributed_timeout_s`` set the
        (blocking, possibly seconds-long) run executes on a worker
        thread under a wall-clock timeout; a timed-out or failed attempt
        retries ONCE with the hedge exchange forced to ring at an 8×
        smaller gather buffer (the cheap-memory spelling — a stall from
        an oversized live allgather buffer cannot recur), and a second
        failure degrades to the approximate lane.  The host is never
        held hostage by one big request."""
        o = self.engine.options
        g = from_edges(edges, n_nodes)
        attempts = [o]
        if o.mode != "ring" or o.gather_buffer_limit_bytes > (1 << 20):
            attempts.append(dataclasses.replace(
                o, mode="ring",
                gather_buffer_limit_bytes=max(
                    1 << 20, o.gather_buffer_limit_bytes >> 3),
            ))
        report, last_err = None, "no attempt ran"
        for attempt, opts in enumerate(attempts):
            try:
                report = self._run_distributed(g, opts, rid, attempt)
                break
            except Exception as exc:  # noqa: BLE001 — degrade, never raise
                last_err = f"attempt {attempt} ({opts.mode}): {exc}"
                if attempt + 1 < len(attempts):
                    self.distributed_retries += 1
        # batches that finished on-device while the distributed run held
        # the host must be stamped NOW, not at the next submit — the
        # same attribution rule as host packing
        self._poll_inflight()
        if report is None:
            self._degrade(rid, edges, n_nodes, t_submit, budget=None,
                          why="failed", detail=f"distributed: {last_err}")
            return
        self.distributed_requests += 1
        self.results.append(TriangleAnalytics(
            request_id=rid,
            n_nodes=n_nodes,
            triangles=report.triangles,
            c1=report.c1,   # None — the unified TriangleReport contract
            c2=report.c2,   # None
            num_horizontal=report.num_horizontal,
            k=report.k,
            latency_s=time.perf_counter() - t_submit,
            budget=ShapeBudget(n_budget=g.n_nodes,
                               slot_budget=g.num_slots),
            overflow=report.overflow.any,
            route="distributed",
            report=report,
            per_vertex=report.per_vertex,
        ))

    def _run_distributed(self, g, opts, rid: int, attempt: int):
        """One distributed attempt, wall-clock-bounded when
        ``opts.distributed_timeout_s`` is set.  A timed-out dispatch is
        *abandoned* (counted, its thread left to finish — a running jax
        computation cannot be cancelled) rather than blocking the
        serving loop."""
        def call():
            if self.faults is not None:
                self.faults.before_distributed(rid, attempt)
            return self.engine.count(g, route="distributed", options=opts)

        timeout = opts.distributed_timeout_s
        if timeout is None:
            return call()
        ex = concurrent.futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix=f"tc-dist-{rid}"
        )
        fut = ex.submit(call)
        try:
            return fut.result(timeout=timeout)
        except concurrent.futures.TimeoutError:
            self.distributed_timeouts += 1
            self.abandoned_distributed += 1
            raise TimeoutError(
                f"exceeded distributed_timeout_s={timeout}"
            ) from None
        finally:
            ex.shutdown(wait=False)

    def drain(self) -> list[ServeResult]:
        """Flush every partial batch (right-sized), finalize all
        in-flight batches, and return all results so far.  Safe on an
        empty server (no submits yet) — returns the empty list."""
        for budget in [b for b, q in self._pending.items() if q]:
            self._flush(budget, cause="drain")
        while self._inflight:
            self._finalize_one()
        return self.results

    def _flush(self, budget: ShapeBudget, *, cause: str = "size") -> None:
        reqs = self._pending.pop(budget, [])
        if not reqs:
            return
        if cause == "deadline":
            self.deadline_flushes += 1
        else:
            self.size_flushes += 1
        lanes = self.batch_size
        if len(reqs) < lanes:  # partial flush: smallest pow2 ladder step
            lanes = min(
                lanes,
                1 << (len(reqs) - 1).bit_length() if len(reqs) > 1 else 1,
            )
        t_flush = time.perf_counter()
        try:
            if self.faults is not None:
                self.faults.before_batch(self.batches_run)
            gb = from_edges_batch(
                [(r.edges, r.n_nodes) for r in reqs],
                budget=budget,
                batch_size=lanes,
            )
            if gb.meta is not None:  # plan stability: one plan per
                gb = dataclasses.replace(  # (cell, lane count), not one
                    gb, meta=self.engine.pool_meta(budget, gb.meta)
                )  # per timing-dependent grouping
            plan = self.engine.plan_for(gb)
            res = self.engine.count_batch_raw(gb, plan=plan)
        except Exception as exc:  # noqa: BLE001 — device failure: degrade
            self._fail_batch(reqs, budget, exc)
            return
        # res is an in-flight device computation — don't block on it here
        self._inflight.append((reqs, budget, res, t_flush))
        self.batches_run += 1
        self._poll_inflight()
        while len(self._inflight) > self.max_inflight:
            self._finalize_one()

    def _fail_batch(self, reqs, budget: ShapeBudget, exc: Exception) -> None:
        """A flush or fetch raised (simulated or real device failure):
        every request of the batch is still answered — through the
        approx lane when enabled, else a structured rejection — and the
        cell's admission tokens are released.  The invariant survives
        the failure; nothing deadlocks, nothing leaks."""
        self.failed_batches += 1
        self._tokens[budget] -= len(reqs)
        for r in reqs:
            self._degrade(r.request_id, r.edges, r.n_nodes, r.t_submit,
                          budget=budget, why="failed",
                          detail=f"batch dispatch failed: {exc}")

    @staticmethod
    def _batch_ready(res) -> bool:
        return all(x.is_ready() for x in jax.tree_util.tree_leaves(res))

    def _poll_inflight(self) -> None:
        """Finalize every already-finished in-flight batch NOW, so its
        requests' latency is stamped at (close to) device completion.
        Without this, a batch sat in the queue until ``drain`` or the
        ``max_inflight`` high-water mark forced a fetch, and early
        batches' p50/p99 absorbed the host time spent packing every
        later batch in between."""
        while self._inflight and self._batch_ready(self._inflight[0][2]):
            self._finalize_one()

    def _finalize_one(self) -> None:
        reqs, budget, res, t_flush = self._inflight.popleft()
        try:
            fields = (res.triangles, res.c1, res.c2, res.num_horizontal,
                      res.k, res.h_overflow)
            if res.per_vertex is not None:
                fields += (res.per_vertex,)
            got = jax.device_get(fields)
            tri, c1, c2, nh, k, ovf = got[:6]
            pv = got[6] if len(got) > 6 else None
        except Exception as exc:  # noqa: BLE001 — fetch failure: degrade
            self._fail_batch(reqs, budget, exc)
            return
        done = time.perf_counter()
        # flush→completion wall feeds the deadline policy's cost model
        sample = done - t_flush
        prev = self._flush_ewma_s.get(budget)
        self._flush_ewma_s[budget] = (
            sample if prev is None
            else self.EWMA_ALPHA * sample + (1 - self.EWMA_ALPHA) * prev
        )
        self._tokens[budget] -= len(reqs)
        for i, r in enumerate(reqs):
            self.results.append(TriangleAnalytics(
                request_id=r.request_id,
                n_nodes=r.n_nodes,
                triangles=int(tri[i]),
                c1=int(c1[i]),
                c2=int(c2[i]),
                num_horizontal=int(nh[i]),
                k=float(k[i]),
                latency_s=done - r.t_submit,
                budget=budget,
                overflow=bool(ovf[i]),
                # slice this request's vertices out of its budget-padded
                # lane — padding vertices carry zero credit by construction
                per_vertex=(
                    np.asarray(pv[i][: r.n_nodes])
                    if pv is not None else None
                ),
            ))

    def summary(self) -> dict:
        """The ops scrape — safe to call at ANY moment: before the
        first submit, mid-stream with lanes in flight, after an
        all-rejected chaos storm.  Percentiles are over *completed*
        (exact + approx) answers; every ratio a scraper might derive is
        served as guarded counters, never a division here."""
        completed = [r for r in self.results
                     if isinstance(r, TriangleAnalytics)]
        lat = sorted(r.latency_s for r in completed)
        by_route: dict[str, int] = defaultdict(int)
        for r in self.results:  # every answer, "rejected" included
            by_route[r.route] += 1
        # plan_hit / jit_compiles since THIS server came up (post
        # pre-warm): 1.0 / 0 is the pre-warm contract on covered traffic
        ps = self.engine.plan_cache_stats()
        hits = ps["hits"] - self._plan_baseline[0]
        misses = ps["misses"] - self._plan_baseline[1]
        looked = hits + misses
        jit_compiles = max(0, _jit_cache_size() - self._jit_baseline)
        return {
            "plan_hit": 1.0 if looked <= 0 else hits / looked,
            "jit_compiles": jit_compiles,
            "requests": len(self.results),
            "completed": len(completed),
            "rejected": self.rejected_requests,
            "by_route": dict(by_route),
            "batches": self.batches_run,
            "failed_batches": self.failed_batches,
            "distributed_requests": self.distributed_requests,
            "distributed_timeouts": self.distributed_timeouts,
            "distributed_retries": self.distributed_retries,
            "abandoned_distributed": self.abandoned_distributed,
            "deadline_flushes": self.deadline_flushes,
            "size_flushes": self.size_flushes,
            "approx_answers": self.approx_answers,
            "stream_sessions": len(self._sessions),
            "stream_mutations": self.stream_mutations,
            "pending": sum(len(q) for q in self._pending.values()),
            "inflight": len(self._inflight),
            "flush_cost_ewma_ms": {
                f"{b.n_budget}x{b.slot_budget}": 1e3 * v
                for b, v in sorted(self._flush_ewma_s.items())
            },
            "p50_ms": _pct_ms(lat, 50),
            "p99_ms": _pct_ms(lat, 99),
        }


def _pct_ms(sorted_lat: Sequence[float], p: float) -> float:
    """Nearest-rank percentile of a sorted latency list, in ms
    (rank ``ceil(p/100 * N)``, 1-based — the standard definition)."""
    if not sorted_lat:
        return 0.0
    i = max(0, math.ceil(p / 100.0 * len(sorted_lat)) - 1)
    return 1e3 * sorted_lat[min(len(sorted_lat) - 1, i)]


def synth_requests(
    num: int, *, seed: int = 0, smoke: bool = False
) -> list[tuple[np.ndarray, int]]:
    """Mixed small/medium analytics-style stream: per-community ER
    graphs, RMAT ego-net-scale graphs, dense cliques — sizes chosen to
    spread over 2–3 budget-grid cells."""
    rng = np.random.default_rng(seed)
    reqs = []
    for _ in range(num):
        kind = int(rng.integers(0, 3))
        if kind == 0:
            n = int(rng.integers(24, 120))
            reqs.append(gen.erdos_renyi(
                n, float(rng.uniform(0.05, 0.15)),
                seed=int(rng.integers(1 << 30)),
            ))
        elif kind == 1:
            scale = int(rng.integers(5, 7 if smoke else 8))
            reqs.append(gen.rmat(scale, 8, seed=int(rng.integers(1 << 30))))
        else:
            reqs.append(gen.complete(int(rng.integers(5, 14))))
    return reqs


def lanes_ladder(batch_size: int) -> list[int]:
    """The pow2 lane counts a server of this ``batch_size`` can flush
    at: 1, 2, 4, ... then ``batch_size`` itself.  ONE definition shared
    by ``prewarm`` (which compiles exactly these) and the compile-set
    auditor (``repro.analysis.compile_set``, which predicts them) — the
    two cannot drift."""
    ladder, lanes = [], 1
    batch_size = int(batch_size)
    while lanes < batch_size:
        ladder.append(lanes)
        lanes <<= 1
    ladder.append(batch_size)
    return ladder


def _jit_cache_size() -> int:
    return int(seq._tc_batch_fused._cache_size())


def measure_serve(
    *,
    num_requests: int = 96,
    batch_sizes: Sequence[int] = (1, 2, 8, 16),
    intersect_backend: str = "auto",
    seed: int = 0,
    smoke: bool = False,
    out: Optional[str] = None,
) -> dict:
    """Throughput/latency trajectory of the serving layer vs the
    sequential one-graph-per-call loop on the same request mix.

    The sequential baseline gets the same static-shape fairness: each
    graph is budget-padded so its jit cache is bounded by the same grid —
    what a non-batching server would do — and each call syncs its result
    (a served response must).  Both sides are warmed on the identical
    request set first, so compiles are excluded from the measured pass.
    Everything runs on ONE shared ``TriangleEngine`` (its plan cache and
    compile grid persist across the servers, as a deployment's would).
    Writes the row to ``out`` when given (``results/BENCH_serve.json``
    for the full run; smoke invocations must use the untracked
    ``results/BENCH_serve_smoke.json``) and prints the benchmark-harness
    CSV lines.
    """
    from repro.api import TCOptions, TriangleEngine

    engine = TriangleEngine(TCOptions(backend=intersect_backend))
    reqs = synth_requests(num_requests, seed=seed, smoke=smoke)
    grid = engine.budgets
    budgets = [
        grid.budget_for(n, np.asarray(e).reshape(-1, 2).shape[0])
        for e, n in reqs
    ]

    def run_sequential() -> tuple[float, list[float], list[int]]:
        lats, tris = [], []
        t0 = time.perf_counter()
        for (e, n), b in zip(reqs, budgets):
            t1 = time.perf_counter()
            g = from_edges(e, b.n_budget, num_slots=b.slot_budget)
            r = engine.count_raw(g)
            tris.append(int(r.triangles))  # the response forces this sync
            lats.append(time.perf_counter() - t1)
        return time.perf_counter() - t0, lats, tris

    run_sequential()  # warm the per-budget compile grid
    seq_wall, seq_lats, seq_tris = run_sequential()
    seq_total = sum(seq_tris)
    seq_lats.sort()

    row: dict = {
        "num_requests": num_requests,
        "seed": seed,
        "smoke": smoke,
        "backend": intersect_backend,
        "sequential": {
            "graphs_per_s": num_requests / seq_wall,
            "wall_s": seq_wall,
            "p50_ms": _pct_ms(seq_lats, 50),
            "p99_ms": _pct_ms(seq_lats, 99),
            "triangles_total": seq_total,
        },
        "batched": [],
        "agree": True,
    }
    print(f"serve_seq,{seq_wall / num_requests * 1e6:.0f},"
          f"graphs_per_s={num_requests / seq_wall:.1f}"
          f"|p50_ms={_pct_ms(seq_lats, 50):.2f}|p99_ms={_pct_ms(seq_lats, 99):.2f}")

    for B in batch_sizes:
        warm = engine.serve(batch_size=B)
        for e, n in reqs:
            warm.submit(e, n)
        warm.drain()  # compile grid + plan cache now hot
        engine.plan_cache_stats(reset=True)
        jit0 = _jit_cache_size()
        server = engine.serve(batch_size=B)
        t0 = time.perf_counter()
        for e, n in reqs:
            server.submit(e, n)
        server.drain()
        wall = time.perf_counter() - t0
        stats = server.summary()
        plan_stats = engine.plan_cache_stats()
        jit1 = _jit_cache_size()
        total = sum(r.triangles for r in server.results)
        # PER-REQUEST agreement (request ids are the submit order), not a
        # stream total that compensating errors could fake — plus the
        # engine's overflow flag on every lane
        by_id = {r.request_id: r for r in server.results}
        agree = len(by_id) == num_requests and all(
            by_id[i].triangles == seq_tris[i] and not by_id[i].overflow
            for i in range(num_requests)
        )
        row["agree"] = row["agree"] and agree
        looked = plan_stats["hits"] + plan_stats["misses"]
        entry = {
            "batch_size": B,
            "graphs_per_s": num_requests / wall,
            "wall_s": wall,
            "p50_ms": stats["p50_ms"],
            "p99_ms": stats["p99_ms"],
            "batches": stats["batches"],
            "speedup_vs_sequential": seq_wall / wall,
            "plan_cache_hit_rate": plan_stats["hits"] / max(looked, 1),
            "jit_compiles_measured": max(0, jit1 - jit0),
            "triangles_total": total,
            "agree": agree,
        }
        row["batched"].append(entry)
        print(f"serve_b{B},{wall / num_requests * 1e6:.0f},"
              f"graphs_per_s={entry['graphs_per_s']:.1f}"
              f"|speedup={entry['speedup_vs_sequential']:.2f}x"
              f"|p50_ms={entry['p50_ms']:.2f}|p99_ms={entry['p99_ms']:.2f}"
              f"|plan_hit={entry['plan_cache_hit_rate']:.2f}"
              f"|agree={agree}")

    if out:
        os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
        with open(out, "w") as f:
            json.dump(row, f, indent=2)
        print(f"serve_json,0,written={os.path.normpath(out)}")
    return row


def main(argv: Optional[list[str]] = None) -> None:
    ap = argparse.ArgumentParser(
        description="Batched triangle-analytics serving benchmark/smoke"
    )
    ap.add_argument("--smoke", action="store_true",
                    help="small fixed workload (CI); writes the untracked"
                         " results/BENCH_serve_smoke.json unless --out"
                         " is given")
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--batch-sizes", type=int, nargs="+", default=None)
    ap.add_argument("--backend", default="auto")
    ap.add_argument("--seed", type=int, default=0)
    # smoke output must NOT land in BENCH_serve.json: that file is the
    # full-run perf trajectory tracked across PRs (README "Benchmarks")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.out is None:
        args.out = os.path.join(
            "results",
            "BENCH_serve_smoke.json" if args.smoke else "BENCH_serve.json",
        )
    num = args.requests or (24 if args.smoke else 96)
    sizes = tuple(args.batch_sizes or ((8,) if args.smoke else (1, 2, 8, 16)))
    row = measure_serve(
        num_requests=num, batch_sizes=sizes,
        intersect_backend=args.backend, seed=args.seed, smoke=args.smoke,
        out=args.out,
    )
    if not row["agree"]:
        raise SystemExit(
            "FAIL: batched serving results disagree with the sequential loop"
        )


if __name__ == "__main__":
    main()
