"""Chaos harness for the triangle-analytics serving layer: deterministic
fault injection, an open-loop bursty load generator, and the replay
driver that proves the serving invariant.

The invariant under test (DESIGN.md §7): every submitted request id
receives exactly one structured result — exact, approx-with-error-bar,
or rejected — and ``submit``/``drain`` never raise and never leak an
in-flight batch, no matter what the stream or the devices do.

Three pieces:

* :class:`FaultPlan` — a frozen, id/ordinal-keyed injection schedule
  (malformed requests, oversized graphs, compile stalls, simulated
  device failures on batch dispatch and on the distributed path).  Same
  plan + same trace = same faults, so a chaos failure reproduces.
* :func:`synth_requests` — the open-loop generator: the same request
  mix as ``serve_tc.synth_requests`` but stamped with *arrival times*
  (``arrival="poisson"`` steady load, ``arrival="burst"`` back-to-back
  bursts separated by idle gaps — the stream mix that starves a
  fixed-B flush policy and makes deadline-driven flushing earn its p99).
* :func:`run_chaos` — replays a trace against a ``TriangleServer`` in
  real time (pumping between arrivals, as an open-loop driver must),
  applies the plan's stream-side mutations, and audits the invariant:
  per-id accounting, no unanswered, no duplicates, nothing left
  pending or in flight.

  PYTHONPATH=src python -m repro.launch.robust --smoke
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import NamedTuple, Optional

import numpy as np

from repro.graph import generators as gen
from repro.launch import serve_tc
from repro.launch.serve_tc import FaultInjected, RejectedRequest, TriangleAnalytics

ARRIVALS = ("poisson", "burst")


def _hits(every: int, i: int) -> bool:
    """Deterministic schedule predicate: ordinal ``i`` is selected when
    ``every > 0`` and ``i % every == every - 1`` (never ordinal 0, so a
    run's first request/batch always establishes the happy path)."""
    return every > 0 and i % every == every - 1


@dataclasses.dataclass(frozen=True)
class FaultPlan:
    """A deterministic fault-injection schedule.

    Stream-side mutations (applied by :func:`run_chaos` before submit,
    keyed on the request's trace ordinal):

      malformed_every:  replace the request with an out-of-range-endpoint
                        edge list — must come back ``RejectedRequest``
                        ("malformed"), not an exception.
      oversized_every:  replace with a graph over the grid's top cell
                        (``oversized_nodes`` star) — must route
                        distributed, and degrade if that also fails.

    Server-side injections (the server calls the hooks; keyed on batch
    ordinal / request id so they are trace-order deterministic):

      stall_batch_every / stall_s:   sleep before dispatching the batch —
                        a simulated compile stall; deadlines slip, the
                        system must still answer everything.
      fail_batch_every: raise :class:`FaultInjected` at batch dispatch —
                        a simulated device failure; every lane must be
                        answered through the degradation ladder.
      fail_distributed_every / fail_distributed_attempts: raise on the
                        distributed path for selected request ids, for
                        the first N attempts (1 = first attempt fails,
                        the retry succeeds; 2 = both fail, the request
                        degrades to the approximate lane).
      stall_distributed_every / distributed_stall_s: sleep inside the
                        distributed call instead — with
                        ``options.distributed_timeout_s`` set this
                        exercises the wall-clock timeout/abandon path.
    """

    malformed_every: int = 0
    oversized_every: int = 0
    oversized_nodes: int = 4096
    stall_batch_every: int = 0
    stall_s: float = 0.05
    fail_batch_every: int = 0
    fail_distributed_every: int = 0
    fail_distributed_attempts: int = 1
    stall_distributed_every: int = 0
    distributed_stall_s: float = 0.5

    # ------------------------------------------ stream-side mutation
    def mutate(self, i: int, edges: np.ndarray, n_nodes: int):
        """The (possibly faulted) request actually submitted for trace
        ordinal ``i``."""
        if _hits(self.malformed_every, i):
            # endpoint == n_nodes: exactly the aliasing class submit()
            # must reject structurally
            return np.array([[0, int(n_nodes)]], dtype=np.int64), int(n_nodes)
        if _hits(self.oversized_every, i):
            return gen.star(int(self.oversized_nodes))
        return edges, n_nodes

    # ---------------------------------------- server-side injections
    def before_batch(self, batch_idx: int) -> None:
        """TriangleServer hook: called once per flush, before dispatch."""
        if _hits(self.stall_batch_every, batch_idx):
            time.sleep(self.stall_s)
        if _hits(self.fail_batch_every, batch_idx):
            raise FaultInjected(f"injected device failure @ batch {batch_idx}")

    def before_distributed(self, rid: int, attempt: int) -> None:
        """TriangleServer hook: called per distributed attempt."""
        if _hits(self.stall_distributed_every, rid):
            time.sleep(self.distributed_stall_s)
        if (_hits(self.fail_distributed_every, rid)
                and attempt < self.fail_distributed_attempts):
            raise FaultInjected(
                f"injected distributed failure @ request {rid} "
                f"attempt {attempt}"
            )


class TimedRequest(NamedTuple):
    """One open-loop arrival: submit ``(edges, n_nodes)`` at ``t``
    seconds after trace start."""

    t: float
    edges: np.ndarray
    n_nodes: int


def synth_requests(
    num: int,
    *,
    arrival: str = "poisson",
    rate_hz: float = 200.0,
    burst_len: int = 16,
    burst_gap_s: float = 0.25,
    mix: str = "serve",
    uniform_scale: int = 6,
    seed: int = 0,
    smoke: bool = False,
) -> list[TimedRequest]:
    """Arrival-stamped open-loop trace.

    ``"poisson"``: exponential inter-arrival gaps at ``rate_hz`` — the
    steady-state load.  ``"burst"``: groups of ``burst_len`` requests
    arriving back-to-back (at 10× ``rate_hz`` spacing) separated by
    ``burst_gap_s`` idle — same mean intensity knobs, radically worse
    tail for any fixed-B flush policy, because every burst strands its
    tail across partially-filled budget cells until the next burst (or
    drain).  This is the trace BENCH_robust measures deadline-driven
    flushing against.

    ``mix="serve"`` draws from the standard mixed serving stream
    (``serve_tc.synth_requests`` — several budget cells, many distinct
    bounded plans: the chaos workload).  ``mix="uniform"`` draws
    same-scale RMAT graphs with varying seeds — one grid cell, a shared
    plan — so a latency comparison between flush policies measures the
    *policy*, not compile-grid luck across groupings.
    """
    if arrival not in ARRIVALS:
        raise ValueError(f"arrival must be one of {ARRIVALS}; got {arrival!r}")
    if mix not in ("serve", "uniform"):
        raise ValueError(f"mix must be 'serve' or 'uniform'; got {mix!r}")
    rng0 = np.random.default_rng(seed)
    if mix == "uniform":
        base = [gen.rmat(uniform_scale, 8, seed=int(rng0.integers(1 << 30)))
                for _ in range(num)]
    else:
        base = serve_tc.synth_requests(num, seed=seed, smoke=smoke)
    rng = np.random.default_rng(seed + 0x5EED)
    if arrival == "poisson":
        gaps = rng.exponential(1.0 / rate_hz, size=num)
    else:
        gaps = np.full(num, 0.1 / rate_hz)
        gaps[::burst_len] = burst_gap_s  # a gap opens each burst
    t = np.cumsum(gaps) - gaps[0]  # first arrival at t=0
    return [TimedRequest(float(t[i]), e, n)
            for i, (e, n) in enumerate(base)]


def run_chaos(
    server,
    trace: list[TimedRequest],
    *,
    faults: Optional[FaultPlan] = None,
    speed: float = 1.0,
    pump_interval_s: float = 0.002,
) -> dict:
    """Replay ``trace`` open-loop against ``server`` (submitting at the
    stamped arrival times — scaled by ``speed`` — and pumping between
    arrivals), apply ``faults``' stream-side mutations, drain, and audit
    the serving invariant.

    Returns the audit: ``unanswered``/``duplicates`` (both must be
    empty), per-category counts, wall time, and the server's final ops
    summary.  The *server-side* hooks of the plan must already be
    installed on the server (``faults=`` at construction) — this driver
    only owns the stream-side mutations, so a plan-free server replay is
    the same code path.
    """
    t0 = time.perf_counter()
    submitted: list[int] = []
    for i, req in enumerate(trace):
        target = t0 + req.t / speed
        # one pump between arrivals however late the replay runs, then
        # more while it waits for the stamped time
        server.pump()
        while (now := time.perf_counter()) < target:
            time.sleep(min(pump_interval_s, target - now))
            server.pump()
        edges, n_nodes = (faults.mutate(i, req.edges, req.n_nodes)
                          if faults is not None
                          else (req.edges, req.n_nodes))
        submitted.append(server.submit(edges, n_nodes))
    results = server.drain()
    wall = time.perf_counter() - t0

    ids = [r.request_id for r in results]
    seen: set[int] = set()
    duplicates = sorted({i for i in ids if i in seen or seen.add(i)})
    unanswered = sorted(set(submitted) - seen)
    stats = server.summary()
    return {
        "submitted": len(submitted),
        "answered": len(seen),
        "unanswered": unanswered,
        "duplicates": duplicates,
        "exact": sum(1 for r in results
                     if isinstance(r, TriangleAnalytics)
                     and r.route in ("batched", "distributed")),
        "approx": sum(1 for r in results
                      if isinstance(r, TriangleAnalytics)
                      and r.route == "approx"),
        "rejected": sum(1 for r in results
                        if isinstance(r, RejectedRequest)),
        "leaked_pending": stats["pending"],
        "leaked_inflight": stats["inflight"],
        "wall_s": wall,
        "summary": stats,
        "ok": (not unanswered and not duplicates
               and stats["pending"] == 0 and stats["inflight"] == 0),
    }


def main(argv: Optional[list[str]] = None) -> None:
    """Standalone chaos smoke: bursty trace + the full fault plan; exits
    nonzero if any request goes unanswered (CI's robust_smoke lane runs
    the richer ``benchmarks/run.py robust_smoke`` instead)."""
    from repro.api import TCOptions, TriangleEngine
    from repro.graph.csr import BudgetGrid

    ap = argparse.ArgumentParser(description="Serving chaos smoke")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    num = args.requests or 48

    plan = FaultPlan(malformed_every=7, oversized_every=11,
                     oversized_nodes=600, stall_batch_every=5,
                     stall_s=0.02, fail_batch_every=6,
                     fail_distributed_every=1, fail_distributed_attempts=2)
    engine = TriangleEngine(
        TCOptions(backend="jnp", deadline_s=0.05, admission_tokens=16,
                  approx_samples=4096),
        budgets=BudgetGrid(max_nodes=256, max_slots=4096),
    )
    server = engine.serve(batch_size=8, faults=plan)
    trace = synth_requests(num, arrival="burst", rate_hz=400.0,
                           burst_len=12, burst_gap_s=0.05,
                           seed=args.seed, smoke=True)
    audit = run_chaos(server, trace, faults=plan)
    print(f"chaos,{audit['wall_s'] / num * 1e6:.0f},"
          f"answered={audit['answered']}/{audit['submitted']}"
          f"|exact={audit['exact']}|approx={audit['approx']}"
          f"|rejected={audit['rejected']}|ok={audit['ok']}")
    if not audit["ok"]:
        raise SystemExit(f"FAIL: chaos audit violated the serving "
                         f"invariant: {audit}")


if __name__ == "__main__":
    main()
