"""Measured communication of Algorithm 2 — closing the modeled-vs-real loop.

``core.comm_model`` *models* what the distributed program should move
(closed forms over static capacities); this module *measures* what the
``shard_map`` program actually contains, three ways that must agree:

  1. **analytic tally** — ``CommTally``: per-phase wire bytes computed
     inside ``parallel_tc._tc_shard`` itself (``tally_comm``) from the
     same static capacities plus the one dynamic quantity, the BFS sweep
     count, and returned as a field of every ``ParallelTCResult``;
  2. **program inspection** — ``collect_collective_sites`` walks the
     jaxpr of the lowered shard_map program and inventories every
     collective (kind, per-shard shape, enclosing-loop multiplier),
     pricing each with the ``comm_model.*_wire_bytes`` conventions;
     ``verify_against_hlo`` cross-checks the inventory against the
     StableHLO text (``Compiled.cost_analysis()`` offers the XLA-side
     aggregate for context);
  3. **closed-form model** — ``comm_model.wire_bytes_report``, keyed by
     the same ``WIRE_PHASES`` names.

The contract (asserted in ``tests/test_comm_instrument.py``): measured
(2) == tally (1) exactly, per phase, for any p and both exchange modes;
and modeled (3) == both whenever its ``n_levels`` equals the run's sweep
count (an upper-bound ``n_levels`` makes it a per-phase envelope).

Phase attribution is structural: all-to-alls are the transpose,
all-gathers before the transpose are splitter gossip and after it the
horizontal exchange, ppermutes are ring-mode horizontal rounds,
n-vector pmax all-reduces are BFS level syncs (per-sweep when inside
the BFS while loop), and everything else that reduces — the scalar
psums/pmaxes plus, with per-vertex attribution on, the n-vector credit
psum — is the final reduction phase.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.walker import (
    COLLECTIVE_PRIMITIVES,
    iter_eqns,
    unwrap as _unwrap_jaxpr,
    uses_axis as _uses_axis,
)
from repro.core.comm_model import (
    NUM_SCALAR_REDUCES,
    WIRE_PHASES,
    allgather_wire_bytes,
    allreduce_wire_bytes,
    alltoall_wire_bytes,
    ppermute_wire_bytes,
)

_REDUCE_PRIMS = ("psum", "pmax", "pmin")
#: the all-reduce JAX binds for ``psum`` of a device-varying value; the
#: inventory records it as the ``psum`` it is
_PSUM_ALIASES = {"psum_invariant": "psum"}


#: Largest per-field value the in-trace tally stores.  A phase beyond
#: ~2 GiB of wire saturates here instead of crashing the trace — the
#: big-graph serving route must keep counting triangles even when the
#: int32 odometer pegs; the float-valued ``comm_model.wire_bytes_report``
#: is the accounting tool at that scale.
TALLY_SAT_BYTES = 2**31 - 1


def _sat32(x) -> jnp.ndarray:
    return jnp.int32(min(int(x), TALLY_SAT_BYTES))


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class CommTally:
    """Per-phase wire bytes (int32 scalars, summed over ALL devices) of
    one Algorithm 2 run, computed analytically inside the shard program.

    ``bfs_sweeps`` is the one data-dependent factor: the number of
    frontier exchanges the level-synchronous BFS executed (= max level
    + 1, reseeds included).  The BFS phase is stored as its exact parts
    (``bfs_fixed`` + ``bfs_per_sweep``, resolved against the sweep count
    with unbounded host arithmetic in ``phase_bytes``); every other
    phase is a pure function of the static capacities.  The tally is
    exact — the instrument tests assert it equals the per-collective
    measurement bit for bit — up to ``TALLY_SAT_BYTES`` per field,
    where it saturates rather than abort a run whose whole point is a
    graph that big (use ``comm_model.wire_bytes_report`` there).
    """

    bfs_fixed: jnp.ndarray      # has-edge seeding pmax, once per run
    bfs_per_sweep: jnp.ndarray  # frontier pmax, once per BFS sweep
    splitter: jnp.ndarray
    transpose: jnp.ndarray
    hedge: jnp.ndarray
    reduce: jnp.ndarray
    bfs_sweeps: jnp.ndarray

    def phase_bytes(self) -> dict[str, int]:
        """Host-side ``{phase: total_bytes}`` keyed by ``WIRE_PHASES``."""
        fixed, per_sweep, sweeps = (int(jax.device_get(x)) for x in (
            self.bfs_fixed, self.bfs_per_sweep, self.bfs_sweeps))
        out = {"bfs": fixed + per_sweep * sweeps}
        for ph in WIRE_PHASES[1:]:
            out[ph] = int(jax.device_get(getattr(self, ph)))
        return out

    @property
    def total(self) -> int:
        return sum(self.phase_bytes().values())


def tally_comm(
    *,
    n: int,
    p: int,
    cap_chunk: int,
    cap_hedge: int,
    mode: str,
    frontier_dtype: str,
    sweeps,
    per_vertex: bool = False,
) -> CommTally:
    """Analytic ``CommTally`` of one shard-program run.  ``sweeps`` may
    be a traced int32 (the in-trace call from ``_tc_shard``) or a host
    int; every other argument is static.  Formulas mirror
    ``comm_model.wire_bytes_report`` term by term — by construction,
    since both sides call the same ``*_wire_bytes`` conventions.
    ``per_vertex`` adds the attribution feature's one extra collective —
    an n-vector credit psum — to the reduce phase."""
    word = 4
    fsize = np.dtype(frontier_dtype).itemsize
    if mode == "allgather":
        hedge = 2 * int(allgather_wire_bytes(cap_hedge * word, p))
    elif mode == "ring":
        # p-1 rounds x p-cycle cross pairs — equals the allgather volume
        cross = p if p > 1 else 0
        hedge = 2 * (p - 1) * int(ppermute_wire_bytes(cap_hedge * word,
                                                      cross))
    else:
        raise ValueError(mode)
    return CommTally(
        bfs_fixed=_sat32(allreduce_wire_bytes(n * word, p)),
        bfs_per_sweep=_sat32(allreduce_wire_bytes(n * fsize, p)),
        splitter=_sat32(allgather_wire_bytes(p * word, p)),
        transpose=_sat32(2 * alltoall_wire_bytes(p * cap_chunk * word, p)),
        hedge=_sat32(hedge),
        reduce=_sat32(
            NUM_SCALAR_REDUCES * allreduce_wire_bytes(word, p)
            + (allreduce_wire_bytes(n * word, p) if per_vertex else 0)
        ),
        bfs_sweeps=jnp.asarray(sweeps, jnp.int32),
    )


# ------------------------------------------------ program inspection


@dataclasses.dataclass(frozen=True)
class CollectiveSite:
    """One collective op found in the lowered program.

    ``bytes_fixed`` is its total wire volume per program run (static
    loop trip counts folded in); ``bytes_per_sweep`` is nonzero only for
    collectives inside the BFS while loop, whose trip count is the
    data-dependent sweep count."""

    kind: str          # all_gather | all_to_all | ppermute | psum | pmax
    phase: str         # one of comm_model.WIRE_PHASES
    shape: tuple
    dtype: str
    bytes_fixed: int
    bytes_per_sweep: int
    trips: int         # static multiplier applied (enclosing scan lengths)


def collect_collective_sites(
    closed_jaxpr, *, n: int, p: int, axis_name: str = "p"
) -> list[CollectiveSite]:
    """Inventory every collective over ``axis_name`` in a (closed) jaxpr,
    classified by phase and priced by the shared wire conventions.

    Traversal is the shared walker (``repro.analysis.walker`` — the PR 4
    machinery, extracted): collectives inside ``scan`` bodies get the
    (static) trip count as a multiplier; collectives inside ``while``
    bodies are flagged per-sweep (the BFS frontier exchange — the only
    dynamically-trip-counted loop in the program)."""
    sites: list[CollectiveSite] = []
    # program-order flag: all-gathers BEFORE the transpose all-to-all
    # are the splitter gossip, gathers after it are the horizontal
    # exchange — structural attribution, immune to the shape collision
    # where cap_hedge happens to equal p (tiny graphs)
    seen_a2a = False
    for es in iter_eqns(_unwrap_jaxpr(closed_jaxpr)):
        name = es.primitive
        if name not in COLLECTIVE_PRIMITIVES or not _uses_axis(
            es.eqn, axis_name
        ):
            continue
        name = _PSUM_ALIASES.get(name, name)
        aval = es.eqn.invars[0].aval
        nbytes = int(math.prod(aval.shape)) * aval.dtype.itemsize
        sites.append(_price_site(
            name, es.eqn, aval, nbytes, n=n, p=p,
            in_while=es.in_while, trips=es.trips,
            before_transpose=not seen_a2a,
        ))
        if name == "all_to_all":
            seen_a2a = True
    return sites


def _price_site(name, eqn, aval, nbytes, *, n, p, in_while, trips,
                before_transpose):
    """Phase + wire bytes for one collective eqn (see module docstring
    for the attribution rules)."""
    per_sweep = 0
    if name == "all_to_all":
        phase, per_run = "transpose", alltoall_wire_bytes(nbytes, p)
    elif name == "all_gather":
        # splitter gossip feeds the transpose, so it is the (only)
        # gather before the all-to-all; the post-transpose gathers are
        # the horizontal exchange
        phase = "splitter" if before_transpose else "hedge"
        per_run = allgather_wire_bytes(nbytes, p)
    elif name == "ppermute":
        perm = eqn.params.get("perm", ())
        cross = sum(1 for s, d in perm if s != d)
        phase, per_run = "hedge", ppermute_wire_bytes(nbytes, cross)
    elif name in _REDUCE_PRIMS:
        vol = allreduce_wire_bytes(nbytes, p)
        # BFS level syncs are pmax (seeding fixed, frontier per-sweep
        # inside the while loop); an n-vector *psum* outside the loop is
        # the per-vertex credit reduction and belongs to "reduce" —
        # size alone cannot separate the two once attribution is on
        if math.prod(aval.shape) >= n and (in_while or name != "psum"):
            phase = "bfs"
            if in_while:
                per_run, per_sweep = 0, vol
            else:
                per_run = vol
        else:
            phase, per_run = "reduce", vol
    else:  # pragma: no cover - gated by COLLECTIVE_PRIMITIVES
        raise ValueError(name)
    return CollectiveSite(
        kind=name, phase=phase, shape=tuple(aval.shape),
        dtype=str(aval.dtype), bytes_fixed=int(per_run) * trips,
        bytes_per_sweep=int(per_sweep) * trips, trips=trips,
    )


def measured_phase_bytes(
    sites: list[CollectiveSite], *, sweeps: int
) -> dict[str, int]:
    """Fold an op inventory into per-phase totals, resolving the BFS
    while loop's dynamic trip count with the run's ``sweeps``."""
    out = {ph: 0 for ph in WIRE_PHASES}
    for s in sites:
        out[s.phase] += s.bytes_fixed + s.bytes_per_sweep * int(sweeps)
    return out


def hlo_collective_counts(lowered_text: str) -> dict[str, int]:
    """Occurrences of each StableHLO collective op in a lowered module —
    the text-level cross-check that the jaxpr inventory saw everything
    XLA will be handed."""
    ops = {"all_gather": "stablehlo.all_gather",
           "all_to_all": "stablehlo.all_to_all",
           "ppermute": "stablehlo.collective_permute",
           "all_reduce": "stablehlo.all_reduce"}
    return {k: lowered_text.count(f'"{v}"(') for k, v in ops.items()}


def verify_against_hlo(sites: list[CollectiveSite], lowered_text: str) -> None:
    """Assert the jaxpr op inventory matches the lowered StableHLO text
    op-for-op (loop bodies appear once in both views)."""
    want = hlo_collective_counts(lowered_text)
    got = {"all_gather": 0, "all_to_all": 0, "ppermute": 0, "all_reduce": 0}
    for s in sites:
        got[s.kind if s.kind not in _REDUCE_PRIMS else "all_reduce"] += 1
    if got != want:
        raise AssertionError(
            f"collective inventory mismatch: jaxpr walk found {got}, "
            f"lowered HLO contains {want}"
        )


# ------------------------------------------------ end-to-end reports


def measure_tc_comm(
    n: int,
    m2: int,
    p: int,
    *,
    mesh=None,
    mode: str = "allgather",
    hedge_chunk: int | None = None,
    frontier_dtype: str = "int32",
    slack: float = 4.0,
    d_pad: int = 256,
    hplan=None,
    axis_name: str = "p",
    check_hlo: bool = True,
    per_vertex: bool = False,
) -> list[CollectiveSite]:
    """Lower the Algorithm 2 shard program for a (n, 2m)-sized graph on
    ``p`` devices and inventory its collectives (no graph data needed —
    the program is lowered from ShapeDtypeStructs, exactly like the
    dry-run path).  ``mesh`` defaults to the first ``p`` local devices.
    """
    from jax.sharding import Mesh, PartitionSpec as P

    from repro.core.parallel_tc import build_tc_shard_fn, result_out_specs

    if mesh is None:
        devs = jax.devices()
        if len(devs) < p:
            raise ValueError(
                f"need {p} devices to lower the p={p} program; found "
                f"{len(devs)} (force --xla_force_host_platform_device_count)"
            )
        mesh = Mesh(np.array(devs[:p]).reshape(p), (axis_name,))
    fn, cap_edges = build_tc_shard_fn(
        n=n, m2=m2, p=p, axis_name=axis_name, slack=slack, d_pad=d_pad,
        mode=mode, hedge_chunk=hedge_chunk, frontier_dtype=frontier_dtype,
        hplan=hplan, per_vertex=per_vertex,
    )
    shard = jax.shard_map(
        fn, mesh=mesh, in_specs=(P(axis_name), P(axis_name)),
        out_specs=result_out_specs(axis_name, per_vertex=per_vertex),
    )
    spec = jax.ShapeDtypeStruct((p * cap_edges,), jnp.int32)
    sites = collect_collective_sites(
        jax.make_jaxpr(shard)(spec, spec), n=n, p=p, axis_name=axis_name
    )
    # p == 1: lowering canonicalizes trivial collectives away (their wire
    # volume is 0 either way), so the op-for-op cross-check only holds
    # for real multi-device programs
    if check_hlo and p > 1:
        verify_against_hlo(
            sites, jax.jit(shard).lower(spec, spec).as_text()
        )
    return sites


def comm_report(
    n: int,
    m2: int,
    p: int,
    *,
    sweeps: int,
    mode: str = "allgather",
    hedge_chunk: int | None = None,
    frontier_dtype: str = "int32",
    slack: float = 4.0,
    n_levels_model: int | None = None,
    mesh=None,
    check_hlo: bool = True,
    per_vertex: bool = False,
) -> dict:
    """Per-phase ``{measured, tally, modeled}`` wire bytes for one
    Algorithm 2 configuration — the modeled-vs-measured closing of the
    loop.  ``sweeps`` is the run's BFS sweep count (``CommTally
    .bfs_sweeps``, or max level + 1 from any BFS of the graph — levels
    are a graph property, not a partition property).  ``n_levels_model``
    feeds the closed-form model; ``None`` uses ``sweeps`` so modeled ==
    measured exactly."""
    from repro.core.comm_model import wire_bytes_report
    from repro.core.parallel_tc import _capacities

    _, cap_chunk, cap_hedge = _capacities(m2, p, slack)
    sites = measure_tc_comm(
        n, m2, p, mesh=mesh, mode=mode, hedge_chunk=hedge_chunk,
        frontier_dtype=frontier_dtype, slack=slack, check_hlo=check_hlo,
        per_vertex=per_vertex,
    )
    measured = measured_phase_bytes(sites, sweeps=sweeps)
    tally = tally_comm(
        n=n, p=p, cap_chunk=cap_chunk, cap_hedge=cap_hedge, mode=mode,
        frontier_dtype=frontier_dtype, sweeps=int(sweeps),
        per_vertex=per_vertex,
    ).phase_bytes()
    modeled = wire_bytes_report(
        n, p, cap_chunk=cap_chunk, cap_hedge=cap_hedge,
        n_levels=int(n_levels_model if n_levels_model is not None
                     else sweeps),
        mode=mode, frontier_dtype=frontier_dtype, per_vertex=per_vertex,
    )
    return {
        "n": n, "m2": m2, "p": p, "mode": mode, "sweeps": int(sweeps),
        "phases": {
            ph: {"measured": measured[ph], "tally": tally[ph],
                 "modeled": modeled[ph]}
            for ph in WIRE_PHASES
        },
        "measured_total": sum(measured.values()),
        "tally_total": sum(tally.values()),
        "modeled_total": sum(modeled.values()),
        # per-device peak buffer of the horizontal exchange — the router
        # signal: the gathered block is p x the per-round ring buffer
        "hedge_round_buffer_bytes": hedge_round_buffer_bytes(m2, p, mode,
                                                             slack=slack),
    }


def hedge_round_buffer_bytes(
    m2: int, p: int, mode: str, *, slack: float = 4.0
) -> int:
    """Per-device bytes the horizontal exchange materializes at once:
    allgather holds the full gathered (hv, hw) block, ring only one
    device's shard — same total wire volume, p x smaller live buffer."""
    from repro.core.parallel_tc import _capacities

    cap_hedge = _capacities(m2, p, slack)[2]
    rows = p * cap_hedge if mode == "allgather" else cap_hedge
    return 2 * rows * 4


def choose_hedge_mode(
    m2: int,
    p: int,
    *,
    gather_buffer_limit_bytes: int = 64 << 20,
    slack: float = 4.0,
) -> str:
    """Router policy for the serving layer's distributed route: both
    exchange modes move the same measured hedge volume (the paper's
    equivalence), so pick by the live buffer — ``allgather`` (one
    collective, fewer dispatches) until its gathered block exceeds
    ``gather_buffer_limit_bytes`` per device, ``ring`` (p x smaller
    per-round buffer, p-1 overlapped rounds) beyond."""
    gathered = hedge_round_buffer_bytes(m2, p, "allgather", slack=slack)
    return "allgather" if gathered <= gather_buffer_limit_bytes else "ring"
