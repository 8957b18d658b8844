"""Pass 4 — collective completeness over every distributed route.

PR 4 proved, for the configurations its tests exercise, that the
shard program's collectives are exactly the ones the wire model prices.
This pass makes that claim total and static: for EVERY distributed
route the engine can run (backend × per-vertex × hedge mode × device
count), walk the lowered shard_map jaxpr and

* **census** — inventory every collective (kind, phase, while-loop
  membership, static trips) and bind the inventory into the finding's
  *site key* (a content digest): adding, removing, or re-phasing a
  single collective anywhere in the program changes the key, which the
  baseline diff turns into a CI failure.  This is how "a synthetic
  unpriced collective fails the build" works without hand-maintaining
  op counts in two places;
* **unpriced detection** — any equation over the mesh axis whose
  primitive is NOT in the priced set (``COLLECTIVE_PRIMITIVES``) is an
  error outright: the wire model has no formula for it, so the PR 4
  modeled-vs-measured contract is silently broken.  Axis casts
  (``AXIS_CASTS``: ``pvary`` lowers to the identity) move nothing and
  are not collectives; one finding per distinct primitive and path;
* **tally cross-check** — the per-phase byte totals folded from the
  inventory must equal the in-trace analytic ``CommTally`` formulas
  for the same capacities (exact, per phase).  At ``p == 1`` both
  sides are zero (the check is vacuous but cheap); at ``p > 1`` it is
  the bit-for-bit PR 4 contract, asserted statically;
* **HLO cross-check** (``p > 1`` only) — the jaxpr inventory must
  match the StableHLO text op-for-op; at ``p == 1`` XLA canonicalizes
  trivial collectives away, so jaxpr-level is the only total view.

Nothing executes: programs are lowered from ShapeDtypeStructs.
"""
from __future__ import annotations

import collections
import hashlib
from typing import Iterable, Optional

import jax

from repro.analysis.findings import Finding, finding_data
from repro.analysis.routes import RouteSpec
from repro.analysis.walker import (
    AXIS_CASTS,
    COLLECTIVE_PRIMITIVES,
    collective_eqns,
    iter_eqns,
    unwrap,
    uses_axis,
)
from repro.core.comm_instrument import (
    collect_collective_sites,
    measured_phase_bytes,
    tally_comm,
    verify_against_hlo,
)

#: BFS sweep count the static byte cross-check is resolved at — any
#: positive value works (both sides scale the per-sweep term by it).
CHECK_SWEEPS = 4


def census_digest(sites) -> str:
    """Stable 10-hex digest of a collective inventory: kind, phase,
    shape, dtype, trips and loop membership of every site, order
    preserved (program order is part of the contract — splitter/hedge
    attribution depends on it)."""
    text = ";".join(
        f"{s.kind}|{s.phase}|{s.shape}|{s.dtype}|{s.trips}|"
        f"{s.bytes_fixed}|{s.bytes_per_sweep}"
        for s in sites
    )
    return hashlib.sha1(text.encode()).hexdigest()[:10]


def unpriced_collectives(closed_jaxpr, *, axis_name: str = "p"
                         ) -> list[str]:
    """Primitives communicating over the mesh axis that the wire model
    has no price for — each is ``"primitive@path"``, once per
    occurrence."""
    out = []
    for es in iter_eqns(unwrap(closed_jaxpr)):
        if es.primitive in COLLECTIVE_PRIMITIVES + AXIS_CASTS:
            continue
        if uses_axis(es.eqn, axis_name):
            out.append(f"{es.primitive}@{'/'.join(es.path) or '<top>'}")
    return out


def audit_program_collectives(
    label: str,
    closed_jaxpr,
    *,
    n: int,
    p: int,
    mode: str,
    cap_chunk: int,
    cap_hedge: int,
    per_vertex: bool,
    frontier_dtype: str = "int32",
    axis_name: str = "p",
    lowered_text: Optional[str] = None,
) -> list[Finding]:
    """All collective findings for one lowered shard program."""
    findings: list[Finding] = []

    unpriced = collections.Counter(
        unpriced_collectives(closed_jaxpr, axis_name=axis_name))
    for site, times in unpriced.items():
        findings.append(Finding(
            pass_name="collectives",
            site=f"unpriced:{label}:{site}",
            severity="error",
            detail=(
                f"collective `{site}` ({times}x) in {label} communicates "
                f"over the mesh axis but is not in the priced set "
                f"{COLLECTIVE_PRIMITIVES} — the wire model cannot "
                f"account for it"
            ),
            data=finding_data(label=label, site=site, times=times),
        ))

    sites = collect_collective_sites(
        closed_jaxpr, n=n, p=p, axis_name=axis_name
    )
    # the raw walker view and the pricing instrument must see the same
    # ops — a divergence means one of them grew a filter the other lacks
    raw = collective_eqns(closed_jaxpr, axis_name=axis_name)
    if len(raw) != len(sites):
        findings.append(Finding(
            pass_name="collectives",
            site=f"walker-divergence:{label}",
            severity="error",
            detail=(
                f"{label}: walker sees {len(raw)} collectives but the "
                f"pricing pass produced {len(sites)} sites — traversal "
                f"or filtering drift between analysis.walker and "
                f"core.comm_instrument"
            ),
            data=finding_data(walker=len(raw), priced=len(sites)),
        ))
    by_phase: dict[str, int] = {}
    for s in sites:
        by_phase[s.phase] = by_phase.get(s.phase, 0) + 1
    findings.append(Finding(
        pass_name="collectives",
        site=f"census:{label}:{len(sites)}c:{census_digest(sites)}",
        severity="info",
        detail=(
            f"{label}: {len(sites)} priced collectives "
            f"({', '.join(f'{k}={v}' for k, v in sorted(by_phase.items()))})"
            f" — any inventory change re-keys this finding and gates CI"
        ),
        data=finding_data(
            count=len(sites), by_phase=by_phase,
            inventory=[
                {"kind": s.kind, "phase": s.phase, "shape": list(s.shape),
                 "dtype": s.dtype, "trips": s.trips,
                 "bytes_fixed": s.bytes_fixed,
                 "bytes_per_sweep": s.bytes_per_sweep}
                for s in sites
            ],
        ),
    ))

    measured = measured_phase_bytes(sites, sweeps=CHECK_SWEEPS)
    tally = tally_comm(
        n=n, p=p, cap_chunk=cap_chunk, cap_hedge=cap_hedge, mode=mode,
        frontier_dtype=frontier_dtype, sweeps=CHECK_SWEEPS,
        per_vertex=per_vertex,
    ).phase_bytes()
    if measured != tally:
        findings.append(Finding(
            pass_name="collectives",
            site=f"tally-mismatch:{label}",
            severity="error",
            detail=(
                f"{label}: program inventory bytes != analytic tally at "
                f"sweeps={CHECK_SWEEPS} — measured {measured}, "
                f"tally {tally}"
            ),
            data=finding_data(measured=measured, tally=tally),
        ))

    if lowered_text is not None:
        try:
            verify_against_hlo(sites, lowered_text)
        except AssertionError as e:
            findings.append(Finding(
                pass_name="collectives",
                site=f"hlo-mismatch:{label}",
                severity="error",
                detail=f"{label}: {e}",
                data=finding_data(error=str(e)),
            ))
    return findings


def audit_collectives(specs: Iterable[RouteSpec]) -> list[Finding]:
    """The full pass over every distributed route spec.  Lowers each
    shard program once; adds the StableHLO cross-check where ``p > 1``
    (below that XLA canonicalizes trivial collectives away and the
    text check is meaningless)."""
    from repro.core.parallel_tc import _capacities

    findings: list[Finding] = []
    for spec in specs:
        if spec.route != "distributed":
            continue
        fn, args = spec.shard_program()
        jaxpr = jax.make_jaxpr(fn)(*args)
        lowered = (jax.jit(fn).lower(*args).as_text()
                   if spec.p > 1 else None)
        _, cap_chunk, cap_hedge = _capacities(spec.slot_budget, spec.p,
                                              4.0)
        findings.extend(audit_program_collectives(
            f"{spec.name}/shard", jaxpr,
            n=spec.n_budget, p=spec.p, mode=spec.mode or "allgather",
            cap_chunk=cap_chunk, cap_hedge=cap_hedge,
            per_vertex=spec.per_vertex, lowered_text=lowered,
        ))
    return findings
