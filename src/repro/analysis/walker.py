"""Shared jaxpr-walker core of the static auditor.

PR 4 proved the repo can audit its own lowered programs
(``core.comm_instrument`` walks the shard_map jaxpr and inventories
every collective); this module generalizes that traversal so every
analysis pass — collective pricing, value-bound propagation, callback
detection, compile-set enumeration — shares ONE definition of "walk a
program", instead of each pass re-deriving how sub-jaxprs nest.

The traversal contract (inherited verbatim from PR 4's walker, which
``core.comm_instrument`` now delegates to):

  * depth-first, program order: an equation is yielded BEFORE its
    sub-jaxprs are descended into;
  * ``in_while`` marks equations inside a ``while`` *body* (the only
    dynamically trip-counted loop in the repo's programs — the BFS
    frontier exchange); cond jaxprs do not set it;
  * ``trips`` multiplies through enclosing ``scan`` bodies with static
    ``length`` — an equation inside nested scans of lengths 3 and 4
    carries ``trips == 12``.

Nothing in this module imports the rest of ``repro`` — the walker is a
leaf dependency every pass (and ``core.comm_instrument``) can build on
without import cycles.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Iterator

#: jaxpr primitive names that move data across a device axis.
#: ``psum_invariant`` is the all-reduce JAX binds for ``psum`` of a
#: device-varying value under ``shard_map``'s varying-axes checks.
COLLECTIVE_PRIMITIVES = ("all_gather", "all_to_all", "ppermute",
                         "psum", "psum_invariant", "pmax", "pmin")

#: primitives that name a mesh axis but move no data: ``pvary`` marks a
#: value as device-varying and lowers to the identity.
AXIS_CASTS = ("pvary",)

#: jaxpr primitive names that re-enter Python from inside a trace —
#: each is a host round-trip (and a serialization barrier) if it ever
#: appears on a serving hot path.
CALLBACK_PRIMITIVES = ("pure_callback", "io_callback", "debug_callback",
                       "callback")


@dataclasses.dataclass(frozen=True)
class EqnSite:
    """One equation, located: where the walker found it and under which
    static loop context.

    ``path`` is the chain of ``"primitive:param"`` frames entered to
    reach the equation (e.g. ``("pjit:jaxpr", "while:body_jaxpr")``) —
    a stable structural address that does not depend on equation
    indices, so findings keyed on it survive unrelated code motion.
    """

    eqn: Any
    path: tuple[str, ...]
    in_while: bool
    trips: int

    @property
    def primitive(self) -> str:
        return self.eqn.primitive.name


def subjaxprs(eqn) -> Iterator[tuple[str, Any]]:
    """``(param_name, jaxpr)`` for every sub-jaxpr of an eqn (while/scan
    bodies, pjit calls, custom-call branches, ...)."""
    for k, v in eqn.params.items():
        vals = v if isinstance(v, (list, tuple)) else [v]
        for x in vals:
            if hasattr(x, "eqns"):
                yield k, x
            elif hasattr(x, "jaxpr"):
                yield k, x.jaxpr


def uses_axis(eqn, axis_name: str) -> bool:
    """True iff the eqn names ``axis_name`` in its ``axes``/``axis_name``
    params — i.e. it is a collective over that mesh axis."""
    for key in ("axes", "axis_name"):
        ax = eqn.params.get(key)
        if ax is None:
            continue
        names = ax if isinstance(ax, (list, tuple)) else (ax,)
        if axis_name in names:
            return True
    return False


def unwrap(closed_jaxpr):
    """The raw jaxpr of a possibly-closed jaxpr."""
    return getattr(closed_jaxpr, "jaxpr", closed_jaxpr)


def iter_eqns(closed_jaxpr) -> Iterator[EqnSite]:
    """Every equation of the program, recursively, as :class:`EqnSite`.

    Yields the composite equation itself (``while``, ``scan``, ``pjit``,
    ...) before descending into its sub-jaxprs, so a pass that only
    cares about leaf primitives can simply ignore composite names, and
    a pass that prunes subtrees can filter on ``path``.
    """

    def visit(jx, path, in_while, trips):
        for eqn in jx.eqns:
            name = eqn.primitive.name
            yield EqnSite(eqn=eqn, path=path, in_while=in_while,
                          trips=trips)
            for key, sub in subjaxprs(eqn):
                w = in_while or (name == "while" and key == "body_jaxpr")
                t = trips
                if name == "scan":
                    t = trips * int(eqn.params.get("length", 1))
                yield from visit(sub, path + (f"{name}:{key}",), w, t)

    yield from visit(unwrap(closed_jaxpr), (), False, 1)


def collective_eqns(closed_jaxpr, *, axis_name: str = "p"
                    ) -> list[EqnSite]:
    """Program-order list of every collective equation over
    ``axis_name`` — the raw census the completeness pass compares
    against the priced inventory."""
    return [s for s in iter_eqns(closed_jaxpr)
            if s.primitive in COLLECTIVE_PRIMITIVES
            and uses_axis(s.eqn, axis_name)]


def callback_eqns(closed_jaxpr) -> list[EqnSite]:
    """Every Python-callback equation in the program — host round-trips
    the host-sync pass must prove absent from serving hot paths."""
    return [s for s in iter_eqns(closed_jaxpr)
            if s.primitive in CALLBACK_PRIMITIVES]


def weak_typed_invars(closed_jaxpr) -> list[str]:
    """Names the trace-level avals (program inputs and constants) that
    carry ``weak_type=True`` — Python-scalar leaks that fragment jit
    caches by splitting otherwise-identical signatures.

    Returns human-readable descriptions (aval position + dtype)."""
    jaxpr = unwrap(closed_jaxpr)
    leaks = []
    for kind, vs in (("invar", jaxpr.invars), ("constvar", jaxpr.constvars)):
        for i, v in enumerate(vs):
            aval = v.aval
            if getattr(aval, "weak_type", False):
                leaks.append(f"{kind}[{i}]: {aval.dtype} "
                             f"shape={tuple(aval.shape)}")
    return leaks
