"""Pass 2 — int32 index-overflow audit by interval propagation.

The paper's headline scales (Graph500 36–42) put 2³¹⁺ directed edge
slots on a host long before anything OOMs, and JAX's x32 default makes
every index computation wrap silently at 2³¹−1.  This pass propagates
*value bounds* — not data — through the lowered route jaxprs: program
inputs get their TRUE ranges from the budget/meta ceilings (a CSR
offset is bounded by the slot count no matter what dtype the array
claims), every equation's output bound is computed by a per-primitive
interval rule, and any site whose bound exceeds its integer dtype's
capacity is reported.  ``jax.make_jaxpr``/``jax.eval_shape`` on
synthetic scale-20/26/36 shapes means no element is ever materialized:
auditing a 2⁴¹-slot graph costs the same as a 2⁸-slot one.

Interval rules are deliberately *partial*: an unsupported primitive
yields an unknown bound (⊤), which can never flag — so every finding
is backed by an actual arithmetic chain from a ceiling, no
false positives from conservatism.  Sites aggregate by
``(program, primitive)``, not equation index, so unrelated code motion
does not churn the baseline.

Synthetic scales use the Graph500 convention: scale ``s`` is ``n = 2^s``
vertices at edgefactor 16, i.e. ``2m = 32·n = 2^(s+5)`` directed slots
— scale 26 is the first where the slot count (2³¹) no longer fits an
int32 index, scale 36 the first where the vertex ids themselves don't.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import numpy as np

from repro.analysis.dtypes import index_dtype
from repro.analysis.findings import Finding, finding_data
from repro.analysis.routes import abstract_lane_view, bounded_plan, synthetic_meta

#: Graph500 edgefactor: m = 16·n undirected edges, 2m directed slots.
EDGEFACTOR = 16

#: Default synthetic scales: last-clean / first-slot-overflow /
#: first-vertex-id-overflow.
DEFAULT_SCALES = (20, 26, 36)

Bound = Optional[tuple[int, int]]  # (lo, hi) in exact host ints, or ⊤


def scale_shape(scale: int) -> tuple[int, int]:
    """``(n_vertices, directed_slots)`` of a Graph500-scale graph."""
    n = 1 << int(scale)
    return n, 2 * EDGEFACTOR * n


# ------------------------------------------------- interval arithmetic

def _add(a: Bound, b: Bound) -> Bound:
    if a is None or b is None:
        return None
    return (a[0] + b[0], a[1] + b[1])


def _sub(a: Bound, b: Bound) -> Bound:
    if a is None or b is None:
        return None
    return (a[0] - b[1], a[1] - b[0])


def _mul(a: Bound, b: Bound) -> Bound:
    if a is None or b is None:
        return None
    prods = [a[0] * b[0], a[0] * b[1], a[1] * b[0], a[1] * b[1]]
    return (min(prods), max(prods))


def _union(*bs: Bound) -> Bound:
    if any(b is None for b in bs) or not bs:
        return None
    return (min(b[0] for b in bs), max(b[1] for b in bs))


def _scaled_sum(a: Bound, count: int) -> Bound:
    """Bound of a sum/cumsum of ``count`` elements each in ``a``."""
    if a is None:
        return None
    lo, hi = a
    return (min(lo * count, lo, 0), max(hi * count, hi, 0))


def _bool() -> Bound:
    return (0, 1)


def _dim(eqn, key: str, default: int = 0) -> int:
    v = eqn.params.get(key, default)
    return int(v)


def _axis_len(aval, axis: int) -> int:
    shape = tuple(aval.shape)
    return int(shape[axis]) if shape else 1


# Primitives whose output VALUES are a subset/permutation of an input's
# values — bounds pass straight through (first operand's bound).
_PASSTHROUGH = frozenset({
    "broadcast_in_dim", "reshape", "squeeze", "expand_dims", "transpose",
    "rev", "copy", "stop_gradient", "slice", "dynamic_slice", "sort",
    "reduce_max", "reduce_min", "cummax", "cummin", "real", "abs_pass",
})

_CMP = frozenset({"eq", "ne", "lt", "le", "gt", "ge", "is_finite"})

_SUBCALL = frozenset({
    "pjit", "closed_call", "core_call", "custom_jvp_call",
    "custom_vjp_call", "custom_vjp_call_jaxpr", "remat", "checkpoint",
})


# newer JAX (0.9 among them) names the call primitive ``jit``, older
# JAX ``pjit``; sites keep ``pjit`` so baselines stay keyed alike
_PRIM_ALIASES = {"jit": "pjit"}


def _prim(eqn) -> str:
    name = eqn.primitive.name
    return _PRIM_ALIASES.get(name, name)


class _Propagator:
    """One program's interval walk.  Collects overflow sites keyed by
    ``(program_label, kind, primitive)``."""

    def __init__(self, label: str):
        self.label = label
        # site -> {"count": int, "worst": int, "example": str}
        self.sites: dict[tuple[str, str], dict] = {}

    # -- flagging ------------------------------------------------------
    def _check(self, var, bound: Bound, kind: str, prim: str) -> None:
        if bound is None:
            return
        dtype = getattr(var.aval, "dtype", None)
        if dtype is None or not np.issubdtype(dtype, np.integer):
            return
        info = np.iinfo(dtype)
        lo, hi = bound
        if hi <= info.max and lo >= info.min:
            return
        key = (kind, prim)
        rec = self.sites.setdefault(
            key, {"count": 0, "worst": 0, "example": ""})
        rec["count"] += 1
        if abs(hi) > abs(rec["worst"]):
            rec["worst"] = hi
            rec["example"] = (
                f"{prim} -> {dtype}{tuple(var.aval.shape)} "
                f"bound [{lo}, {hi}] exceeds {dtype} "
                f"[{info.min}, {info.max}]"
            )

    # -- evaluation ----------------------------------------------------
    def run(self, closed_jaxpr, in_bounds: list[Bound],
            *, _top: bool = True) -> list[Bound]:
        jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
        consts = getattr(closed_jaxpr, "consts", [])
        env: dict = {}

        def read(v) -> Bound:
            if hasattr(v, "val"):  # Literal
                x = np.asarray(v.val)
                if np.issubdtype(x.dtype, np.integer) or x.dtype == bool:
                    return (int(x.min()), int(x.max())) if x.size else (0, 0)
                return None
            return env.get(v)

        def write(v, b: Bound) -> None:
            env[v] = b

        if len(in_bounds) != len(jaxpr.invars):
            raise ValueError(
                f"{self.label}: {len(in_bounds)} input bounds for "
                f"{len(jaxpr.invars)} invars"
            )
        for v, b in zip(jaxpr.invars, in_bounds):
            write(v, b)
            # only the program's DECLARED inputs get the input check —
            # sub-jaxpr invars carry propagated bounds whose producing
            # op already flagged
            if _top:
                self._check(v, b, "input", "invar")
        for v, c in zip(jaxpr.constvars, consts):
            x = np.asarray(c)
            if x.size and (np.issubdtype(x.dtype, np.integer)
                           or x.dtype == bool):
                write(v, (int(x.min()), int(x.max())))
            else:
                write(v, None)

        for eqn in jaxpr.eqns:
            ins = [read(v) for v in eqn.invars]
            outs = self._eval(eqn, ins)
            for v, b in zip(eqn.outvars, outs):
                write(v, b)
                self._check(v, b, "op", _prim(eqn))
        return [read(v) for v in jaxpr.outvars]

    def _eval(self, eqn, ins: list[Bound]) -> list[Bound]:
        name = _prim(eqn)
        n_out = len(eqn.outvars)
        top = [None] * n_out

        if name in _SUBCALL:
            for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
                sub = eqn.params.get(key)
                if sub is not None:
                    try:
                        outs = self.run(sub, list(ins)[:len(
                            getattr(sub, "jaxpr", sub).invars)],
                            _top=False)
                    except ValueError:
                        return top
                    return (outs + top)[:n_out]
            return top
        if name in ("while", "scan", "cond"):
            # dynamic/branching control flow: outputs unknown (sound);
            # nested overflow still flags via the recursive audit of
            # each route's full program from its own ceilings
            return top
        if name in _CMP or name in ("and", "or", "not", "xor",
                                    "reduce_and", "reduce_or"):
            return [_bool()] * n_out
        if name in _PASSTHROUGH:
            return [ins[0] if ins else None] * n_out
        if name == "convert_element_type":
            return [ins[0]] * n_out
        if name == "add":
            return [_add(ins[0], ins[1])]
        if name == "sub":
            return [_sub(ins[0], ins[1])]
        if name == "mul":
            return [_mul(ins[0], ins[1])]
        if name == "neg":
            b = ins[0]
            return [None if b is None else (-b[1], -b[0])]
        if name == "max":
            if ins[0] is None or ins[1] is None:
                return top
            return [(max(ins[0][0], ins[1][0]),
                     max(ins[0][1], ins[1][1]))]
        if name == "min":
            if ins[0] is None or ins[1] is None:
                return top
            return [(min(ins[0][0], ins[1][0]),
                     min(ins[0][1], ins[1][1]))]
        if name == "clamp":
            a, x, b = ins
            if a is None or x is None or b is None:
                return top
            return [(min(max(x[0], a[0]), b[0]),
                     min(max(x[1], a[1]), b[1]))]
        if name == "select_n":
            return [_union(*ins[1:])] * n_out
        if name == "iota":
            dim = _dim(eqn, "dimension")
            aval = eqn.outvars[0].aval
            return [(0, max(0, _axis_len(aval, dim) - 1))]
        if name == "cumsum":
            axis = _dim(eqn, "axis")
            return [_scaled_sum(ins[0], _axis_len(eqn.invars[0].aval,
                                                  axis))]
        if name == "reduce_sum":
            axes = eqn.params.get("axes", ())
            count = 1
            for ax in axes:
                count *= _axis_len(eqn.invars[0].aval, int(ax))
            return [_scaled_sum(ins[0], count)]
        if name in ("argmax", "argmin"):
            axes = eqn.params.get("axes", (0,))
            size = _axis_len(eqn.invars[0].aval, int(tuple(axes)[0]))
            return [(0, max(0, size - 1))]
        if name == "gather":
            return [ins[0]] * n_out
        if name == "concatenate":
            return [_union(*ins)]
        if name == "pad":
            return [_union(ins[0], ins[1])]
        if name == "rem":
            d = ins[1]
            if d is None:
                return top
            mag = max(abs(d[0]), abs(d[1]))
            return [(-(mag - 1), mag - 1) if mag > 0 else (0, 0)]
        if name == "div":
            a, b = ins[0], ins[1]
            if a is None or b is None or b[0] <= 0:
                return top
            quots = [a[0] // b[0], a[0] // b[1], a[1] // b[0],
                     a[1] // b[1]]
            return [(min(quots), max(quots))]
        if name == "shift_left":
            a, s = ins
            if a is None or s is None or a[0] < 0 or s[0] < 0:
                return top
            return [(a[0] << s[0], a[1] << s[1])]
        if name in ("shift_right_logical", "shift_right_arithmetic"):
            a, s = ins
            if a is None or s is None or a[0] < 0 or s[0] < 0:
                return top
            return [(a[0] >> s[1], a[1] >> s[0])]
        return top


def lane_view_bounds(n_budget: int, slot_budget: int) -> list[Bound]:
    """TRUE value ranges of ``GraphBatch.lane_view()``'s arrays in
    ``Graph`` flatten order (src, dst, row_offsets, deg, n_edges_dir):
    ids are bounded by the sentinel, offsets/edge counts by the slot
    budget — regardless of what dtype the arrays claim."""
    return [
        (0, n_budget),            # src (sentinel-padded)
        (0, n_budget),            # dst
        (0, slot_budget),         # row_offsets
        (0, max(0, n_budget - 1)),  # deg
        (0, slot_budget),         # n_edges_dir
    ]


def audit_program_bounds(label: str, closed_jaxpr,
                         in_bounds: list[Bound]) -> list[Finding]:
    """Run the interval walk over one lowered program and fold the
    overflow sites into findings."""
    prop = _Propagator(label)
    prop.run(closed_jaxpr, in_bounds)
    out = []
    for (kind, prim), rec in sorted(prop.sites.items()):
        out.append(Finding(
            pass_name="bounds",
            site=f"{label}:{kind}:{prim}",
            severity="error" if kind == "input" else "warning",
            detail=(
                f"{rec['count']} {kind} site(s) of `{prim}` in {label} "
                f"exceed the integer dtype's capacity — worst "
                f"{rec['example']}"
            ),
            data=finding_data(count=rec["count"], worst=rec["worst"],
                              example=rec["example"]),
        ))
    return out


def audit_fused_bounds(scale: int, *, batch: int = 2) -> list[Finding]:
    """Interval-audit the serving hot path (``_tc_batch_fused``) at a
    synthetic Graph500 scale — lowered abstractly, never executed.

    At scale ≥ 26 the slot axis itself (2³¹) no longer fits an int32
    and JAX *refuses to trace* under x32 — tracing machinery constants
    (axis-size normalizers) overflow before any interval rule runs.
    That refusal is the strongest possible overflow evidence, so it is
    converted into an error finding rather than propagated as a crash.
    """
    from repro.core import sequential as seq

    n, slots = scale_shape(scale)
    gview = abstract_lane_view(n, slots, batch)
    plan = bounded_plan(synthetic_meta(n, slots, d_pad=1024))
    fn = functools.partial(seq._tc_batch_fused, plan=plan, root=0,
                           per_vertex=False)
    label = f"fused@scale{scale}"
    try:
        jaxpr = jax.make_jaxpr(fn)(gview)
    except OverflowError as e:
        return [Finding(
            pass_name="bounds",
            site=f"{label}:trace:x32-refused",
            severity="error",
            detail=(
                f"the fused serving program cannot even be LOWERED at "
                f"Graph500 scale {scale} under x32 — {slots} directed "
                f"slots exceed int32 axis indexing ({e}); serving this "
                f"scale requires the int64 index policy end to end"
            ),
            data=finding_data(scale=scale, n=n, slots=slots,
                              error=str(e)),
        )]
    return audit_program_bounds(
        label, jaxpr, lane_view_bounds(n, slots)
    )


def audit_host_sites(scale: int) -> list[Finding]:
    """The host-side construction sites (``csr.from_edges`` /
    ``from_edges_batch``), audited against the index-dtype policy: a
    scale whose bounds demand int64 yields a warning finding — the
    pinned ROADMAP-item-5 worklist — and the policy guarantees the
    build fails loudly (``IndexWidthError``) instead of wrapping."""
    from repro.graph.csr import abstract_graph

    n, slots = scale_shape(scale)
    # the policy constructor itself picks the dtypes — audit what the
    # build would actually do, not a re-derivation of it
    g = abstract_graph(n, slots)
    out = []
    for site, bound, dt in (
        ("vertex-ids", n, np.dtype(g.src.dtype)),
        ("row_offsets", slots, np.dtype(g.row_offsets.dtype)),
    ):
        assert dt == index_dtype(bound), (site, dt)
        if dt != np.dtype(np.int32):
            out.append(Finding(
                pass_name="bounds",
                site=f"host:from_edges:{site}@scale{scale}",
                severity="warning",
                detail=(
                    f"csr.from_edges {site} bound {bound} needs "
                    f"{dt} at Graph500 scale {scale}; x32 serving "
                    f"programs cannot index this graph "
                    f"(IndexWidthError at build, per policy)"
                ),
                data=finding_data(bound=bound, dtype=str(dt),
                                  scale=scale),
            ))
    return out


def audit_bounds(scales: tuple[int, ...] = DEFAULT_SCALES,
                 *, jaxpr_scales: Optional[tuple[int, ...]] = None
                 ) -> list[Finding]:
    """The full pass: host policy sites at every scale, interval walks
    over the fused program at the scales worth tracing (id-overflow
    scales ≥ 36 are already fully told by the host policy; the walk
    adds nothing but trace time there)."""
    if jaxpr_scales is None:
        # trace every requested slot-representable-or-first-refused
        # scale, plus scale 25 (the LAST scale whose slot axis fits
        # int32) so the walk certifies the largest clean shape too;
        # id-overflow scales ≥ 36 are fully told by the host policy
        jaxpr_scales = tuple(sorted(
            {s for s in scales if s < 36} | {25}
        ))
    findings: list[Finding] = []
    seen = set()
    for s in scales:
        for f in audit_host_sites(s):
            if f.key not in seen:
                seen.add(f.key)
                findings.append(f)
    for s in jaxpr_scales:
        for f in audit_fused_bounds(s):
            if f.key not in seen:
                seen.add(f.key)
                findings.append(f)
    return findings
