"""Engine-route enumeration + abstract lowering for the auditor.

One definition of "every program the engine can run" that every pass
shares: local / batch / find / distributed, crossed with the jnp and
(interpreted) Pallas intersection backends, with per-vertex attribution
on and off, and — on the distributed route — both hedge exchange modes
and a device-count axis.  Each :class:`RouteSpec` lowers its jit
programs to closed jaxprs from ``ShapeDtypeStruct``s only: nothing in
this module executes device code, so the auditor can reason about
Graph500-scale shapes on a laptop.

The local route contributes TWO programs (its exact pipeline is a plan
jit plus a run jit separated by one host sync); the batch/serving route
is the fused single-jit hot path; find is the per-bucket probe block;
distributed is the full shard_map body, lowered exactly like PR 4's
dry-run path (``comm_instrument.measure_tc_comm``); stream is the
level-free exact-planned delta probe the streaming subsystem issues per
mutation batch (``repro.stream.delta.probe_sum`` — the one device
program of the stream route; its refresh reuses the local route's
programs verbatim).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.intersect import IntersectPlan, plan_buckets_bounded
from repro.graph.csr import (
    META_ROW_QUANT,
    META_WIDTHS,
    BatchDegreeMeta,
    Graph,
)

#: intersection backends every route is audited under.  Pallas runs in
#: interpret mode — the audit must work on CPU CI runners, and the
#: jaxpr-level structure is what the passes consume.
BACKENDS = (("jnp", True), ("pallas", True))

#: distributed hedge exchange modes.
HEDGE_MODES = ("allgather", "ring")


def _next_pow2(x: int) -> int:
    return 1 << max(0, (int(x) - 1).bit_length())


def _ceil_to(x: int, mult: int) -> int:
    return max(mult, -(-int(x) // mult) * mult)


def synthetic_meta(n_budget: int, slot_budget: int,
                   *, d_pad: Optional[int] = None) -> BatchDegreeMeta:
    """A valid ``BatchDegreeMeta`` for a worst-case batch at this budget
    — every bound at its ceiling, exceedance decaying across the width
    grid so bounded plans lay out realistic multi-bucket shapes.  This
    is what "audit a budget cell without a graph" means: the meta IS
    the cell's upper bound, no data required."""
    d = int(d_pad) if d_pad is not None else min(
        _next_pow2(max(2, n_budget // 8)), 1024
    )
    h_rows = _ceil_to(max(1, slot_budget // 2), META_ROW_QUANT)
    exceed = []
    for i, w in enumerate(META_WIDTHS):
        c = h_rows >> (i + 1) if w < d else 0
        exceed.append((w, _ceil_to(c, META_ROW_QUANT) if c else 0))
    return BatchDegreeMeta(d_pad=d, h_rows=h_rows, exceed=tuple(exceed))


def bounded_plan(meta: BatchDegreeMeta, *, backend: str = "jnp",
                 interpret: bool = True,
                 query_chunk: Optional[int] = None) -> IntersectPlan:
    """The serving-path bounded plan for a synthetic meta — host-only."""
    return plan_buckets_bounded(
        meta.h_rows, d_pad=meta.d_pad, exceed=meta.exceed,
        backend=backend, interpret=interpret, query_chunk=query_chunk,
        row_mult=META_ROW_QUANT, sort_queries=False,
    )


def abstract_lane_view(n_budget: int, slot_budget: int,
                       batch: int) -> Graph:
    """``GraphBatch.lane_view()`` as ShapeDtypeStructs — lane-axis
    int32 arrays at the budget, the exact avals every serving flush
    traces with (the device program is x32; the bounds pass supplies
    the TRUE value ranges separately)."""
    s = jax.ShapeDtypeStruct
    i32 = jnp.int32
    return Graph(
        src=s((batch, slot_budget), i32),
        dst=s((batch, slot_budget), i32),
        row_offsets=s((batch, n_budget + 2), i32),
        deg=s((batch, n_budget), i32),
        n_edges_dir=s((batch,), i32),
        n_nodes=int(n_budget),
    )


def abstract_single_graph(n_nodes: int, num_slots: int) -> Graph:
    """Single-graph avals at the current x32 device dtypes."""
    s = jax.ShapeDtypeStruct
    i32 = jnp.int32
    return Graph(
        src=s((num_slots,), i32),
        dst=s((num_slots,), i32),
        row_offsets=s((n_nodes + 2,), i32),
        deg=s((n_nodes,), i32),
        n_edges_dir=s((), i32),
        n_nodes=int(n_nodes),
    )


@dataclasses.dataclass(frozen=True)
class RouteSpec:
    """One audited engine configuration.

    ``name`` is the stable finding-site prefix; ``programs()`` lowers
    the configuration's jit program(s) to ``(label, closed_jaxpr)``
    pairs without executing anything."""

    name: str
    route: str                # local | batch | find | distributed | stream
    backend: str
    interpret: bool
    per_vertex: bool
    mode: Optional[str] = None     # distributed hedge mode
    p: int = 1                     # distributed device count
    n_budget: int = 64
    slot_budget: int = 256
    batch: int = 2

    def programs(self) -> list[tuple[str, object]]:
        if self.route == "distributed":
            fn, args = self.shard_program()
            return [(f"{self.name}/shard", jax.make_jaxpr(fn)(*args))]
        if self.route == "batch":
            return [(f"{self.name}/fused", self._fused_jaxpr())]
        if self.route == "local":
            return self._local_jaxprs()
        if self.route == "find":
            return [(f"{self.name}/find_block", self._find_jaxpr())]
        if self.route == "stream":
            return [(f"{self.name}/delta_probe", self._stream_jaxpr())]
        raise ValueError(f"unknown route {self.route!r}")

    # ---------------------------------------------------- batch route
    def _plan(self) -> IntersectPlan:
        meta = synthetic_meta(self.n_budget, self.slot_budget)
        return bounded_plan(meta, backend=self.backend,
                            interpret=self.interpret)

    def _fused_jaxpr(self):
        from repro.core import sequential as seq

        gview = abstract_lane_view(self.n_budget, self.slot_budget,
                                   self.batch)
        fn = functools.partial(
            seq._tc_batch_fused, plan=self._plan(), root=0,
            per_vertex=self.per_vertex,
        )
        return jax.make_jaxpr(fn)(gview)

    # ---------------------------------------------------- local route
    def _local_jaxprs(self):
        from repro.core import sequential as seq

        gview = abstract_lane_view(self.n_budget, self.slot_budget,
                                   self.batch)
        plan_fn = functools.partial(seq._plan_batch, root=0)
        plan_jaxpr = jax.make_jaxpr(plan_fn)(gview)
        # stage 2's query avals come from stage 1's output shapes —
        # eval_shape is the no-execution bridge across the host sync
        level, qu, qw, *_ = jax.eval_shape(plan_fn, gview)
        run_fn = functools.partial(
            seq._run_batch, plan=self._plan(), per_vertex=self.per_vertex
        )
        run_jaxpr = jax.make_jaxpr(run_fn)(gview, qu, qw, level)
        return [(f"{self.name}/plan", plan_jaxpr),
                (f"{self.name}/run", run_jaxpr)]

    # ----------------------------------------------------- find route
    def _find_jaxpr(self):
        from repro.core import sequential as seq

        g = abstract_single_graph(self.n_budget, self.slot_budget)
        plan = self._plan()
        b = plan.buckets[0]
        s = jax.ShapeDtypeStruct
        qrow = s((b.rows,), jnp.int32)
        level = s((self.n_budget,), jnp.int32)
        fn = functools.partial(
            seq._find_block, d_cand=b.d_cand, d_targ=b.d_targ,
            backend=self.backend, interpret=self.interpret,
            max_triangles=64,
        )
        return jax.make_jaxpr(fn)(g, qrow, qrow, level)

    # ---------------------------------------------------- stream route
    def _stream_jaxpr(self):
        from repro.core.intersect import (
            DEFAULT_BUCKET_WIDTHS,
            CsrAdjacency,
            plan_buckets,
            run_plan,
        )

        # a synthetic net-batch degree profile spanning the default
        # width grid — the exact host plan the session prices per batch
        # (stream.delta.probe_sum).  The device program is ONE
        # level-free run_plan over the delta query block; the pinned
        # profile keeps the lowered structure (and the baseline's site
        # keys) identical on any host.
        ds = np.array([1, 2, 2, 4, 4, 8, 8, 16], dtype=np.int64)
        plan = plan_buckets(
            ds, 2 * ds, bucket_widths=DEFAULT_BUCKET_WIDTHS,
            backend=self.backend, interpret=self.interpret,
        )
        g = abstract_single_graph(self.n_budget, self.slot_budget)
        q = jax.ShapeDtypeStruct((int(ds.size),), jnp.int32)

        def fn(flat, row_offsets, deg, qu, qw):
            adj = CsrAdjacency(flat=flat, row_offsets=row_offsets,
                               deg=deg, n_nodes=self.n_budget)
            return run_plan(adj, qu, qw, plan, level=None,
                            per_vertex=self.per_vertex)

        return jax.make_jaxpr(fn)(g.dst, g.row_offsets, g.deg, q, q)

    # ---------------------------------------------- distributed route
    def shard_program(self) -> tuple[Callable, tuple]:
        """The shard_map program + its ShapeDtypeStruct args — shared
        by the jaxpr passes (``make_jaxpr``) and the collective pass's
        StableHLO cross-check (``jax.jit(fn).lower(*args)``).  Needs
        ``p`` local devices (CI forces 8 host devices via XLA_FLAGS)."""
        from jax.sharding import Mesh, PartitionSpec as P

        from repro.core.parallel_tc import (
            build_tc_shard_fn,
            result_out_specs,
        )

        devs = jax.devices()
        if len(devs) < self.p:
            raise ValueError(
                f"route {self.name}: need {self.p} devices, found "
                f"{len(devs)} (set --xla_force_host_platform_device_count)"
            )
        mesh = Mesh(np.array(devs[: self.p]).reshape(self.p), ("p",))
        m2 = self.slot_budget
        fn, cap_edges = build_tc_shard_fn(
            n=self.n_budget, m2=m2, p=self.p, mode=self.mode or "allgather",
            intersect_backend=self.backend, interpret=self.interpret,
            per_vertex=self.per_vertex,
        )
        shard = jax.shard_map(
            fn, mesh=mesh, in_specs=(P("p"), P("p")),
            out_specs=result_out_specs("p", per_vertex=self.per_vertex),
        )
        spec = jax.ShapeDtypeStruct((self.p * cap_edges,), jnp.int32)
        return shard, (spec, spec)


def enumerate_route_specs(
    *,
    n_budget: int = 64,
    slot_budget: int = 256,
    batch: int = 2,
    p_values: tuple[int, ...] = (1,),
) -> list[RouteSpec]:
    """The full audited route space: local/batch/find/stream × backend
    × per_vertex, plus distributed × backend × per_vertex × mode × p.
    ``p_values`` beyond the local device count are skipped by callers
    that execute lowering (the CLI forces 8 host devices first).

    Backends are pinned (never ``"auto"``) so the enumeration — and
    therefore every baseline site key — is identical on any host."""
    shape = dict(n_budget=n_budget, slot_budget=slot_budget, batch=batch)
    specs: list[RouteSpec] = []
    for backend, interpret in BACKENDS:
        for pv in (False, True):
            tag = f"{backend}{'/pv' if pv else ''}"
            specs.append(RouteSpec(
                name=f"batch/{tag}", route="batch", backend=backend,
                interpret=interpret, per_vertex=pv, **shape,
            ))
            specs.append(RouteSpec(
                name=f"local/{tag}", route="local", backend=backend,
                interpret=interpret, per_vertex=pv, **shape,
            ))
            if not pv:  # finding has no per-vertex variant
                specs.append(RouteSpec(
                    name=f"find/{tag}", route="find", backend=backend,
                    interpret=interpret, per_vertex=pv, **shape,
                ))
            specs.append(RouteSpec(
                name=f"stream/{tag}", route="stream", backend=backend,
                interpret=interpret, per_vertex=pv, **shape,
            ))
            for mode in HEDGE_MODES:
                for p in p_values:
                    specs.append(RouteSpec(
                        name=f"distributed/{tag}/{mode}/p{p}",
                        route="distributed", backend=backend,
                        interpret=interpret, per_vertex=pv, mode=mode,
                        p=p, **shape,
                    ))
    return specs
