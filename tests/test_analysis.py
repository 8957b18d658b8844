"""The static program auditor (``repro.analysis``, DESIGN.md §12).

Covers every pass on toy programs with known answers, the negative
tests the acceptance criteria demand (a synthetic unpriced collective
and a synthetic int32-overflow site must each dirty the baseline diff
and therefore fail CI), the golden findings JSON for the toy bounds
program, and the compile-set property: the static enumeration equals a
real prewarmed server's observed compile count, with zero post-warm
compiles on a replay of the profiled trace.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.bounds import (
    audit_fused_bounds,
    audit_host_sites,
    audit_program_bounds,
    lane_view_bounds,
    scale_shape,
)
from repro.analysis.collectives import (
    audit_collectives,
    census_digest,
    unpriced_collectives,
)
from repro.analysis.compile_set import (
    audit_compile_set,
    enumerate_compile_keys,
    predicted_jit_compiles,
)
from repro.analysis.deadcode import find_unused_symbols, public_symbols
from repro.analysis.dtypes import (
    INT32_MAX,
    IndexWidthError,
    index_dtype,
    jnp_index_dtype,
)
from repro.analysis.findings import (
    Finding,
    Report,
    diff_reports,
    merge_findings,
)
from repro.analysis.hostsync import (
    _sync_calls,
    audit_hot_path_syncs,
    audit_program_callbacks,
)
from repro.analysis.routes import enumerate_route_specs
from repro.analysis.walker import (
    callback_eqns,
    collective_eqns,
    iter_eqns,
    weak_typed_invars,
)

GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "data")


# ---------------------------------------------------------------------------
# walker: the shared jaxpr traversal core
# ---------------------------------------------------------------------------


class TestWalker:
    def test_program_order_and_paths(self):
        def f(x):
            y = x + 1.0

            def body(c, _):
                return c * 2.0, c

            z, _ = jax.lax.scan(body, y, None, length=3)
            return z

        names = [es.primitive for es in iter_eqns(jax.make_jaxpr(f)(1.0))]
        # composite (scan) yielded BEFORE its body's eqns
        assert names.index("scan") < names.index("mul")
        mul = next(es for es in iter_eqns(jax.make_jaxpr(f)(1.0))
                   if es.primitive == "mul")
        assert mul.path and mul.path[0].startswith("scan:")

    def test_scan_trips_multiply(self):
        def f(x):
            def body(c, _):
                return c + 1, None

            return jax.lax.scan(body, x, None, length=5)[0]

        add = next(es for es in iter_eqns(jax.make_jaxpr(f)(0))
                   if es.primitive == "add")
        assert add.trips == 5
        assert not add.in_while

    def test_while_body_flagged(self):
        def f(x):
            return jax.lax.while_loop(lambda c: c < 10, lambda c: c + 1, x)

        sites = list(iter_eqns(jax.make_jaxpr(f)(0)))
        adds = [es for es in sites if es.primitive == "add"]
        lts = [es for es in sites if es.primitive == "lt"]
        assert adds and all(es.in_while for es in adds)
        # the cond jaxpr is NOT the dynamically-tripped body
        assert lts and not any(es.in_while for es in lts)

    def test_collective_and_callback_detection(self):
        def f(x):
            return jax.lax.psum(x, "p")

        jx = jax.make_jaxpr(f, axis_env=[("p", 2)])(1.0)
        assert [es.primitive for es in collective_eqns(jx)] == ["psum"]
        assert collective_eqns(jx, axis_name="q") == []

        def g(x):
            return jax.pure_callback(
                lambda v: v, jax.ShapeDtypeStruct((), jnp.float32), x
            )

        cb = callback_eqns(jax.make_jaxpr(g)(jnp.float32(1.0)))
        assert len(cb) == 1 and "callback" in cb[0].primitive

    def test_weak_type_detection(self):
        weak = weak_typed_invars(jax.make_jaxpr(lambda x: x + 1)(1.0))
        assert len(weak) == 1
        strong = weak_typed_invars(
            jax.make_jaxpr(lambda x: x + 1)(jnp.float32(1.0))
        )
        assert strong == []


# ---------------------------------------------------------------------------
# findings: report, baseline diff, the CI gate mechanics
# ---------------------------------------------------------------------------


def _finding(site, pass_name="bounds", severity="warning"):
    return Finding(pass_name=pass_name, site=site, severity=severity,
                   detail=f"toy {site}")


class TestFindings:
    def test_report_roundtrip_and_sorting(self, tmp_path):
        r = Report(findings=[_finding("b"), _finding("a")], meta={"k": 1})
        p = tmp_path / "r.json"
        r.save(str(p))
        back = Report.load(str(p))
        assert [f.site for f in back.findings] == ["a", "b"]
        assert back.meta == {"k": 1}

    def test_duplicate_keys_rejected(self):
        with pytest.raises(ValueError, match="duplicate"):
            Report(findings=[_finding("x"), _finding("x")])
        with pytest.raises(ValueError, match="duplicate"):
            merge_findings([_finding("x")], [_finding("x")])

    def test_diff_clean_and_dirty(self):
        base = Report(findings=[_finding("a"), _finding("b")])
        assert diff_reports(
            Report(findings=[_finding("b"), _finding("a")]), base
        ).clean
        d = diff_reports(Report(findings=[_finding("a"), _finding("c")]),
                         base)
        assert [f.site for f in d.new] == ["c"]
        assert [f.site for f in d.fixed] == ["b"]
        text = d.render(baseline_path="results/AUDIT_baseline.json")
        assert "--write-baseline" in text and "NEW" in text

    def test_newer_version_refused(self):
        with pytest.raises(ValueError, match="version"):
            Report.from_json({"version": 999, "findings": []})


# ---------------------------------------------------------------------------
# dtypes policy + the satellite regression at the offending scale
# ---------------------------------------------------------------------------


class TestIndexDtypePolicy:
    def test_boundaries(self):
        assert index_dtype(INT32_MAX) == np.dtype(np.int32)
        assert index_dtype(2**31) == np.dtype(np.int64)
        assert index_dtype(0) == np.dtype(np.int32)
        with pytest.raises(ValueError):
            index_dtype(-1)
        with pytest.raises(IndexWidthError):
            index_dtype(2**63)

    def test_x32_refuses_int64_bounds(self):
        assert not jax.config.jax_enable_x64
        assert jnp_index_dtype(INT32_MAX, site="t") == np.dtype(np.int32)
        with pytest.raises(IndexWidthError, match="row_offsets"):
            jnp_index_dtype(2**31, site="row_offsets test")

    def test_from_edges_scale26_fails_loudly_without_materializing(self):
        """The satellite regression: at Graph500 scale 26 the slot
        budget is 2³¹ — the historical int32 cast wrapped offsets
        silently; the policy now raises BEFORE any giant buffer is
        allocated (this test runs in milliseconds)."""
        from repro.graph.csr import from_edges

        edges = np.array([[0, 1], [1, 2]])
        _, slots = scale_shape(26)
        with pytest.raises(IndexWidthError, match="row_offsets"):
            from_edges(edges, 3, num_slots=slots)
        # one scale down still fits int32 and must keep working
        g = from_edges(edges, 3, num_slots=64)
        assert g.row_offsets.dtype == jnp.int32

    def test_abstract_graph_eval_shape_at_scale26(self):
        """``jax.eval_shape`` over the policy avals at the offending
        scale — no element is ever materialized.  Offsets need int64,
        ids still fit int32; and under x32 the device trace SILENTLY
        canonicalizes the int64 aval back down to int32 — the exact
        wrap hazard that forces ``jnp_index_dtype`` to refuse the
        build rather than hand the program a downcast array."""
        from repro.graph.csr import abstract_graph

        n, slots = scale_shape(26)
        g = abstract_graph(n, slots)
        assert np.dtype(g.row_offsets.dtype) == np.dtype(np.int64)
        assert np.dtype(g.src.dtype) == np.dtype(np.int32)
        got = jax.eval_shape(lambda gr: gr.row_offsets[-1], g)
        assert np.dtype(got.dtype) == np.dtype(np.int32)  # the hazard
        with jax.enable_x64(True):
            got64 = jax.eval_shape(lambda gr: gr.row_offsets[-1], g)
        assert np.dtype(got64.dtype) == np.dtype(np.int64)


# ---------------------------------------------------------------------------
# bounds pass: interval rules, golden toy findings, synthetic overflow
# ---------------------------------------------------------------------------


def _toy_overflow_jaxpr():
    """cumsum of an int32 bounded by 2³⁰ over 8 elements: bound 2³³."""
    return jax.make_jaxpr(lambda x: jnp.cumsum(x))(
        jax.ShapeDtypeStruct((8,), jnp.int32)
    )


class TestBoundsPass:
    def test_clean_program_no_findings(self):
        jx = jax.make_jaxpr(lambda x: jnp.cumsum(x) + 1)(
            jax.ShapeDtypeStruct((8,), jnp.int32)
        )
        assert audit_program_bounds("toy", jx, [(0, 100)]) == []

    def test_cumsum_overflow_flagged(self):
        fs = audit_program_bounds("toy", _toy_overflow_jaxpr(),
                                  [(0, 2**30)])
        assert any("cumsum" in f.site for f in fs)

    def test_mul_overflow_flagged(self):
        jx = jax.make_jaxpr(lambda x: x * x)(
            jax.ShapeDtypeStruct((4,), jnp.int32)
        )
        fs = audit_program_bounds("toy", jx, [(0, 2**16 + 1)])
        assert any("mul" in f.site for f in fs)

    def test_input_bound_exceeding_dtype_is_error(self):
        jx = jax.make_jaxpr(lambda x: x)(
            jax.ShapeDtypeStruct((4,), jnp.int32)
        )
        fs = audit_program_bounds("toy", jx, [(0, 2**31)])
        assert [f.severity for f in fs] == ["error"]
        assert fs[0].site == "toy:input:invar"

    def test_unknown_primitive_is_sound_top(self):
        # while outputs are unknown — downstream ops cannot flag from ⊤
        def f(x):
            y = jax.lax.while_loop(lambda c: c < 3, lambda c: c + 1, x)
            return y * y

        jx = jax.make_jaxpr(f)(jax.ShapeDtypeStruct((), jnp.int32))
        assert audit_program_bounds("toy", jx, [(0, 2**30)]) == []

    def test_golden_toy_findings(self):
        """The toy overflow program's findings, pinned as golden JSON —
        the bounds pass's output format is part of the CI contract."""
        fs = audit_program_bounds("toy", _toy_overflow_jaxpr(),
                                  [(0, 2**30)])
        got = [f.to_json() for f in fs]
        with open(os.path.join(GOLDEN_DIR,
                               "analysis_toy_findings.json")) as fh:
            assert got == json.load(fh)

    def test_host_sites_by_scale(self):
        assert audit_host_sites(20) == []
        s26 = {f.site for f in audit_host_sites(26)}
        assert s26 == {"host:from_edges:row_offsets@scale26"}
        s36 = {f.site for f in audit_host_sites(36)}
        assert s36 == {"host:from_edges:row_offsets@scale36",
                       "host:from_edges:vertex-ids@scale36"}

    def test_fused_scale26_trace_refused(self):
        fs = audit_fused_bounds(26)
        assert [f.severity for f in fs] == ["error"]
        assert "x32-refused" in fs[0].site

    def test_lane_view_bounds_match_flatten_order(self):
        from repro.analysis.routes import abstract_lane_view

        gview = abstract_lane_view(64, 256, 2)
        leaves = jax.tree_util.tree_leaves(gview)
        assert len(leaves) == len(lane_view_bounds(64, 256))

    def test_synthetic_overflow_dirties_baseline(self):
        """Negative test (acceptance): a new int32-overflow finding is
        a NEW baseline key, so ``audit --check`` exits nonzero."""
        base = Report(findings=[_finding("fused@scale25:op:add")])
        injected = Report(findings=[
            _finding("fused@scale25:op:add"),
            _finding("fused@scale25:op:cumsum"),  # the synthetic site
        ])
        assert not diff_reports(injected, base).clean


# ---------------------------------------------------------------------------
# hostsync pass
# ---------------------------------------------------------------------------


class TestHostsyncPass:
    def test_sanctioned_sync_set_is_exactly_pinned(self):
        sites = {f.site for f in audit_hot_path_syncs()}
        assert sites == {
            "ast:TriangleServer._finalize_one:device_get:x1",
            "ast:repro.core.sequential._exact_batch_plan:device_get:x1",
        }

    def test_toy_function_sync_counting(self):
        def hot(x):
            jax.block_until_ready(x)
            return int(jax.device_get(x).item())

        counts = _sync_calls("toy.hot", hot)
        assert counts == {"block_until_ready": 1, "device_get": 1,
                          "item": 1}

    def test_callback_in_program_is_error(self):
        def f(x):
            return jax.pure_callback(
                lambda v: v, jax.ShapeDtypeStruct((), jnp.float32), x
            )

        fs = audit_program_callbacks(
            [("toy/f", jax.make_jaxpr(f)(jnp.float32(1.0)))]
        )
        assert len(fs) == 1 and fs[0].severity == "error"

    def test_route_programs_are_callback_free(self):
        specs = enumerate_route_specs(p_values=(1,))
        programs = [p for s in specs for p in s.programs()]
        # 4 batch + 2x4 local + 2 find + 8 dist + 4 stream
        assert len(programs) == 26
        assert audit_program_callbacks(programs) == []


# ---------------------------------------------------------------------------
# collectives pass
# ---------------------------------------------------------------------------


def _p1_distributed_specs():
    return [s for s in enumerate_route_specs(p_values=(1,))
            if s.route == "distributed"]


class TestCollectivesPass:
    def test_census_is_deterministic_and_error_free(self):
        spec = _p1_distributed_specs()[0]
        a = audit_collectives([spec])
        b = audit_collectives([spec])
        assert [f.site for f in a] == [f.site for f in b]
        assert all(f.severity == "info" for f in a)
        census = a[0]
        assert census.data["count"] in (13, 14)

    def test_per_vertex_adds_exactly_one_reduce(self):
        specs = _p1_distributed_specs()
        plain = next(s for s in specs
                     if not s.per_vertex and s.mode == "allgather"
                     and s.backend == "jnp")
        pv = next(s for s in specs
                  if s.per_vertex and s.mode == "allgather"
                  and s.backend == "jnp")
        c_plain = audit_collectives([plain])[0].data
        c_pv = audit_collectives([pv])[0].data
        assert c_pv["count"] == c_plain["count"] + 1
        assert (c_pv["by_phase"]["reduce"]
                == c_plain["by_phase"]["reduce"] + 1)

    def test_census_digest_keys_on_inventory(self):
        from repro.core.comm_instrument import CollectiveSite

        s1 = CollectiveSite(kind="psum", phase="reduce", shape=(),
                            dtype="int32", bytes_fixed=0,
                            bytes_per_sweep=0, trips=1)
        s2 = CollectiveSite(kind="psum", phase="bfs", shape=(),
                            dtype="int32", bytes_fixed=0,
                            bytes_per_sweep=0, trips=1)
        assert census_digest([s1]) != census_digest([s1, s1])
        assert census_digest([s1]) != census_digest([s2])

    def test_unpriced_collective_detected(self):
        """A collective over the mesh axis that the wire model cannot
        price is reported outright."""
        def f(x):
            return jax.lax.psum_scatter(x, "p")

        jx = jax.make_jaxpr(f, axis_env=[("p", 2)])(
            jax.ShapeDtypeStruct((2,), jnp.float32)
        )
        hits = unpriced_collectives(jx)
        assert len(hits) == 1 and "scatter" in hits[0]
        # priced collectives do NOT appear
        jx2 = jax.make_jaxpr(lambda x: jax.lax.psum(x, "p"),
                             axis_env=[("p", 2)])(jnp.float32(1.0))
        assert unpriced_collectives(jx2) == []

    def test_synthetic_unpriced_collective_dirties_baseline(self):
        """Negative test (acceptance): an injected collective changes
        the census site key AND adds an unpriced error — both are NEW
        baseline keys, so ``audit --check`` exits nonzero."""
        spec = _p1_distributed_specs()[0]
        label = f"{spec.name}/shard"
        base = Report(findings=audit_collectives([spec]))
        injected = Report(findings=merge_findings(
            base.findings,
            [Finding(pass_name="collectives",
                     site=f"unpriced:{label}:psum_scatter@shard",
                     severity="error", detail="synthetic injection")],
        ))
        d = diff_reports(injected, base)
        assert not d.clean and len(d.new) == 1


# ---------------------------------------------------------------------------
# dead-code pass
# ---------------------------------------------------------------------------


class TestDeadcodePass:
    def test_public_symbol_extraction(self, tmp_path):
        mod = tmp_path / "m.py"
        mod.write_text(
            "X_CONST = 1\n_private = 2\nlower_var = 3\n"
            "def used():\n    pass\n\ndef _hidden():\n    pass\n"
            "class Thing:\n    pass\n"
        )
        assert public_symbols(mod) == ["X_CONST", "used", "Thing"]

    def test_unused_detection_counts_any_reference(self, tmp_path):
        pkg = tmp_path / "src" / "repro"
        pkg.mkdir(parents=True)
        (pkg / "a.py").write_text(
            "def dead():\n    pass\n\ndef alive():\n    pass\n"
            "def internal():\n    pass\n\ndef caller():\n"
            "    return internal()\n"
        )
        (pkg / "b.py").write_text("from repro.a import alive\nalive()\n")
        unused = find_unused_symbols(tmp_path)
        assert {u["symbol"] for u in unused} == {"dead", "caller"}

    def test_partition_module_is_wired_and_documented(self):
        """The satellite: partition.py must not be silently dead — its
        symbols are referenced, and the module documents itself as the
        ROADMAP item 5 seam."""
        import repro.graph.partition as partition

        unused = {(u["module"], u["symbol"])
                  for u in find_unused_symbols()}
        assert ("repro.graph.partition", "vertex_partition") not in unused
        assert ("repro.graph.partition", "shard_edges") not in unused
        assert "ROADMAP" in (partition.__doc__ or "")
        assert "seam" in partition.__doc__


# ---------------------------------------------------------------------------
# compile-set pass: the static-enumeration == observed-compiles property
# ---------------------------------------------------------------------------


class TestCompileSetPass:
    def test_profileless_engine_has_empty_compile_set(self):
        from repro.api import TriangleEngine

        engine = TriangleEngine()
        assert enumerate_compile_keys(engine) == []
        assert engine.compile_space() == []

    def test_prediction_matches_prewarmed_server(self):
        """The acceptance property, end to end: record a trace, freeze
        it into a profile, statically enumerate the compile set — then
        prove a real ``serve(prewarm=True)`` server compiles EXACTLY
        that many fused entries and replays the trace with zero
        post-warm compiles and a 100% plan-cache hit rate."""
        from repro.api import TriangleEngine
        from repro.core import sequential as seq
        from repro.graph import generators as gen
        from repro.launch.serve_tc import _jit_cache_size
        from repro.tune.sweep import SweepConfig, build_profile
        from repro.tune.trace import TraceRecorder

        # 1. record a small mixed trace
        engine0 = TriangleEngine()
        with TraceRecorder() as rec:
            server0 = engine0.serve(batch_size=2, recorder=rec)
            for i in range(6):
                if i % 3 == 2:
                    edges, nn = gen.complete(5 + i % 3)
                else:
                    edges, nn = gen.erdos_renyi(20 + 6 * i, 0.15,
                                                seed=100 + i)
                server0.submit(edges, nn, deadline_s=1e9)
            server0.drain()
            records = list(rec.records)
        assert records

        # 2. freeze a profile from the trace; enumerate statically
        profile = build_profile(
            SweepConfig("prop", engine0.options), records
        )
        engine = TriangleEngine(profile=profile)
        predicted = predicted_jit_compiles(engine, batch_size=2)
        assert predicted > 0
        assert len(engine.compile_space(batch_size=2)) == predicted

        # 3. the prewarmed server compiles exactly the enumerated set
        seq._tc_batch_fused._clear_cache()
        assert _jit_cache_size() == 0
        server = engine.serve(batch_size=2, prewarm=True)
        assert _jit_cache_size() == predicted

        # 4. replay the profiled trace: fully covered, zero compiles
        for r in records:
            edges, nn = r.request()
            server.submit(edges, nn, deadline_s=1e9)
        server.drain()
        stats = server.summary()
        assert stats["jit_compiles"] == 0
        assert stats["plan_hit"] == 1.0

    def test_audit_findings_shape(self):
        from repro.api import TriangleEngine
        from repro.graph import generators as gen
        from repro.tune.sweep import SweepConfig, build_profile
        from repro.tune.trace import TraceRecorder

        engine0 = TriangleEngine()
        with TraceRecorder() as rec:
            server = engine0.serve(batch_size=2, recorder=rec)
            edges, nn = gen.erdos_renyi(24, 0.2, seed=5)
            server.submit(edges, nn, deadline_s=1e9)
            server.drain()
        profile = build_profile(
            SweepConfig("t", engine0.options), list(rec.records)
        )
        engine = TriangleEngine(profile=profile)
        fs = audit_compile_set(engine, batch_size=2, label="t")
        sites = [f.site for f in fs]
        assert any(s.startswith("census:t:") for s in sites)
        # the default grid is unbounded — the warning documents it
        assert any(s.startswith("unbounded-grid") for s in sites)
        # no weak-type leaks in the real fused program
        assert not any(s.startswith("weak-type") for s in sites)


# ---------------------------------------------------------------------------
# audit CLI plumbing (pass wiring; the full run is the CI audit job)
# ---------------------------------------------------------------------------


class TestAuditCli:
    def test_check_against_written_baseline_roundtrips(self, tmp_path):
        base = Report(findings=[_finding("a")], meta={})
        p = tmp_path / "base.json"
        base.save(str(p))
        fresh = Report(findings=[_finding("a")])
        assert diff_reports(fresh, Report.load(str(p))).clean

    def test_tracked_baseline_exists_and_parses(self):
        path = os.path.join(os.path.dirname(__file__), os.pardir,
                            "results", "AUDIT_baseline.json")
        report = Report.load(path)
        assert len(report.findings) > 0
        passes = {f.pass_name for f in report.findings}
        assert passes == {"bounds", "collectives", "compile_set",
                          "deadcode", "hostsync"}
