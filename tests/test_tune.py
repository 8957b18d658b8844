"""Autotuning layer (DESIGN.md §11): plan_view as a cache key, the
LRU-bounded plan cache, BudgetGrid geometry validation + fits()
round-trip, trace recording/replay, TunedProfile persistence (including
corrupt-file degradation), per-cell option resolution, and the pre-warm
contract (plan_hit == 1.0, zero post-warm jit compiles)."""
from __future__ import annotations

import dataclasses
import json

import numpy as np
import pytest

from repro.api import TCOptions, TriangleEngine
from repro.core import sequential as seq
from repro.core.sequential import PlanCache
from repro.graph import generators as gen
from repro.graph.csr import (
    DEFAULT_BUDGET_GRID,
    BudgetGrid,
    ShapeBudget,
    degree_meta,
    from_edges_batch,
)
from repro.tune import (
    CellProfile,
    SweepConfig,
    TraceRecord,
    TraceRecorder,
    TunedProfile,
    build_profile,
    load_profile,
    prewarm_replay,
    read_trace,
    successive_halving,
    trace_signature,
    write_trace,
)
from repro.tune.sweep import SweepMismatch, _check_identical, evaluate_config

from conftest import optional_hypothesis

given, settings, st = optional_hypothesis()


def _mini_requests(n=10, seed=7):
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        if i % 3 == 2:
            reqs.append(gen.complete(5 + (i % 4)))
        else:
            reqs.append(gen.erdos_renyi(
                20 + 4 * i, 0.15, seed=int(rng.integers(1 << 30))))
    return reqs


def _mini_trace(n=10, seed=7, path=None):
    engine = TriangleEngine()
    with TraceRecorder(path) as rec:
        server = engine.serve(recorder=rec)
        for edges, nn in _mini_requests(n, seed):
            server.submit(edges, nn, deadline_s=1e9)
        server.drain()
        return list(rec.records), server


# ---------------------------------------------------------------------------
# TCOptions.plan_view() as the plan-cache key
# ---------------------------------------------------------------------------


class TestPlanView:
    def test_idempotent(self):
        o = TCOptions(bucket_widths=(8, 64), row_mult=16, deadline_s=0.5)
        assert o.plan_view().plan_view() == o.plan_view()

    def test_hashable(self):
        views = {TCOptions().plan_view(), TCOptions(root=3).plan_view()}
        assert len(views) == 1  # root is plan-irrelevant AND hash-stable

    def test_non_plan_knobs_collide(self):
        # options differing ONLY in plan-irrelevant knobs must share one
        # plan-cache entry: plan_view is the collision
        base = TCOptions()
        for variant in (
            TCOptions(deadline_s=0.25),
            TCOptions(admission_tokens=4),
            TCOptions(per_vertex=True),
            TCOptions(root=2),
            TCOptions(approx_samples=64),
            TCOptions(grid=BudgetGrid(min_nodes=128, min_slots=1024)),
            TCOptions(mode="ring"),
        ):
            assert variant.plan_view() == base.plan_view(), variant

    def test_plan_knobs_do_not_collide(self):
        base = TCOptions().plan_view()
        for variant in (
            TCOptions(bucket_widths=(8, 64)),
            TCOptions(row_mult=16),
            TCOptions(query_chunk=128),
        ):
            assert variant.plan_view() != base, variant

    def test_row_mult_folds_into_query_chunk(self):
        a = TCOptions(query_chunk=128, row_mult=64)
        b = TCOptions(query_chunk=128, row_mult=32)
        assert a.plan_view() == b.plan_view()

    def test_grid_is_plan_irrelevant_and_reset(self):
        o = TCOptions(grid=BudgetGrid(min_nodes=128, min_slots=512))
        assert o.plan_view().grid is None


# ---------------------------------------------------------------------------
# BudgetGrid geometry: validation + fits()/budget_for round-trip
# ---------------------------------------------------------------------------


class TestBudgetGridGeometry:
    @pytest.mark.parametrize("kw", [
        dict(min_nodes=0), dict(min_slots=-1), dict(factor=1.0),
        dict(factor=0.5), dict(max_nodes=32),  # < min_nodes=64
        dict(min_slots=512, max_slots=256),
    ])
    def test_invalid_geometry_raises(self, kw):
        with pytest.raises((ValueError, TypeError)):
            BudgetGrid(**kw)

    def test_hashable_value_semantics(self):
        assert BudgetGrid(factor=4.0) == BudgetGrid(factor=4.0)
        assert hash(BudgetGrid()) == hash(DEFAULT_BUDGET_GRID)
        assert TCOptions(grid=BudgetGrid(factor=4.0)) == TCOptions(
            grid=BudgetGrid(factor=4.0))

    def test_engine_surfaces_options_grid(self):
        g = BudgetGrid(min_nodes=128, min_slots=1024, factor=4.0)
        assert TriangleEngine(TCOptions(grid=g)).budgets == g
        # explicit budgets outrank options.grid
        assert TriangleEngine(
            TCOptions(grid=g), budgets=DEFAULT_BUDGET_GRID
        ).budgets == DEFAULT_BUDGET_GRID

    def _roundtrip(self, grid, n, m):
        if grid.fits(n, m):
            b = grid.budget_for(n, m)
            assert b.n_budget >= max(n, 1) or n == 0
            assert b.n_budget >= n and b.slot_budget >= 2 * m
            assert b.n_budget >= grid.min_nodes
            assert b.slot_budget >= grid.min_slots
            if grid.max_nodes is not None:
                assert b.n_budget <= grid.max_nodes
            if grid.max_slots is not None:
                assert b.slot_budget <= grid.max_slots
            # the cell is a fixed point: a request of exactly the cell's
            # extent rounds onto the same cell
            assert grid.budget_for(b.n_budget, b.slot_budget // 2) == b
        else:
            with pytest.raises(ValueError):
                grid.budget_for(n, m)

    def test_fits_roundtrip_examples(self):
        grid = BudgetGrid(min_nodes=64, min_slots=256, factor=2.0,
                          max_nodes=512, max_slots=4096)
        for n, m in [(0, 0), (1, 0), (64, 128), (65, 128), (512, 2048),
                     (513, 1), (1, 5000), (300, 700)]:
            self._roundtrip(grid, n, m)
        # a non-integer factor: the slot cell ceil(64 * 1.5**11) = 5536
        # must round onto itself
        self._roundtrip(BudgetGrid(min_nodes=1, min_slots=64, factor=1.5),
                        0, 1846)

    def test_default_factor_cells_are_power_of_two_multiples(self):
        """At factor 2.0 each cell is the smallest ``lo * 2**k`` holding
        the request: the budgets the default grid has always given."""
        grid = BudgetGrid()

        def want(x, lo):
            return lo if x <= lo else lo << (-(-x // lo) - 1).bit_length()

        sizes = list(range(0, 70000)) + [
            (1 << j) + d for j in range(17, 30) for d in (-1, 0, 1)]
        for x in sizes:
            b = grid.budget_for(x, x)
            assert b.n_budget == want(x, grid.min_nodes), x
            assert b.slot_budget == want(2 * x, grid.min_slots), x

    @settings(max_examples=80, deadline=None)
    @given(
        st.integers(1, 256), st.integers(64, 1024),
        st.sampled_from([1.5, 2.0, 4.0, 8.0]),
        st.one_of(st.none(), st.integers(256, 4096)),
        st.one_of(st.none(), st.integers(2048, 65536)),
        st.integers(0, 5000), st.integers(0, 50000),
    )
    def test_fits_roundtrip_property(self, mn, ms, f, mx_n, mx_s, n, m):
        self._roundtrip(
            BudgetGrid(min_nodes=mn, min_slots=ms, factor=f,
                       max_nodes=mx_n, max_slots=mx_s), n, m)


# ---------------------------------------------------------------------------
# LRU-bounded plan cache
# ---------------------------------------------------------------------------


class TestPlanCacheLRU:
    def test_capacity_evicts_lru(self):
        c = PlanCache(capacity=2)
        c["a"], c["b"] = 1, 2
        assert c.get("a") == 1  # refreshes 'a' to most-recent
        c["c"] = 3
        assert c.get("b") is None and c.get("a") == 1 and c.get("c") == 3
        assert c.evictions == 1 and len(c) == 2

    def test_unbounded_and_invalid(self):
        c = PlanCache(capacity=None)
        for i in range(1000):
            c[i] = i
        assert len(c) == 1000 and c.evictions == 0
        with pytest.raises(ValueError):
            PlanCache(capacity=0)

    def test_engine_stats_carry_eviction_counters(self):
        engine = TriangleEngine(plan_cache_capacity=1)
        reqs = _mini_requests(4)
        gb_small = from_edges_batch([reqs[0]], grid=engine.budgets)
        gb_large = from_edges_batch(
            [gen.complete(20)], grid=engine.budgets)
        engine.plan_for(gb_small)
        engine.plan_for(gb_small)  # hit
        engine.plan_for(gb_large)  # distinct key -> evicts the first
        stats = engine.plan_cache_stats()
        assert stats["capacity"] == 1 and stats["size"] == 1
        assert stats["hits"] == 1 and stats["misses"] == 2
        assert stats["evictions"] == 1
        # eviction is performance-only: replanning is pure, the count is
        # bit-identical after the entry was dropped and rebuilt
        engine.plan_for(gb_small)
        assert engine.plan_cache_stats()["evictions"] == 2

    def test_module_cache_stats_shape(self):
        stats = seq.batch_plan_cache_stats()
        for key in ("hits", "misses", "size", "evictions", "capacity"):
            assert key in stats


# ---------------------------------------------------------------------------
# Trace recording / replay
# ---------------------------------------------------------------------------


class TestTrace:
    def test_recorder_captures_and_file_roundtrips(self, tmp_path):
        path = tmp_path / "t.jsonl"
        records, server = _mini_trace(8, path=str(path))
        assert len(records) == 8
        assert all(r.route == "batch" and r.budget is not None
                   for r in records)
        back = read_trace(str(path))
        assert len(back) == 8
        for a, b in zip(records, back):
            assert (a.edges == b.edges).all()
            assert a.meta == b.meta and a.budget == b.budget
            assert a.n_nodes == b.n_nodes and a.request_id == b.request_id

    def test_write_read_roundtrip(self, tmp_path):
        records, _ = _mini_trace(5)
        p = tmp_path / "w.jsonl"
        write_trace(records, str(p))
        back = read_trace(str(p))
        assert [r.request_id for r in back] == [r.request_id for r in records]

    def test_signature_stable_and_versioned(self):
        records, _ = _mini_trace(8)
        sig = trace_signature(records)
        assert sig.startswith("v1|")
        assert sig == trace_signature(list(records))
        assert trace_signature([]) == "v1|empty"

    def test_future_version_rejected(self):
        with pytest.raises(ValueError, match="version"):
            TraceRecord.from_json({"v": 99, "id": 0, "n_nodes": 1,
                                   "n_edges": 0, "route": "batch"})

    def test_per_request_meta_bounds_batch_meta(self):
        # the quantizers commute with max: the union of per-request
        # degree_meta upper-bounds the packed batch's meta — the
        # property the pre-warm contract stands on
        reqs = _mini_requests(6)
        metas = [degree_meta(np.asarray(e), n) for e, n in reqs]
        union = metas[0]
        for m in metas[1:]:
            union = union.union(m)
        gb = from_edges_batch(
            [(np.asarray(e), n) for e, n in reqs],
            budget=ShapeBudget(256, 2048),
        )
        assert union.union(gb.meta) == union  # union >= batch meta

    def test_signature_only_record_refuses_replay(self):
        rec = TraceRecord(request_id=0, n_nodes=4, n_edges=0,
                          route="batch", budget=None, meta=None,
                          deadline_s=None, edges=None)
        with pytest.raises(ValueError, match="signature-only"):
            rec.request()


# ---------------------------------------------------------------------------
# TunedProfile persistence + engine resolution
# ---------------------------------------------------------------------------


def _tiny_profile():
    records, _ = _mini_trace(6)
    cfg = SweepConfig(
        "t", TCOptions(bucket_widths=(8, 64), row_mult=16),
        BudgetGrid(min_nodes=128, min_slots=1024, factor=4.0),
    )
    return build_profile(cfg, records, objective={"graphs_per_s": 1.0}), cfg


class TestProfile:
    def test_roundtrip_identical_per_cell_options(self, tmp_path):
        profile, cfg = _tiny_profile()
        path = profile.save(str(tmp_path / "p.json"))
        loaded = load_profile(path)
        assert loaded is not None
        assert loaded.signature == profile.signature
        assert loaded.options == profile.options
        assert loaded.grid == profile.grid
        assert loaded.cells == profile.cells
        for cell in profile.cells:
            assert loaded.options_for(cell.budget) == cfg.options
            assert loaded.meta_for(cell.budget) == cell.meta
        # an uncovered cell resolves to the profile default
        assert loaded.options_for(ShapeBudget(1 << 20, 1 << 22)) == cfg.options

    @pytest.mark.parametrize("payload", [
        "not json {",
        json.dumps({"version": 999, "signature": "x", "options": {},
                    "grid": {}}),
        json.dumps({"version": 1, "signature": "x",
                    "options": {"no_such_knob": 1},
                    "grid": {"min_nodes": 64, "min_slots": 256}}),
        json.dumps({"version": 1}),
    ])
    def test_corrupt_profile_degrades_with_warning(self, tmp_path, payload):
        p = tmp_path / "bad.json"
        p.write_text(payload)
        with pytest.warns(UserWarning, match="unusable tuned profile"):
            assert load_profile(str(p)) is None
        # server start NEVER crashes on a bad profile: defaults + warning
        with pytest.warns(UserWarning, match="unusable tuned profile"):
            engine = TriangleEngine(profile=str(p))
        assert engine.profile is None
        assert engine.budgets == DEFAULT_BUDGET_GRID
        assert engine.options == TCOptions()
        server = engine.serve()
        e, n = gen.complete(6)
        server.submit(e, n)
        out = server.drain()
        assert out[0].triangles == 20

    def test_missing_profile_file_degrades(self, tmp_path):
        with pytest.warns(UserWarning, match="unusable tuned profile"):
            engine = TriangleEngine(profile=str(tmp_path / "nope.json"))
        assert engine.profile is None

    def test_engine_adopts_profile_options_grid_and_cells(self):
        profile, cfg = _tiny_profile()
        engine = TriangleEngine(profile=profile)
        assert engine.options == cfg.options
        assert engine.budgets == cfg.grid
        for cell in profile.cells:
            assert engine.options_for(cell.budget) == cfg.options
            # the ceiling was seeded at construction
            assert engine._meta_ceiling[cell.budget] == cell.meta
        # explicit options outrank the profile default but not the cells
        eng2 = TriangleEngine(TCOptions(row_mult=128), profile=profile)
        assert eng2.options.row_mult == 128
        assert eng2.options_for(profile.cells[0].budget) == cfg.options
        assert eng2.options_for(ShapeBudget(1 << 20, 1 << 22)).row_mult == 128


# ---------------------------------------------------------------------------
# Sweep + pre-warm contract
# ---------------------------------------------------------------------------


class TestSweepAndPrewarm:
    def test_check_identical_raises_on_mismatch(self):
        base = {"triangles": [1, 2, 3], "overflow": False}
        ok = {"triangles": [1, 2], "overflow": False}
        _check_identical(ok, base, "ok")  # prefix compare, no raise
        with pytest.raises(SweepMismatch, match="changed request 1"):
            _check_identical({"triangles": [1, 9], "overflow": False},
                             base, "bad")
        with pytest.raises(SweepMismatch, match="overflow"):
            _check_identical({"triangles": [1], "overflow": True},
                             base, "ovf")

    def test_mini_sweep_bit_identical_and_winner(self):
        records, _ = _mini_trace(8)
        space = [
            SweepConfig("default", TCOptions()),
            SweepConfig("rm16", TCOptions(row_mult=16)),
        ]
        out = successive_halving(space, records, rungs=(1.0,))
        assert out["winner"]["label"] in {"default", "rm16"}
        assert len(out["triangles"]) == len(records)
        # ground truth: replays answered exactly what direct counting does
        engine = TriangleEngine()
        for rec, got in zip(records, out["triangles"]):
            assert engine.count(rec.request()).triangles == got

    def test_evaluate_config_rejects_unanswered_trace(self):
        records, _ = _mini_trace(4)
        # admission_tokens=1 + approx disabled sheds most of the stream:
        # the sweep must refuse to score such a config
        cfg = SweepConfig("shedding", TCOptions(
            admission_tokens=1, approx_on_overload=False))
        with pytest.raises(SweepMismatch):
            evaluate_config(cfg, records, batch_size=4)

    def test_prewarm_plan_hit_one_and_zero_compiles(self, tmp_path):
        records, _ = _mini_trace(10)
        profile = build_profile(SweepConfig("default", TCOptions()), records)
        loaded = load_profile(profile.save(str(tmp_path / "p.json")))
        rep = prewarm_replay(loaded, records)
        assert rep["plan_hit"] == 1.0
        assert rep["jit_compiles"] == 0
        engine = TriangleEngine()
        for rec, got in zip(records, rep["triangles"]):
            assert engine.count(rec.request()).triangles == got

    def test_unwarmed_server_reports_plan_misses(self):
        records, _ = _mini_trace(6)
        engine = TriangleEngine()
        server = engine.serve()  # no profile, no prewarm
        for rec in records:
            server.submit(*rec.request(), deadline_s=1e9)
        server.drain()
        s = server.summary()
        assert s["plan_hit"] < 1.0  # the cold path really is cold
        assert s["jit_compiles"] is None or s["jit_compiles"] >= 0
