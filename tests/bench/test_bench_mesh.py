"""The four-chip cell ``kron15.p4``: its driver at test size on four
host devices comes out correct, and its control and planted faults (a
wrong total, per-chip partials that do not sum to it) do not; its
readers take the slowest chip, not the sum over chips, find the
collectives by either naming, and give ``None`` on a trace with no
Algorithm 2 program or spans."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import run, tracing  # noqa: E402

CELL = "kron15.p4"

BODY = """
import dataclasses, json, sys
import jax, numpy as np
sys.path[:0] = [ROOT, ROOT + "/src"]
from bench import run
from repro.api import TriangleEngine

cell = run.resolve(run.load_json(run.ROOT / "BENCHMARK.json"), "kron15.p4")
cell.config.update(scale=9)
devices = jax.devices()[:4]

def once(variant=None):
    out = run.run_cell(cell, 2**31 + 29, 0.5, False, devices,
                       variant=variant)
    return {k: out[k] for k in ("correct", "attempted", "failed",
                                "checks", "metrics")}

real = TriangleEngine.count
def wrong_total(self, *a, **kw):
    rep = real(self, *a, **kw)
    return dataclasses.replace(rep, triangles=rep.triangles + 1,
                               per_device=rep.per_device + np.eye(4, 1, 0,
                                   dtype=rep.per_device.dtype).ravel())
def unequal_parts(self, *a, **kw):
    rep = real(self, *a, **kw)
    return dataclasses.replace(rep, per_device=rep.per_device[::-1] * 2)

out = {"program": once(), "control": once(cell.config["control"])}
TriangleEngine.count = wrong_total
out["wrong_total"] = once()
TriangleEngine.count = unequal_parts
out["unequal_parts"] = once()
print("RESULT", json.dumps(out))
"""


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    body = f"ROOT = {str(ROOT)!r}\n" + textwrap.dedent(BODY)
    out = subprocess.run([sys.executable, "-c", body], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    (line,) = [ln for ln in out.stdout.splitlines()
               if ln.startswith("RESULT ")]
    return json.loads(line[len("RESULT "):])


def test_program_on_four_devices_is_correct(runs):
    out = runs["program"]
    assert out["correct"] and out["failed"] == 0 and out["attempted"] >= 2
    assert set(out["checks"]) == {"count_error", "overflow_flags",
                                  "other_route", "unequal_per_device"}
    assert set(out["metrics"]) == {"count_s", "setup_s"}


def test_control_is_not_correct(runs):
    out = runs["control"]
    assert not out["correct"] and out["failed"] == out["attempted"]
    assert out["checks"]["other_route"]["value"] == out["attempted"]
    assert out["checks"]["unequal_per_device"]["value"] == out["attempted"]


def test_wrong_total_is_not_correct(runs):
    out = runs["wrong_total"]
    assert not out["correct"] and out["failed"] == out["attempted"]
    assert out["checks"]["count_error"]["value"] == 1
    # the partials were moved with the total: only the count is wrong
    assert out["checks"]["unequal_per_device"]["value"] == 0


def test_unequal_partials_are_not_correct(runs):
    out = runs["unequal_parts"]
    assert not out["correct"] and out["failed"] == out["attempted"]
    assert out["checks"]["count_error"]["value"] == 0
    assert out["checks"]["unequal_per_device"]["value"] == out["attempted"]


# -- readers on a hand-made trace of four chips ---------------------------

HOST = "/host:CPU"
PLANES = [f"/device:TPU:{i}" for i in range(4)]
#: per chip: the program's time and its ops; chip 2 is the slowest
PROGRAM_NS = [6000, 5000, 8000, 4000]


def _events():
    ev = [(HOST, "python", "bench.window", 0, 20000),
          (HOST, "python", "bench.count", 0, 20000),
          (HOST, "python", "tc.ingest", 0, 500),
          (HOST, "python", "tc.shard", 500, 900),
          (HOST, "python", "tc.plan_layout", 900, 1000),
          (HOST, "python", "tc.probe", 1000, 1100),
          (HOST, "python", "tc.fetch", 1100, 19000)]
    for i, (plane, prog) in enumerate(zip(PLANES, PROGRAM_NS)):
        start = 1200
        ev += [
            (plane, "XLA Modules", "jit__tc_distributed(7)", start,
             start + prog),
            # the chip starts its op before ingest ends on chip 0 only
            (plane, "XLA Ops", "fusion.4", 400 if i == 0 else start,
             start + 100),
            (plane, "XLA Ops", "pmax.42", start + 100, start + 200),
            (plane, "XLA Ops", "all_to_all.13", start + 200, start + 300),
            (plane, "XLA Ops", "all-reduce.11", start + 300,
             start + 300 + 100 * (i + 1)),
            (plane, "XLA Ops", "all-gather-start.2", start + 800,
             start + 850),
            (plane, "XLA Ops", "collective-permute-done", start + 850,
             start + 900),
            (plane, "XLA Ops", "reduce_max.3", start + 900, start + 1000),
            (plane, "XLA Ops", "psum_fusion.1", start + 1000, start + 1100),
            (plane, "XLA Ops", "intersect_split_w32.9", start + 1100,
             start + 1100 + 1000 * (i + 1)),
        ]
    return ev


def reader(name):
    return run.load_module(ROOT / "bench" / "metrics" / f"{name}.py")


def _ctx(events, **counters):
    return SimpleNamespace(
        trace=tracing.TraceSummary(events),
        counters=dict({"counts": 2, "n": 1000, "m": 4000, "chips": 4},
                      **counters),
        memory_peak_bytes=3e9, peaks=run.peaks_for("TPU v5 lite"))


def test_program_and_kernel_time_is_the_slowest_chip():
    ctx = _ctx(_events())
    assert reader("dist_dev_s.p4").read(ctx) == pytest.approx(8000e-9 / 2)
    assert ctx.trace.device_s("_tc_distributed") == pytest.approx(
        sum(PROGRAM_NS) * 1e-9)  # what the sum over chips would read
    assert reader("compare_dev_s.p4").read(ctx) == pytest.approx(
        4000e-9 / 2)


def test_collectives_by_opcode_or_primitive_name():
    ctx = _ctx(_events())
    # pmax, all_to_all, all-reduce (400 ns on chip 3), the -start and
    # -done halves; not reduce_max nor a fusion that holds a psum
    want = 100 + 100 + 400 + 50 + 50
    assert reader("collective_dev_s.p4").read(ctx) == pytest.approx(
        want * 1e-9 / 2)


def test_roofline_shares_the_floor_over_the_chips():
    ctx = _ctx(_events())
    floor_s = (4 * 1001 + 8 * 4000) / (4 * 819e9)
    assert reader("probe_roofline.p4").read(ctx) == pytest.approx(
        100 * floor_s * 2 / 8000e-9)


def test_host_prep_counts_only_where_every_chip_waits():
    ctx = _ctx(_events())
    # ingest, shard and layout span 0..1000 ns; chip 0 runs from 400
    assert reader("host_prep_s.p4").read(ctx) == pytest.approx(
        400e-9 / 2)


def test_program_counters(monkeypatch):
    from repro import obs

    monkeypatch.setattr(obs, "_COUNTERS", type(obs._COUNTERS)())
    ctx = _ctx(_events())
    assert reader("row_fill.p4").read(ctx) is None
    assert reader("wire_mb.p4").read(ctx) is None
    obs.incr("dist.counts", 2)
    obs.incr("dist.rows_planned", 800)
    obs.incr("dist.rows_real", 308)
    obs.incr("dist.wire_bytes", 5e6)
    assert reader("row_fill.p4").read(ctx) == pytest.approx(38.5)
    assert reader("wire_mb.p4").read(ctx) == pytest.approx(2.5)
    assert reader("peak_hbm_gb.p4").read(ctx) == pytest.approx(3.0)


def test_device_idle_averages_the_chips():
    ctx = _ctx(_events())
    t = ctx.trace
    assert len(t.devices) == 4
    assert reader("device_idle.p4").read(ctx) == pytest.approx(
        100 * (1 - t.busy_s / t.window_s))


def test_readers_find_nothing_without_the_program():
    """A distributed count as the parent program traces it: another
    program name and no ``tc.shard`` span."""
    events = [e for e in _events() if e[2] != "tc.shard"]
    events = [(p, ln, "jit_shard_map(3)" if ln == "XLA Modules" else n, s, e)
              for p, ln, n, s, e in events]
    ctx = _ctx(events)
    for name in ("dist_dev_s.p4", "probe_roofline.p4", "host_prep_s.p4"):
        assert reader(name).read(ctx) is None, name
    # the kernels and collectives are still there to read
    assert reader("compare_dev_s.p4").read(ctx) > 0
    assert reader("collective_dev_s.p4").read(ctx) > 0
