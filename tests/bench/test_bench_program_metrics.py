"""The readers of the program's own spans, kernel names and counters
(``ingest_s``, ``compare_dev_s``, ``gather_fill``) give known numbers
on a count recorded on a TPU v5e and kept in ``bench/testdata``, and
nothing, without raising, where the program records none of them."""
from __future__ import annotations

import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import run, tracing  # noqa: E402

RECORDED = ROOT / "bench" / "testdata" / "trace_count_urand.json"
CELLS = ("count", "urand")


def reader(name):
    return run.load_module(ROOT / "bench" / "metrics" / f"{name}.py")


@pytest.fixture(scope="module")
def recorded():
    """One whole ``urand15.count`` count, traced on one v5e."""
    return tracing.TraceSummary(tracing.load_events(RECORDED))


@pytest.mark.parametrize("cell", CELLS)
def test_compare_kernels_on_the_recorded_count(recorded, cell):
    ctx = SimpleNamespace(trace=recorded, counters={"counts": 1})
    got = reader(f"compare_dev_s.{cell}").read(ctx)
    kernels = [(s, e) for p, ln, name, s, e in recorded.events
               if ln == tracing.OPS_LINE
               and tracing.op_name(name).startswith("intersect")]
    assert kernels
    assert got == pytest.approx(sum(e - s for s, e in kernels) * 1e-9)
    # the compare is a part of the probe program, not all of it
    assert 0 < got < recorded.device_s("_run_batch")


@pytest.mark.parametrize("cell", CELLS)
def test_ingest_on_the_recorded_count(recorded, cell):
    ctx = SimpleNamespace(trace=recorded, counters={"counts": 1})
    got = reader(f"ingest_s.{cell}").read(ctx)
    (span,) = [(s, e) for _, _, name, s, e in recorded.host
               if name == "tc.ingest"]
    assert got == pytest.approx((span[1] - span[0]) * 1e-9)
    # the device waits on the host throughout ingest
    assert sum(e - s for s, e in recorded.idle_gaps()) * 1e-9 >= got


def test_recorded_idle_carries_program_names(recorded):
    """The benchmark's own spans label almost none of the idle time:
    the program's ``tc.*`` spans and JAX's host events name the rest."""
    idle = dict((n, s) for n, s in recorded.idle_by_host(1000))
    bench = idle.get("bench.count", 0) + idle.get(tracing.WINDOW_SPAN, 0)
    assert bench < 0.05 * sum(idle.values())
    assert idle["tc.ingest"] == max(idle.values())


@pytest.mark.parametrize("cell", CELLS)
def test_readers_find_nothing_where_the_program_records_nothing(cell):
    """A trace of a program without the spans or kernel names, and a
    registry without the gather counters: each reader gives ``None``."""
    host, dev = "/host:CPU", "/device:TPU:0"
    events = [(host, "python", "bench.window", 0, 1000),
              (host, "python", "bench.count", 0, 1000),
              (dev, "XLA Ops", "fusion.1", 100, 900)]
    ctx = SimpleNamespace(trace=tracing.TraceSummary(events),
                          counters={"counts": 1})
    assert reader(f"ingest_s.{cell}").read(ctx) is None
    assert reader(f"compare_dev_s.{cell}").read(ctx) is None
    from repro import obs

    kept = obs.counters()
    obs.reset()
    try:
        assert reader(f"gather_fill.{cell}").read(ctx) is None
        obs.incr("probe.entries_gathered", 400)
        obs.incr("probe.entries_real", 100)
        assert reader(f"gather_fill.{cell}").read(ctx) == 25.0
    finally:
        obs.reset()
        for k, v in kept.items():
            obs.incr(k, v)
