"""The trace reduction gives known numbers: on a hand-made trace, on a
small trace recorded on a TPU v5e and kept in ``bench/testdata``, and
the reader finds the benchmark's host span in a real profiler file."""
from __future__ import annotations

import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import tracing  # noqa: E402

HOST, DEV = "/host:CPU", "/device:TPU:0"
EVENTS = [
    (HOST, "python", "bench.window", 1000, 11000),
    (HOST, "python", "bench.count", 1000, 6000),
    (HOST, "python", "bench.count", 6000, 11000),
    (HOST, "python", "inner", 1100, 1400),
    (HOST, "other", "elsewhere", 0, 20000),
    (DEV, "XLA Ops", "%fusion.1 = s32[8] fusion(...)", 1500, 2800),
    (DEV, "XLA Ops", "fusion.2", 2800, 4000),
    (DEV, "XLA Ops", "while.3", 7000, 12000),
    (DEV, "XLA Ops", "fusion.4", 8000, 9500),
    (DEV, "XLA Modules", "jit__plan_batch(1)", 1500, 4000),
    (DEV, "XLA Modules", "jit__run_batch(2)", 7000, 12000),
]


def test_hand_made_trace():
    t = tracing.TraceSummary(EVENTS)
    assert t.window_s == pytest.approx(10000e-9)
    assert t.devices == [DEV]
    assert t.busy_s == pytest.approx(6500e-9)
    assert t.device_s("_plan_batch") == pytest.approx(2500e-9)
    assert t.device_s("_run_batch") == pytest.approx(4000e-9)
    assert t.idle_gaps() == [(1000, 1500), (4000, 7000)]
    assert t.idle_by_host() == [["bench.count", pytest.approx(3000e-9)],
                                ["inner", pytest.approx(500e-9)]]
    # own time: the while loop's clipped 4000 ns less its nested fusion
    assert t.top_ops() == [["while.3", pytest.approx(2500e-9)],
                           ["fusion.4", pytest.approx(1500e-9)],
                           ["fusion.1", pytest.approx(1300e-9)],
                           ["fusion.2", pytest.approx(1200e-9)]]


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        tracing.TraceSummary(EVENTS[1:])


def _busy_by_timeline(events, t0, t1, step):
    """Busy time counted on a grid of ``step`` ns, independently of the
    interval union."""
    grid = np.zeros((t1 - t0) // step + 1, bool)
    for plane, line, _, s, e in events:
        if plane.startswith("/device:") and line == "XLA Ops":
            a, b = max(s, t0), min(e, t1)
            if b > a:
                grid[(a - t0) // step:(b - t0 + step - 1) // step] = True
    return grid.sum() * step * 1e-9


def test_recorded_trace():
    """A window of ``kron15.count``'s traced run on one v5e, cut to its
    first events: the busy time matches a timeline count, the probe and
    plan programs are found, and idle time is attributed to host spans
    that all lie inside the window."""
    events = tracing.load_events(ROOT / "bench" / "testdata"
                                 / "trace_small.json")
    t = tracing.TraceSummary(events)
    busy = _busy_by_timeline(events, t.t0, t.t1, 1000)
    assert t.busy_s == pytest.approx(busy, rel=0.02)
    assert 0 < t.busy_s < t.window_s
    assert t.device_s("_run_batch") > 0 and t.device_s("_plan_batch") > 0
    idle = sum(s for _, s in t.idle_by_host(1000))
    assert idle == pytest.approx(t.window_s - t.busy_s, rel=1e-6)


def test_reads_a_profiler_file(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    f = jax.jit(lambda x: (x * 2).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with TraceAnnotation("bench.window"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    t = tracing.TraceSummary(tracing.load_xplane(tracing.find_xplane(tmp_path)))
    assert t.window_s > 0
