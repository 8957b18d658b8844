"""The reader ``hedge_entries_g.p4`` gives the program's
``dist.entries_planned`` counter in 10⁹ entries per chip per count, and
``None``, without raising, on a program that keeps no such counter."""
from __future__ import annotations

import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import run  # noqa: E402


def reader():
    return run.load_module(ROOT / "bench" / "metrics" / "hedge_entries_g.p4.py")


@pytest.fixture
def counters(monkeypatch):
    from repro import obs

    monkeypatch.setattr(obs, "_COUNTERS", type(obs._COUNTERS)())
    return obs


@pytest.mark.parametrize("incr", [{}, {"dist.counts": 2},
                                  {"dist.entries_planned": 3.5e9}])
def test_none_without_the_counter(counters, incr):
    for name, value in incr.items():
        counters.incr(name, value)
    assert reader().read(SimpleNamespace(counters={})) is None


@pytest.mark.parametrize("counts,entries,want", [(2, 3.5e9, 1.75),
                                                 (1, 1757937664, 1.757937664)])
def test_reads_entries_per_chip_per_count(counters, counts, entries, want):
    counters.incr("dist.counts", counts)
    counters.incr("dist.entries_planned", entries)
    assert reader().read(SimpleNamespace(counters={})) == pytest.approx(want)
