"""What decides ``correct`` fails what it should.

Each cell runs here on the CPU at a size a test run holds, through the
harness's whole run except its look for a chip: the program comes out
correct; the configuration's control (the program's approximate path)
does not; nor does a run whose timed path is broken underneath, once
for each fault the cell can have (an answer altered where it is
produced; for serving, half of a batch's answers left out)."""
from __future__ import annotations

import dataclasses
import json
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import run  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
# the serving cell's files are kept under bench/ while the cell waits
# for its proof on the chip (PERF.md, Open questions)
SERVE = {"name": "collab.serve", "config": "collab-egonets",
         "traffic": "poisson-knee", "chips": 1, "why": "-"}
if SERVE["name"] not in {w["name"] for w in BENCH["workloads"]}:
    BENCH["workloads"].append(SERVE)
    BENCH["configs"].append({"name": "collab-egonets",
                             "file": "bench/configs/collab-egonets.json"})
    BENCH["end_to_end"].append({"name": "serve_p95_ms", "unit": "ms",
                                "workloads": ["collab.serve"]})
SMALL = {
    "count": {"scale": 9},
    "serve": {"count": 12, "lo": 10, "hi": 40, "mu": 2.5, "sigma": 0.5,
              "group": 10},
}
SEED = 2**31 + 17


def cell(name):
    c = run.resolve(BENCH, name)
    c.config.update(SMALL[c.traffic["driver"]])
    if c.traffic["driver"] == "serve":
        c.traffic.update(rate_per_s=40.0, latency_limit_ms=100.0, grace_s=1.0)
    return c


def once(name, variant=None):
    out = run.run_cell(cell(name), SEED, 0.5, False, jax.devices()[:1],
                       variant=variant)
    assert out["attempted"] > 0
    return out


CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_program_is_correct(name):
    out = once(name)
    assert out["correct"] and out["failed"] == 0
    assert set(out["metrics"]) == {m["name"] for m in cell(name).end_to_end}
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    c = cell(name)
    out = once(name, variant=c.config["control"])
    assert not out["correct"] and out["failed"] > 0


@pytest.mark.parametrize("name", ["kron15.count", "urand15.count"])
def test_altered_count_is_not_correct(name, monkeypatch):
    from repro.api import TriangleEngine

    real = TriangleEngine.count

    def off_by_one(self, *a, **kw):
        rep = real(self, *a, **kw)
        return dataclasses.replace(rep, triangles=rep.triangles + 1)

    monkeypatch.setattr(TriangleEngine, "count", off_by_one)
    out = once(name)
    assert not out["correct"] and out["checks"]["count_error"]["value"] == 1


@pytest.mark.parametrize("fault", ["altered", "half_dropped"])
def test_broken_serving_is_not_correct(fault, monkeypatch):
    from repro.launch.serve_tc import TriangleServer

    real = TriangleServer._finalize_one

    def broken(self):
        start = len(self.results)
        real(self)
        new = self.results[start:]
        if fault == "altered":
            for r in new:
                r.triangles += 1
        else:
            del self.results[start + len(new) // 2 + len(new) % 2:]

    monkeypatch.setattr(TriangleServer, "_finalize_one", broken)
    out = once("collab.serve")
    assert not out["correct"] and out["failed"] > 0
