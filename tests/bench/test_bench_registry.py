"""Every cell of ``BENCHMARK.json`` resolves by name to files of its
own, the file keeps to the benchmark's contract, and a new cell, mix or
metric needs new files only."""
from __future__ import annotations

import json
import re
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import run  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_resolves(cell):
    c = run.resolve(BENCH, cell)
    for fn in ("setup", "measure", "release", "check"):
        assert callable(getattr(c.driver, fn))
    assert {m["name"] for m in c.end_to_end} >= {"setup_s"}
    assert len(c.end_to_end) >= 2 and c.per_layer
    assert all(callable(r.read) for r in c.readers.values())
    for key in ("source", "reduced", "assumed", "guarantee", "control"):
        assert key in c.config


def test_contract_shape():
    assert list(BENCH) == ["command", "paths", "run_seconds", "configs",
                           "workloads", "end_to_end", "per_layer"]
    assert 1 <= BENCH["run_seconds"] <= 51
    names = set()
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert set(c["reduced"]) == set(cfg["reduced"])
        assert all(k in cfg for k in c["reduced"])
        names.add(c["name"])
    cells = set()
    for w in BENCH["workloads"]:
        assert w["config"] in names and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200 and NAME.match(w["name"])
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").exists()
        cells.add(w["name"])
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and UNIT.match(m["unit"])
        assert m["source"] in ("host_clock", "device_trace")
        assert set(m.get("workloads", cells)) <= cells
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and UNIT.match(m["unit"])
        assert set(m["workloads"]) <= cells and NAME.match(m["name"])
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").exists()


def test_new_cell_needs_new_files_only(tmp_path):
    """A later change adds a mix, a reader and a cell by adding files
    and entries; nothing that exists is edited."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    bench = json.loads(json.dumps(BENCH))
    (tmp_path / "bench" / "traffic" / "once.json").write_text(
        json.dumps({"driver": "count", "route": "local"}))
    (tmp_path / "bench" / "metrics" / "answer.count.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    bench["workloads"].append({"name": "kron15.once", "config": "gap-kron-s15",
                               "traffic": "once", "chips": 1, "why": "x"})
    bench["end_to_end"][0]["workloads"].append("kron15.once")
    bench["per_layer"].append({"name": "answer.count", "unit": "s",
                               "better": "lower", "source": "host_clock",
                               "layer": "device", "moves": "count_s",
                               "workloads": ["kron15.once"]})
    c = run.resolve(bench, "kron15.once", root=tmp_path)
    assert c.traffic["route"] == "local" and list(c.readers) == ["answer.count"]
    assert c.readers["answer.count"].read(None) == 42.0


def test_unknown_device_kind_has_no_peaks():
    assert run.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        run.peaks_for("cpu")
