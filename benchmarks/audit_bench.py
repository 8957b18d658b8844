"""Static-audit wall-time gate.

Runs the full ``repro.analysis.audit`` CLI — all five passes over
every route × backend × per_vertex × device count, plus the baseline
check — in a subprocess (the CLI must own jax initialization: it
forces 8 host devices via ``XLA_FLAGS`` before the backend starts) and
gates the wall time.  The audit is a per-PR CI job; if it creeps past
the budget it stops being something people run before pushing, so the
budget is enforced here exactly like a perf claim.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASELINE = os.path.join(ROOT, "results", "AUDIT_baseline.json")

#: the audit must stay this fast, end to end, baseline diff included.
WALL_BUDGET_S = 60.0


def measure(*, check: bool = True) -> dict:
    """One timed full-audit run.  ``check=True`` also diffs against the
    tracked baseline (the exact CI invocation)."""
    out_path = os.path.join(ROOT, "results", "AUDIT_report.json")
    cmd = [sys.executable, "-m", "repro.analysis.audit", "--out", out_path]
    if check and os.path.exists(BASELINE):
        cmd += ["--check", BASELINE]
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"  # a CPU analysis; never reach for a chip
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(ROOT, "src"), env.get("PYTHONPATH", "")]
    ).rstrip(os.pathsep)
    t0 = time.time()
    proc = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                          text=True)
    wall = time.time() - t0
    if proc.returncode != 0:
        raise SystemExit(
            f"audit failed (exit {proc.returncode}):\n{proc.stdout}\n"
            f"{proc.stderr}"
        )
    with open(out_path) as fh:
        report = json.load(fh)
    return {
        "platform": "cpu",
        "wall_s": round(wall, 3),
        "wall_budget_s": WALL_BUDGET_S,
        "within_budget": wall <= WALL_BUDGET_S,
        "baseline_checked": check and os.path.exists(BASELINE),
        "findings": len(report["findings"]),
        "counts": report["counts"],
        "passes": sorted({f["pass"] for f in report["findings"]}),
        "predicted_jit_compiles":
            report["meta"].get("predicted_jit_compiles"),
    }
