"""Benchmark harness entry point — one function per paper table/claim.
Prints ``name,us_per_call,derived`` CSV rows (plus the detailed tables).

Usage: ``python benchmarks/run.py [bench ...]`` — any of the names below;
no argument runs everything.

  table1   -> Table I communication volumes (closed-form, vs paper)
  k_frac   -> §V-C: k ≈ 0.65 on Graph500 RMAT
  tc       -> §III/IV: compacted cover-edge pipeline vs the dense seed
              path vs wedge-iterator; also writes ``results/BENCH_tc.json``
              so the perf trajectory is tracked across PRs
  parallel -> Alg. 2 end-to-end on p = 8 simulated CPU devices (subprocess):
              allgather vs ring wall time, per-round estimate, planned
              bucket occupancy, wedge-baseline agreement; writes
              ``results/BENCH_parallel.json``
  serve    -> batched triangle-analytics serving vs the sequential
              one-graph-per-call loop on a mixed request stream:
              throughput vs batch size, p50/p99 latency, plan-cache and
              jit-cache behavior; writes ``results/BENCH_serve.json``
  robust   -> serving robustness acceptance: deadline-driven continuous
              batching vs fixed-B flush p99 on a bursty open-loop
              trace, approximate-lane error bound, and the chaos
              invariant under fault injection; writes
              ``results/BENCH_robust.json``.  ``robust_smoke`` is the
              CI variant (smaller trace; writes the untracked
              ``results/BENCH_robust_smoke.json`` so the tracked
              trajectory is never overwritten)
  pervertex-> per-vertex attribution overhead vs counts-only on the
              scale-12 fixture (must stay <= 15%); writes
              ``results/BENCH_pervertex.json``
  api      -> TriangleEngine facade overhead vs the direct pipeline on
              the scale-10 fixture (must stay < 5%); writes
              ``results/BENCH_api.json``
  comm     -> measured vs modeled communication per phase for
              p in {1, 2, 4, 8} on scale-10/12 RMAT (subprocess, 8 simulated
              CPU devices) + the k·m·p hedge-volume scaling curve; writes
              ``results/BENCH_comm.json``.  ``comm_smoke`` is the CI
              variant (scale 10, p = 4 only; writes the untracked
              ``results/BENCH_comm_smoke.json``)
  tune     -> trace-driven autotuner acceptance (DESIGN.md §11): record
              the serve-mix trace, successive-halving sweep of the plan
              space (bit-identical counts asserted per config), persist
              the winning TunedProfile to results/tuned/, and prove the
              pre-warm contract (plan_hit == 1.0, zero post-warm jit
              compiles) on a fresh engine; writes
              ``results/BENCH_autotune.json``.  ``tune_smoke`` is the CI
              variant (smaller trace + space; writes the untracked
              ``results/BENCH_autotune_smoke.json``)
  stream   -> streaming subsystem acceptance (DESIGN.md §13): ~20 mixed
              insert/delete batches of <= 1% of edges on scale-12 RMAT;
              the delta session must stay bit-identical to a full
              recount (totals AND per-vertex) after EVERY batch and
              answer updates >= 5x faster than recounting; writes
              ``results/BENCH_stream.json``.  ``stream_smoke`` is the
              CI variant (scale 8, 5 batches, bit-identity only —
              writes the untracked ``results/BENCH_stream_smoke.json``)
  audit    -> static program audit wall-time gate: the full
              ``repro.analysis.audit`` run (compile-set, int32 bounds,
              host-sync, collectives, dead code over every route) plus
              the baseline diff must finish within 60 s; writes
              ``results/BENCH_audit.json``
  roofline -> §Roofline terms from the dry-run artifacts (if present)
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)  # `python benchmarks/run.py` just works


def bench_table1():
    from benchmarks.comm_table import rows

    t0 = time.time()
    rs = rows()
    dt = (time.time() - t0) / len(rs) * 1e6
    worst = max(abs(1 - r["speedup_ratio"]) for r in rs)
    print(f"table1_comm,{dt:.1f},max_speedup_dev={worst:.3f}")
    exact = [r for r in rs if r["graph"].startswith("RMAT")]
    for r in exact:
        print(f"table1_{r['graph']},0,{r['ours']}|paper={r['ours_paper']}"
              f"|speedup={r['speedup']}vs{r['speedup_paper']}")


def bench_k_fraction():
    from benchmarks.k_fraction import measure

    rs = measure(scales=(10, 11, 12))
    for r in rs:
        print(f"k_fraction_scale{r['scale']},{r['seconds']*1e6:.0f},"
              f"k={r['k']:.3f}")


def bench_tc(scales=(10, 11, 12)):
    from benchmarks.tc_bench import measure

    rows = []
    for scale in scales:
        r = measure(scale)
        rows.append(r)
        print(f"tc_cover_scale{scale},{r['cover_s']*1e6:.0f},"
              f"T={r['triangles']}|rows={r['probe_rows']}"
              f"|speedup_vs_dense={r['speedup_vs_dense']:.2f}x")
        print(f"tc_dense_scale{scale},{r['cover_dense_s']*1e6:.0f},"
              f"rows={r['dense_rows']}")
        print(f"tc_wedge_scale{scale},{r['wedge_s']*1e6:.0f},"
              f"reduction={r['examination_reduction']:.2f}x")
    out = os.path.join(os.path.dirname(__file__), "..", "results",
                       "BENCH_tc.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(rows, f, indent=2)
    print(f"tc_json,0,written={os.path.normpath(out)}")


def bench_parallel():
    """Algorithm 2 on p = 8 simulated devices (subprocess, the device-count
    flag must precede the first jax import): wall time of both exchange
    modes, per-round estimate, planned-bucket occupancy of the horizontal
    rounds, and the wedge-baseline comparison.  Writes
    ``results/BENCH_parallel.json`` so the distributed perf trajectory is
    tracked across PRs alongside ``BENCH_tc.json``."""
    json_out = os.path.normpath(
        os.path.join(_ROOT, "results", "BENCH_parallel.json")
    )
    body = (
        "from benchmarks.tc_bench import measure_parallel\n"
        f"measure_parallel(scale=10, p=8, out={json_out!r})\n"
    )
    _run_in_8dev_subprocess(body, json_out, "parallel")


def _run_in_8dev_subprocess(body: str, json_out: str, tag: str) -> None:
    """Run ``body`` with 8 forced host devices (the flag must precede
    the first jax import, hence the subprocess) and report its output.
    A failing subprocess fails THIS process too — these benches gate CI
    (the comm smoke's measured==tally asserts), so an error must turn
    the step red, not print a CSV line and exit 0.

    The child is a CPU simulation by design: ``JAX_PLATFORMS=cpu`` keeps
    it off the accelerator (which this process may already hold), and
    its CSV lines are labelled ``platform=cpu``."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    src = os.path.join(_ROOT, "src")
    env["PYTHONPATH"] = (
        src + os.pathsep + _ROOT + os.pathsep + env.get("PYTHONPATH", "")
    )
    out = subprocess.run([sys.executable, "-c", body], env=env,
                         capture_output=True, text=True, timeout=900)
    if out.returncode:
        err = out.stderr.strip().splitlines()[-1][:200] if out.stderr else "?"
        print(f"{tag},0,ERROR:{err}")
        raise SystemExit(f"{tag} bench subprocess failed: {err}")
    for line in out.stdout.strip().splitlines():
        print(f"{line}|platform=cpu" if line.count(",") >= 2 else line)
    print(f"{tag}_json,0,written={json_out}|platform=cpu")


def bench_comm(smoke: bool = False):
    """Measured-vs-modeled communication accounting (DESIGN.md §5):
    the comm instrument's per-phase volumes against the analytic tally
    and the closed-form wire model, p in {1, 2, 4, 8}, plus the hedge
    scaling curve.  Writes ``results/BENCH_comm.json`` — except in smoke
    mode, which writes the untracked ``results/BENCH_comm_smoke.json``:
    the full sweep is the perf trajectory tracked across PRs and a CI
    subset must never overwrite it."""
    json_out = os.path.normpath(os.path.join(
        _ROOT, "results",
        "BENCH_comm_smoke.json" if smoke else "BENCH_comm.json",
    ))
    args = ("scales=(10,), ps=(4,)" if smoke
            else "scales=(10, 12), ps=(1, 2, 4, 8)")
    body = (
        "from benchmarks.comm_bench import measure_comm\n"
        f"measure_comm({args}, execute_scale=10, out={json_out!r})\n"
    )
    _run_in_8dev_subprocess(body, json_out, "comm")


def bench_serve():
    """Serving-layer trajectory: the batched pipeline (one fused jit per
    batch, cached bounded plans) vs the sequential per-graph loop on the
    same mixed request stream — the acceptance claim is graphs/sec at
    B >= 8 over the sequential baseline.  Writes
    ``results/BENCH_serve.json``."""
    from repro.launch.serve_tc import measure_serve

    out = os.path.join(_ROOT, "results", "BENCH_serve.json")
    measure_serve(num_requests=96, batch_sizes=(1, 2, 8, 16), out=out)


def bench_robust(smoke: bool = False):
    """Serving robustness acceptance (DESIGN.md §7): deadline-driven
    continuous batching vs fixed-B flush p99 on a bursty open-loop
    trace, approximate-lane relative error at the configured sample
    rate, and the chaos invariant (every request answered exactly once,
    structurally, under the full fault plan).  Writes
    ``results/BENCH_robust.json``; a violated claim exits nonzero.
    ``robust_smoke`` is the CI variant (smaller trace; writes the
    untracked ``results/BENCH_robust_smoke.json`` so the tracked
    trajectory is never overwritten)."""
    from benchmarks.robust_bench import measure_robust

    if smoke:
        out = os.path.join(_ROOT, "results", "BENCH_robust_smoke.json")
        measure_robust(num_requests=48, smoke=True, out=out)
    else:
        out = os.path.join(_ROOT, "results", "BENCH_robust.json")
        measure_robust(num_requests=96, out=out)


def bench_api():
    """Facade-overhead smoke: ``repro.api.TriangleEngine.count`` vs the
    direct pipeline on scale-10 RMAT — asserts the < 5% acceptance bound
    and writes ``results/BENCH_api.json``."""
    from benchmarks.api_bench import measure_api

    out = os.path.join(_ROOT, "results", "BENCH_api.json")
    measure_api(scale=10, out=out)


def bench_pervertex():
    """Per-vertex attribution overhead gate: scale-12 RMAT through the
    local route with ``TCOptions(per_vertex=True)`` vs counts-only —
    asserts the <= 15% acceptance bound and writes
    ``results/BENCH_pervertex.json``."""
    from benchmarks.pervertex_bench import measure_pervertex

    out = os.path.join(_ROOT, "results", "BENCH_pervertex.json")
    measure_pervertex(scale=12, out=out)


def bench_tune(smoke: bool = False):
    """Autotuner acceptance (DESIGN.md §11): serve-mix trace -> sweep
    (bit-identity asserted per config) -> persisted TunedProfile ->
    pre-warm contract on a fresh engine.  A violated claim exits
    nonzero.  Writes ``results/BENCH_autotune.json``; ``tune_smoke``
    writes the untracked ``results/BENCH_autotune_smoke.json`` so the
    tracked trajectory is never overwritten."""
    from benchmarks.tune_bench import measure_tune

    if smoke:
        out = os.path.join(_ROOT, "results", "BENCH_autotune_smoke.json")
        measure_tune(num_requests=32, smoke=True, out=out)
    else:
        out = os.path.join(_ROOT, "results", "BENCH_autotune.json")
        measure_tune(num_requests=96, out=out)


def bench_stream(smoke: bool = False):
    """Streaming acceptance (DESIGN.md §13): bit-identical totals and
    per-vertex credit vs a full recount after every mutation batch, and
    the >= 5x updates/sec bound at <= 1% edges mutated per batch on
    scale-12 RMAT.  A violated claim exits nonzero.  Writes
    ``results/BENCH_stream.json``; ``stream_smoke`` is the CI variant
    (scale 8, correctness only, untracked
    ``results/BENCH_stream_smoke.json``)."""
    from benchmarks.stream_bench import measure_stream

    if smoke:
        out = os.path.join(_ROOT, "results", "BENCH_stream_smoke.json")
        measure_stream(scale=8, batches=5, smoke=True, out=out)
    else:
        out = os.path.join(_ROOT, "results", "BENCH_stream.json")
        measure_stream(scale=12, batches=20, out=out)


def bench_roofline():
    from benchmarks.roofline import RESULTS, analyze

    for mesh in ("pod", "multipod"):
        for variant, label in (("_baseline", "base"), ("_opt", "opt")):
            path = RESULTS / f"dryrun_{mesh}{variant}.json"
            if not path.exists():
                continue
            ok = [r for r in analyze(mesh, variant=variant)
                  if r["status"] == "ok"]
            for r in ok:
                print(
                    f"roofline_{mesh}_{label}_{r['cell'].replace('|','_x_')},"
                    f"0,dom={r['dominant']}|frac={r['roofline_frac']:.2f}"
                    f"|peakGB={r['peak_gb']:.1f}")


def bench_audit():
    from benchmarks.audit_bench import measure

    res = measure()
    path = os.path.join(_ROOT, "results", "BENCH_audit.json")
    with open(path, "w") as fh:
        json.dump(res, fh, indent=2, sort_keys=True)
        fh.write("\n")
    print(f"audit,{res['wall_s'] * 1e6:.0f},findings={res['findings']}"
          f"|baseline_checked={res['baseline_checked']}"
          f"|within_budget={res['within_budget']}|platform=cpu")
    assert res["within_budget"], (
        f"static audit took {res['wall_s']}s > {res['wall_budget_s']}s "
        f"budget — it must stay cheap enough to gate every PR"
    )


BENCHES = {
    "table1": bench_table1,
    "k_frac": bench_k_fraction,
    "tc": bench_tc,
    "parallel": bench_parallel,
    "serve": bench_serve,
    "robust": bench_robust,
    "robust_smoke": lambda: bench_robust(smoke=True),
    "api": bench_api,
    "pervertex": bench_pervertex,
    "comm": bench_comm,
    "comm_smoke": lambda: bench_comm(smoke=True),
    "tune": bench_tune,
    "tune_smoke": lambda: bench_tune(smoke=True),
    "stream": bench_stream,
    "stream_smoke": lambda: bench_stream(smoke=True),
    "audit": bench_audit,
    "roofline": bench_roofline,
}


def main(argv: list[str] | None = None) -> None:
    argv = sys.argv[1:] if argv is None else argv
    unknown = [a for a in argv if a not in BENCHES]
    if unknown:
        sys.exit(f"unknown bench(es) {unknown}; choose from {list(BENCHES)}")
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    print("name,us_per_call,derived")
    # run-everything excludes the smoke lanes: they are strict CI
    # subsets of comm/robust (and write separate *_smoke.json files)
    default = [n for n in BENCHES if not n.endswith("_smoke")]
    for name in argv or default:
        BENCHES[name]()


if __name__ == "__main__":
    main()
