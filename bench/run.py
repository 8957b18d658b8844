#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python bench/run.py --workload kron15.count --seed 7 --seconds 10 \\
        --trace 0

Everything is found by name from ``BENCHMARK.json``: the cell names its
configuration (``bench/configs/<config>.json``) and its traffic mix
(``bench/traffic/<traffic>.json``); the traffic names its driver
(``bench/drivers/<driver>.py``: ``setup``, ``measure``, ``release``,
``check``); each per-layer metric is read by ``bench/metrics/<name>.py``
(``read(ctx)``, ``None`` where it finds nothing to read).

A run: set-up (graphs from the seed, engine, warm-up, which compiles or
loads every program from the compile cache), the measured window of
``--seconds`` (under the profiler with ``--trace 1``), the device's
peak memory, the program's state freed, then every answer compared with
the plain reference.  The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer ones),
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``: each
number compared, beside its limit.  The checks also end standard error.

With no TPU, or fewer chips than the cell asks for, the run exits 2 and
prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
# libtpu writes its logs under /tmp unless told otherwise; a run writes
# only inside its checkout and its own HOME and TMPDIR
os.environ.setdefault("TPU_LOG_DIR", "disabled")
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

#: JAX's persistent compilation cache, at a fixed path in the checkout
CACHE_DIR = ROOT / ".jax_cache"
#: where a traced run's profile goes unless ``--trace-dir`` names one
TRACE_DIR = ROOT / ".bench_trace"

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: backend compiles since the listener was registered (once a process)
_compiles: list[int] = []


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def load_json(path) -> dict:
    return json.loads(Path(path).read_text())


def load_module(path: Path):
    """Import one file of the benchmark by its path (metric names hold
    dots, so they are files and not importable module names)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(bench: dict, workload: str, root: Path = ROOT) -> SimpleNamespace:
    """The cell ``workload`` with its configuration, traffic, driver,
    end-to-end metrics and per-layer readers, each found by name under
    ``root``."""
    here = root / "bench"
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    traffic = load_json(here / "traffic" / f"{cell['traffic']}.json")
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (workload in m["workloads"] if "workloads" in m
                 else m["moves"] in names)]
    return SimpleNamespace(
        name=workload, chips=cell["chips"],
        config=load_json(root / cfg_entry["file"]), traffic=traffic,
        driver=load_module(here / "drivers" / f"{traffic['driver']}.py"),
        end_to_end=e2e, per_layer=layer,
        readers={m["name"]: load_module(here / "metrics" / f"{m['name']}.py")
                 for m in layer},
    )


def chips(n: int) -> list:
    """The first ``n`` TPU devices; :class:`NoChip` where there are
    fewer, or JAX found another platform."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found {devices[0].platform}, not a TPU")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chips; JAX found {len(devices)}")
    return devices[:n]


def enable_compile_cache() -> str:
    """The persistent cache: ``JAX_COMPILATION_CACHE_DIR`` where set,
    else ``<checkout>/.jax_cache``; every program is written to it."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def _count_compiles(event, duration, **_):
    if event == COMPILE_EVENT:
        _compiles[0] += 1


def compiles() -> int:
    """Backend compiles in this process since the first call."""
    if not _compiles:
        import jax

        _compiles.append(0)
        jax.monitoring.register_event_duration_secs_listener(_count_compiles)
    return _compiles[0]


def _peak_bytes(devices) -> int:
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)


def run_cell(cell, seed: int, seconds: float, trace: bool, devices, *,
             variant=None, trace_dir=None, t_start=None) -> dict:
    """One run of ``cell`` on ``devices``; returns the result object.
    ``variant`` overrides the driver's options (the control)."""
    import jax
    from jax.profiler import ProfileOptions

    from bench import tracing

    compiles()
    drv = cell.driver
    st = drv.setup(cell.config, cell.traffic, seed, variant=variant)
    setup_s = time.perf_counter() - (T_START if t_start is None else t_start)
    keep = trace_dir is not None
    tdir = Path(trace_dir) if keep else TRACE_DIR / cell.name
    if trace:
        shutil.rmtree(tdir, ignore_errors=True)
        opts = ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(tdir), profiler_options=opts)
    before = compiles()
    win = drv.measure(st, seconds)
    in_window = compiles() - before
    if trace:
        jax.profiler.stop_trace()
    peak = _peak_bytes(devices)
    drv.release(st)
    summary = None
    if trace:
        summary = tracing.TraceSummary(
            tracing.load_xplane(tracing.find_xplane(tdir)))
        if not keep:
            shutil.rmtree(tdir, ignore_errors=True)
    attempted, failed, checks = drv.check(st)
    counters = dict(win["counters"], compiles_in_window=in_window)
    info(f"window {win['window_s']} s; counters {json.dumps(counters)}")
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    out = {"correct": all(v <= lim for v, lim in checks.values())
           and attempted > 0,
           "attempted": attempted, "failed": failed}
    if trace:
        ctx = SimpleNamespace(trace=summary, counters=counters,
                              memory_peak_bytes=peak,
                              peaks=peaks_for(device["kind"]))
        metrics = {}
        for m in cell.per_layer:
            v = cell.readers[m["name"]].read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        out.update(metrics=metrics, device=device, breakdown={
            "device_ops": summary.top_ops(10),
            "idle_gaps": summary.idle_by_host(10)})
    else:
        # a metric split per cell (``count_s.urand``) is the driver's
        # value under the name before the dot
        got = dict(win["end_to_end"], setup_s=setup_s)
        out.update(device=device, metrics={
            m["name"]: {"value": got[m["name"].split(".")[0]],
                        "unit": m["unit"]}
            for m in cell.end_to_end})
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    return out


def peaks_for(kind: str) -> dict:
    """The published peaks of ``kind`` from ``bench/peaks.json``; an
    unknown kind is an error."""
    table = load_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def info(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def emit(out: dict) -> None:
    for k, c in out["checks"].items():
        print(f"check {k} {c['value']} limit {c['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profile here (default: a directory in "
                         "the checkout, removed once read)")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    try:
        cell = resolve(load_json(ROOT / "BENCHMARK.json"), args.workload)
        devices = chips(cell.chips)
    except (NoChip, OSError, KeyError) as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 2
    info(f"compile cache {enable_compile_cache()}")
    emit(run_cell(cell, args.seed, args.seconds, bool(args.trace), devices,
                  trace_dir=args.trace_dir))
    return 0


if __name__ == "__main__":
    sys.exit(main())
