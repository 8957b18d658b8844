import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
).strip()

"""Multi-pod dry-run: lower + compile EVERY (architecture x input shape)
cell on the production meshes and dump the roofline inputs.

  python -m repro.launch.dryrun --mesh pod            # (16,16) = 256 chips
  python -m repro.launch.dryrun --mesh multipod       # (2,16,16) = 512
  python -m repro.launch.dryrun --arch gemma3-1b --shape long_500k
  python -m repro.launch.dryrun --list

Per cell this records: memory_analysis (bytes/device), cost_analysis
(FLOPs, bytes accessed), and the collective-bytes breakdown parsed from the
compiled HLO — everything §Roofline consumes — into
``results/dryrun_<mesh>.json``.
"""  # noqa: E402

import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

import jax  # noqa: E402

RESULTS = Path(__file__).resolve().parents[3] / "results"

from repro.launch.hlo_analysis import collective_bytes  # noqa: E402


def run_cell(arch: str, shape: str, mesh, *, smoke: bool = False,
             overrides: dict | None = None) -> dict:
    from repro.configs.registry import build_cell

    t0 = time.time()
    cell = build_cell(arch, shape, mesh, smoke=smoke, overrides=overrides)
    if cell.skipped:
        return {"arch": arch, "shape": shape, "status": "skipped",
                "reason": cell.skip_reason, "model_flops": 0.0}
    with jax.set_mesh(cell.mesh if cell.mesh is not None else mesh):
        jitted = jax.jit(
            cell.fn, in_shardings=cell.in_shardings,
            out_shardings=cell.out_shardings,
        )
        lowered = jitted.lower(*cell.args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    coll = collective_bytes(compiled.as_text())
    rec = {
        "arch": arch,
        "shape": shape,
        "kind": cell.kind,
        "status": "ok",
        "model_flops": cell.model_flops,
        "hlo_flops": float(cost.get("flops", 0.0)),
        "hlo_bytes": float(cost.get("bytes accessed", 0.0)),
        "collective_bytes": coll,
        "argument_bytes": mem.argument_size_in_bytes,
        "output_bytes": mem.output_size_in_bytes,
        "temp_bytes": mem.temp_size_in_bytes,
        "peak_bytes": (
            mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes
        ),
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
    }
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", choices=["pod", "multipod"], default="pod")
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--include-tc", action="store_true",
                    help="also run the paper's TC workload cell")
    ap.add_argument("--set", default=None, dest="overrides",
                    help="config overrides k=v[,k=v...] (§Perf variants); "
                         "ints/floats/bools parsed, e.g. "
                         "--set attn_impl=chunked,act_dtype=bfloat16")
    ap.add_argument("--tag", default=None,
                    help="result key suffix for variant runs")
    ap.add_argument("--opt", action="store_true",
                    help="apply the per-arch §Perf-winning knobs "
                         "(registry.opt_overrides); writes *_opt.json")
    args = ap.parse_args()

    overrides = None
    if args.overrides:
        overrides = {}
        for kv in args.overrides.split(","):
            k, v = kv.split("=", 1)
            if v in ("true", "True", "false", "False"):
                v = v in ("true", "True")
            else:
                try:
                    v = int(v)
                except ValueError:
                    try:
                        v = float(v)
                    except ValueError:
                        pass
            overrides[k] = v

    from repro.configs.registry import all_cells
    from repro.launch.mesh import make_production_mesh

    cells = all_cells()
    if args.include_tc:
        cells.append(("cover-edge-tc", "rmat_pod"))
    if args.arch:
        cells = [(a, s) for a, s in cells if a == args.arch]
    if args.shape:
        cells = [(a, s) for a, s in cells if s == args.shape]
    if args.list:
        for a, s in cells:
            print(f"{a} x {s}")
        return

    mesh = make_production_mesh(multi_pod=args.mesh == "multipod")
    print(f"mesh: {dict(mesh.shape)} = {mesh.devices.size} devices")
    RESULTS.mkdir(exist_ok=True)
    suffix = "_opt" if args.opt else ""
    out_path = RESULTS / f"dryrun_{args.mesh}{suffix}.json"
    results = {}
    if out_path.exists():
        results = json.loads(out_path.read_text())
    failures = 0
    for arch, shape in cells:
        key = f"{arch}|{shape}" + (f"|{args.tag}" if args.tag else "")
        try:
            cell_over = overrides
            if args.opt:
                from repro.configs.registry import opt_overrides

                cell_over = {**opt_overrides(arch), **(overrides or {})}
            rec = run_cell(arch, shape, mesh, smoke=args.smoke,
                           overrides=cell_over)
            if args.tag:
                rec["variant"] = args.tag
                rec["overrides"] = overrides
            status = rec["status"]
            extra = (
                f" flops={rec['hlo_flops']:.3g} peakB={rec['peak_bytes']:.3g}"
                f" coll={sum(v for k, v in rec['collective_bytes'].items() if k != 'count'):.3g}"
                if status == "ok" else f" ({rec.get('reason', '')})"
            )
            print(f"[{status:>7}] {arch} x {shape}"
                  f" lower={rec.get('lower_s', 0)}s"
                  f" compile={rec.get('compile_s', 0)}s{extra}", flush=True)
        except Exception as e:  # noqa: BLE001
            failures += 1
            rec = {"arch": arch, "shape": shape, "status": "error",
                   "error": f"{type(e).__name__}: {e}"}
            print(f"[  ERROR] {arch} x {shape}: {e}", flush=True)
            traceback.print_exc()
        results[key] = rec
        out_path.write_text(json.dumps(results, indent=1))
    print(f"\n{len(cells) - failures}/{len(cells)} cells OK -> {out_path}")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
