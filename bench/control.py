#!/usr/bin/env python3
"""Run a cell with its configuration's control switched on, and print
the numbers that decide ``correct`` for each seed.

    python bench/control.py --workload kron15.count --seeds 1,2,3 \\
        --seconds 5

The control is the program's own lower-fidelity path, named under
``"control"`` in the configuration: the approximate (wedge-sampled)
route for an exact count, an admission limit that sends requests to the
approximate lane for serving.  It has to come out not correct.  The
benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from run import ROOT, chips, emit, enable_compile_cache, load_json, resolve, \
    run_cell


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one run each")
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    cell = resolve(load_json(ROOT / "BENCHMARK.json"), args.workload)
    devices = chips(cell.chips)
    enable_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        out = run_cell(cell, seed, args.seconds, False, devices,
                       variant=cell.config["control"],
                       t_start=time.perf_counter())
        print(json.dumps({"seed": seed, "control": True}), file=sys.stderr)
        emit(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
