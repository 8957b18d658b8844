#!/usr/bin/env python3
"""Sweep the offered rate of a serving cell once, to find the highest
rate the server sustains under the traffic's latency limit.

    python bench/knee.py --workload collab.serve --seed 5 --seconds 20 \\
        --rates 25,50,100,200

One set-up (the pool warmed), then one window per rate, each with the
same arrival pattern scaled to that rate.  Each prints a JSON line:
the offered rate, the requests, the p95 latency beside the limit, how
long the last answers took after the arrivals stopped (a backlog that
grew), lanes per flush and compiles in the window.  The traffic file
then takes about 0.8 x the highest rate whose p95 met the limit with no
growing backlog.
"""
from __future__ import annotations

import argparse
import json
import sys
import time


from run import ROOT, chips, enable_compile_cache, load_json, resolve


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    cell = resolve(load_json(ROOT / "BENCHMARK.json"), args.workload)
    devices = chips(cell.chips)
    enable_compile_cache()
    drv = cell.driver
    t0 = time.perf_counter()
    st = drv.setup(cell.config, cell.traffic, args.seed)
    print(json.dumps({"setup_s": time.perf_counter() - t0,
                      "device": devices[0].device_kind}), flush=True)
    base = st["gaps"] * st["rate"]
    for rate in (float(r) for r in args.rates.split(",")):
        st["rate"], st["gaps"] = rate, base / rate
        win = drv.measure(st, args.seconds)
        print(json.dumps(dict(
            rate_per_s=rate, window_s=win["window_s"],
            limit_ms=cell.traffic["latency_limit_ms"],
            **win["end_to_end"], **win["counters"])), flush=True)
    drv.release(st)
    return 0


if __name__ == "__main__":
    sys.exit(main())
