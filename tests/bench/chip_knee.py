"""Sweep a closed-loop cell's client count on the chip, once, to find the
knee its traffic file's ``clients`` is taken from: the fewest clients at
which the completed rate comes within a tenth of the sweep's highest.  One
set-up, then a short window per count; the answers are not checked here
(``bench/run.py`` checks them).

    python3 tests/bench/chip_knee.py --workload prim-resident-gemv --clients 1,2,4,8,16 --seconds 8

One JSON line per count: requests/s completed, and latency p50 and p95.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [os.path.join(ROOT, "bench"), os.path.join(ROOT, "src")]

import harness  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--clients", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=4294967311)
    args = ap.parse_args()
    counts = [int(c) for c in args.clients.split(",")]
    harness.enable_compile_cache()
    cell = harness.resolve(harness.load_benchmark(), args.workload)
    devices = harness.require_devices(cell.chips)
    ctx = harness.Context(cell=cell, seed=args.seed, seconds=args.seconds,
                          trace=False, t_process=time.perf_counter(),
                          devices=devices,
                          peaks=harness.load_peaks(devices[0].device_kind))
    drv = harness.load_driver(cell.traffic["driver"])
    loop = drv._Loop(ctx)
    loop.drive(max(counts), float("inf"),
               rounds=int(cell.traffic.get("warm_rounds", 2)))
    for n in counts:
        t0 = time.perf_counter()
        cs = loop.drive(n, t0 + args.seconds)
        done = [d for c in cs for d in c.done if d[3]]
        lat = [d[2] - d[1] for d in done]
        ok = sum(1 for d in done if d[2] <= t0 + args.seconds)
        print(json.dumps({
            "workload": cell.name, "clients": n,
            "requests_per_s": ok / args.seconds, "requests": len(done),
            "p50_ms": 1e3 * harness.exact_percentile(lat, 50),
            "p95_ms": 1e3 * harness.exact_percentile(lat, 95),
            "errors": loop.errors[:2]}), flush=True)
    loop.session.close()


if __name__ == "__main__":
    main()
