"""Read each cell's control at the cell's own size and load, on the chip:
the plain reference one precision lower, put in the program's place
(``controls.py``), drives a short window through the harness on several
seeds, and its result line must come out ``correct: false``.  The limits in
``bench/configs/*.json`` sit between these readings (the upper ones) and
the program's own readings over a dozen seeds or more (the lower ones,
printed by every run of ``bench/run.py``).

    python3 tests/bench/chip_controls.py --workload prim-resident-gemv --seeds 1,2,3 --seconds 3

One JSON line per seed: the result line's ``correct`` and ``checks``.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [os.path.join(ROOT, "bench"), os.path.join(ROOT, "src"), HERE]

import harness  # noqa: E402
from controls import control_in_place  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args()
    harness.enable_compile_cache()
    cell = harness.resolve(harness.load_benchmark(), args.workload)
    devices = harness.require_devices(cell.chips)
    peaks = harness.load_peaks(devices[0].device_kind)
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        ctx = harness.Context(cell=cell, seed=seed, seconds=args.seconds,
                              trace=False, t_process=t, devices=devices,
                              peaks=peaks)
        with control_in_place(cell):
            run, line = harness.run_cell(ctx)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "control": True, "correct": line["correct"],
                          "attempted": line["attempted"],
                          "failed": line["failed"], "checks": line["checks"],
                          "seconds": time.perf_counter() - t}), flush=True)


if __name__ == "__main__":
    main()
