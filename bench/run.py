"""Run one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads the cell named in ``BENCHMARK.json``, builds its deployment from the
seed, warms up the shapes its traffic uses (all of that is ``setup_s``,
counted from process start), measures for ``--seconds``, checks what the
window produced against the plain reference, and prints one JSON line as the
last line of standard output.  ``--trace 1`` profiles the window and reports
the cell's per-layer metrics instead of its end-to-end ones.

Refuses (exit 2, no result) any platform but a TPU, fewer chips than the
cell asks for, and a ``device_kind`` that ``bench/peaks.json`` lacks.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = harness.resolve(harness.load_benchmark(), args.workload)
    cache_dir = harness.enable_compile_cache()
    try:
        devices = harness.require_devices(cell.chips)
        peaks = harness.load_peaks(devices[0].device_kind)
    except harness.Refused as e:
        print(f"bench: refused: {e}", file=sys.stderr)
        return 2
    watch = harness.CompileWatch()
    ctx = harness.Context(cell=cell, seed=args.seed, seconds=args.seconds,
                          trace=bool(args.trace), t_process=T_PROCESS,
                          devices=devices, peaks=peaks, watch=watch)
    ctx.log(f"bench: {cell.name} on {devices[0].device_kind} x{len(devices)}"
            f", seed {args.seed}, {args.seconds} s, trace {args.trace}, "
            f"compile cache {cache_dir}")
    run, line = harness.run_cell(ctx)
    print(f"compiles in window: {run.compiles_in_window}; compile cache "
          f"hits {watch.hits} misses {watch.misses}; setup_s "
          f"{run.setup_s}", flush=True)
    for c in run.checks:
        print(f"check {c.name} {c.value!r} limit {c.limit!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
