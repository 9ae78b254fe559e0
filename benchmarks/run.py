"""Benchmark driver — one table per paper figure. Prints CSV rows.

Suites:
  micro      figs 4-10 (microbenchmark characterization, model vs measured)
  prim       figs 12-15 (PrIM strong/weak scaling with phase breakdown)
  throughput runtime serialized-vs-pipelined table (full registry)
  compare    figs 16-17 (CPU measured vs PIM/TPU modeled)
  roofline   S-Roofline table from dry-run records (if present)

Workload coverage everywhere comes from ``repro.prim.registry`` (the prim /
throughput suites iterate it; the compare suite's per-workload model
constants are keyed and validated against its variant labels) — no suite
carries a hand-maintained workload list.  For the machine-readable
schema-versioned artifact CI gates on, use ``tools/bench.py`` instead
(EXPERIMENTS.md §Bench-artifacts) — it wraps these same suites.

``--banks N`` re-execs under N forced host devices so the scaling tables
sweep a real bank axis (kept out of the default path: benches see the true
device count unless explicitly asked).
"""
from __future__ import annotations

import argparse
import csv
import io
import os
import subprocess
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "src"))

from repro.launch.cli import cpu_rehearsal_env, enable_compile_cache  # noqa: E402


def emit(rows) -> None:
    if not rows:
        return
    by_table: dict = {}
    for r in rows:
        by_table.setdefault(r.get("table", "misc"), []).append(r)
    for table, trs in by_table.items():
        keys = list(trs[0].keys())
        buf = io.StringIO()
        w = csv.DictWriter(buf, fieldnames=keys, extrasaction="ignore")
        w.writeheader()
        for r in trs:
            w.writerow(r)
        print(f"# --- {table} ---")
        print(buf.getvalue().rstrip())
        print()


def suite_micro(fast: bool = True):
    from benchmarks import microbench as mb
    rows = []
    for fig in mb.ALL:           # every registered figure, no hand list
        kw = {"fast": fast} if fig is mb.fig4_arith_throughput else {}
        rows += fig(**kw)
    return rows


def suite_prim():
    from benchmarks import prim_scaling as ps
    import jax
    counts = sorted({1, min(2, jax.device_count()), jax.device_count()})
    rows = []
    rows += ps.tasklet_scaling()
    rows += ps.strong_scaling(bank_counts=counts)
    rows += ps.weak_scaling(bank_counts=counts)
    return rows


def suite_throughput():
    from benchmarks.throughput import throughput
    return throughput()


def suite_compare():
    from benchmarks import system_compare as sc
    return sc.compare() + sc.energy()


def suite_roofline():
    from benchmarks import roofline as rl
    recs = rl.load_records()
    return rl.rows(recs) if recs else []


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--suite", default="all",
                    choices=["all", "micro", "prim", "throughput", "compare",
                             "roofline"])
    ap.add_argument("--banks", type=int, default=0,
                    help="re-exec with N forced host devices")
    ap.add_argument("--full", action="store_true",
                    help="full tasklet sweep in fig4")
    args = ap.parse_args()

    if args.banks:
        env = cpu_rehearsal_env(args.banks)
        cmd = [sys.executable, "-m", "benchmarks.run", "--suite", args.suite]
        if args.full:
            cmd.append("--full")
        raise SystemExit(subprocess.call(cmd, env=env))

    rows = []
    if args.suite in ("all", "micro"):
        rows += suite_micro(fast=not args.full)
    if args.suite in ("all", "prim"):
        rows += suite_prim()
    if args.suite == "throughput":     # not in "all": minutes-long on 1 bank
        rows += suite_throughput()
    if args.suite in ("all", "compare"):
        rows += suite_compare()
    if args.suite in ("all", "roofline"):
        rows += suite_roofline()
    emit(rows)


if __name__ == "__main__":
    enable_compile_cache()
    main()
