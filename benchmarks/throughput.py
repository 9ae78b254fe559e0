"""Runtime throughput: serialized ``pim()`` baseline vs the pipelined
scheduler (requests/sec and overlap speedup), for the FULL registry.

The serialized column reproduces the paper's execution model — every request
runs scatter | compute | retrieve with hard syncs, one after another.  The
pipelined column submits the same requests to a `repro.pim` session, which
chunks, double-buffers, and batches them (``runtime/pipeline.py``).  The
ratio is the transfer time the UPMEM SDK's serialization leaves on the
table (§5 stacked bars; arXiv:2110.01709 makes the same argument).

With a :class:`~repro.runtime.autotune.TuningResult` (``--tuned``), a third
column serves the same requests under the autotuner's per-workload plans:
the fitted model narrows the chunk-count sweep to a few candidates (always
including the untuned default), each candidate is measured end-to-end
through the scheduler, and the measured best is adopted — so
``tuned_speedup >= overlap_speedup`` holds by construction (ties allowed).
See DESIGN.md §8 and EXPERIMENTS.md §Bench-artifacts.

Workloads, argument generators, and result checks all come from
``repro.prim.registry``.  Serialized-only workloads (NW, BFS) are not
skipped: they get a row with ``pipelineable=no`` and the registry's reason,
so the table always covers the whole suite.

    PYTHONPATH=src python -m benchmarks.throughput --banks 8 [--tuned]
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "src"))

import numpy as np

from repro.launch.cli import cpu_rehearsal_env, enable_compile_cache


def _sched_run(grid, entry, args_list, *, n_chunks, plan=None,
               serialized_per_req=0.0):
    """One scheduler-level measurement through a deterministic PimSession
    sharing the caller's grid (and its compiled phase cache): warm (first
    batch pays compilation for this chunk shape), then time
    submit→drain→results end-to-end."""
    from repro.pim import PimSession

    plans = {entry.name: plan} if plan is not None else None
    sess = PimSession(grid=grid, n_chunks=n_chunks, plans=plans)
    warm = sess.submit(entry.name, *args_list[0])
    sess.drain()
    warm.result()
    sess.telemetry.reset()    # drop warm-up from records AND running stats

    t0 = time.perf_counter()
    reqs = [sess.submit(entry.name, *args) for args in args_list]
    sess.drain()
    outs = [r.result() for r in reqs]
    dt = time.perf_counter() - t0
    if serialized_per_req:
        for r in reqs:
            r.record.serialized_s = serialized_per_req
    sess.close()       # dpu_free; telemetry/plans stay readable
    return outs, dt, sess


def throughput(workloads=None, n_requests: int = 6, n_chunks: int = 4,
               scale: int = 2, check: bool = True, tuning=None, grid=None):
    """Rows for the ``runtime_throughput`` table.  ``tuning`` (a
    ``TuningResult``) adds the tuned columns; ``grid`` reuses a caller's
    BankGrid (and its compiled phase cache) instead of allocating one
    through a fresh ``pim.session()``."""
    from repro import pim
    from repro.runtime.autotune import prefilter_candidates

    registry = pim.registry()
    own = pim.PimSession(grid=grid)       # grid=None -> allocate one
    grid = own.grid
    entries = [registry[name] for name in (workloads or registry)]
    rng = np.random.default_rng(0)
    rows = []
    for e in entries:
        args_list = [e.make_args(rng, scale) for _ in range(n_requests)]

        e.pim(grid, *args_list[0])   # warm the serialized path's compile
        t0 = time.perf_counter()
        serial_out = [e.pim(grid, *args)[0] for args in args_list]
        serialized_s = time.perf_counter() - t0

        row = {"table": "runtime_throughput", "workload": e.name,
               "banks": grid.n_banks, "requests": n_requests,
               "chunks": n_chunks,
               "pipelineable": "yes" if e.pipelineable else "no",
               "serialized_s": serialized_s,
               "serialized_rps": n_requests / serialized_s,
               "pipelined_s": "", "pipelined_rps": "",
               "overlap_speedup": "", "mean_queue_wait_s": "",
               "aggregate_gbps": "",
               "tuned_s": "", "tuned_rps": "", "tuned_speedup": "",
               "tuned_chunks": "", "tuned_batch": "",
               "predicted_overlap": "", "adopted": "", "note": ""}

        if not e.pipelineable:
            row["note"] = f"serialized-only: {e.reason}"
            rows.append(row)
            continue

        per_req = serialized_s / n_requests
        pipe_out, pipelined_s, sess = _sched_run(
            grid, e, args_list, n_chunks=n_chunks,
            serialized_per_req=per_req)
        if check:
            for s, p in zip(serial_out, pipe_out):
                e.compare(p, s)

        agg = sess.stats()
        row.update({
            "pipelined_s": pipelined_s,
            "pipelined_rps": n_requests / pipelined_s,
            "overlap_speedup": serialized_s / pipelined_s,
            "mean_queue_wait_s": agg["mean_queue_wait_s"],
            "aggregate_gbps": agg["aggregate_gbps"],
        })

        if tuning is not None and e.name in tuning.plans:
            plan = tuning.plans[e.name]
            measured = {}
            # with cost-model predictions on the plan this prunes the probe
            # sweep (DESIGN.md §15); without them it is probe_candidates
            for c in prefilter_candidates(plan, default=n_chunks):
                cand = dataclasses.replace(plan, n_chunks=c)
                outs, dt, _ = _sched_run(grid, e, args_list, n_chunks=c,
                                         plan=cand,
                                         serialized_per_req=per_req)
                if check:
                    for s, p in zip(serial_out, outs):
                        e.compare(p, s)
                measured[c] = dt
            best = min(measured, key=lambda c: (measured[c], c))
            if measured[best] <= pipelined_s:
                tuned_s, tuned_chunks = measured[best], best
                tuned_batch, adopted = plan.max_batch_requests, "tuned"
            else:    # the untuned default measured best: fall back to it
                tuned_s, tuned_chunks = pipelined_s, n_chunks
                tuned_batch, adopted = \
                    sess.scheduler.max_batch_requests, "default"
            row.update({
                "tuned_s": tuned_s,
                "tuned_rps": n_requests / tuned_s,
                "tuned_speedup": serialized_s / tuned_s,
                "tuned_chunks": tuned_chunks,
                "tuned_batch": tuned_batch,
                "predicted_overlap": plan.predicted_overlap,
                "adopted": adopted,
            })
        rows.append(row)
    own.close()
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--banks", type=int, default=0,
                    help="re-exec with N forced host devices")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--chunks", type=int, default=4)
    ap.add_argument("--scale", type=int, default=2)
    ap.add_argument("--tuned", action="store_true",
                    help="autotune chunk/batch sizes and add tuned columns")
    ap.add_argument("--workloads", nargs="*", default=None,
                    help="subset of registry names (default: full registry)")
    args = ap.parse_args()
    if args.banks:
        env = cpu_rehearsal_env(args.banks)
        cmd = [sys.executable, "-m", "benchmarks.throughput",
               "--requests", str(args.requests), "--chunks", str(args.chunks),
               "--scale", str(args.scale)]
        if args.tuned:
            cmd.append("--tuned")
        if args.workloads:
            cmd += ["--workloads", *args.workloads]
        raise SystemExit(subprocess.call(cmd, env=env))
    from repro import pim
    sess = pim.session()
    tuning = None
    if args.tuned:
        registry = pim.registry()
        names = [n for n in (args.workloads or registry)
                 if registry[n].pipelineable]
        tuning = sess.autotune(names, scale=args.scale, probe=False)
    from benchmarks.run import emit
    emit(throughput(workloads=args.workloads, n_requests=args.requests,
                    n_chunks=args.chunks, scale=args.scale, tuning=tuning,
                    grid=sess.grid))
    sess.close()


if __name__ == "__main__":
    enable_compile_cache()
    main()
