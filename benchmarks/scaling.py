"""Rank-level strong/weak scaling over the pipelineable registry
(paper §5; arXiv:2110.01709 §5 — the headline evidence that the PIM
paradigm scales is PrIM at 1→32 ranks / up to 2,556 DPUs).

Strong scaling: a fixed problem served on 1..R ranks of ``banks_per_rank``
banks each (``pim.session(ranks=r, banks_per_rank=B)``, DESIGN.md §10) —
more ranks mean more banks *and* rank-parallel CPU↔bank transfers, so
service time should fall.  Weak scaling: the problem grows ∝ ranks, so
aggregate throughput (bytes served per second) should hold or grow —
``tools/check_bench.py`` gates bench artifacts on exactly that invariant
(the monotone weak-scaling check).

Each measurement is a full session ``run()`` — split, rank-sharded chunk
pipelines, merge — warmed once (compilation), then the best of ``reps``
timed runs.  Rows ride into the ``scaling`` section of the bench artifact
(EXPERIMENTS.md §Scaling).

    PYTHONPATH=src python -m benchmarks.scaling --devices 8 \
        --ranks 1 2 4 --banks-per-rank 2
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys
import time
import zlib

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "src")
)

import numpy as np

from repro.launch.cli import cpu_rehearsal_env, enable_compile_cache


#: Workloads whose weak scaling a *host-simulated* backend can sustain —
#: transfer/dispatch-dominated ones.  On real PIM hardware every PrIM
#: workload weak-scales with ranks (paper §5: each rank brings its own
#: DPUs); on a CPU simulation the "ranks" share the host's physical cores,
#: so compute-bound workloads (MLP's matmuls, TRNS) cannot, and gating
#: them would test the host's core count, not the runtime.  The bench
#: artifact's gated ``rank_weak`` section uses this subset; the full sweep
#: stays available via the CLI.
WEAK_GATE_WORKLOADS = ("VA", "SEL", "SCAN")


def _entries(workloads=None):
    from repro import pim

    return [
        e
        for name, e in pim.registry().items()
        if e.pipelineable and (not workloads or name in workloads)
    ]


def _measure(sess, entry, args, reps: int) -> float:
    """Best-of-``reps`` service time of one warmed session.run()
    invocation.  Min, not median: scaling ratios compare the *achievable*
    time per configuration, and min is the standard estimator robust to
    interference from co-tenants on a shared host."""
    sess.run(entry.name, *args)  # warm: compile per-rank phases
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        sess.run(entry.name, *args)
        ts.append(time.perf_counter() - t0)
    return float(np.min(ts))


def _rows(mode: str, rank_counts, banks_per_rank, scales, workloads, reps):
    """Shared sweep: one session per rank count, every pipelineable
    workload measured at its ``scales[rank_count]`` problem size.  Rank
    counts are swept ascending so the ``*_vs_1`` ratios are always quoted
    against the smallest rank count in the sweep."""
    from repro import pim

    rows = []
    counts = sorted(set(rank_counts))
    base: dict = {}
    for r in counts:
        sess = pim.session(ranks=r, banks_per_rank=banks_per_rank)
        for entry in _entries(workloads):
            rng = np.random.default_rng(zlib.crc32(entry.name.encode()))
            args = entry.make_args(rng, scales[r])
            nbytes = entry.arg_nbytes(args)
            sec = _measure(sess, entry, args, reps)
            gbps = nbytes / sec / 1e9
            base.setdefault(entry.name, (sec, gbps))
            rows.append(
                {
                    "table": f"rank_{mode}",
                    "workload": entry.name,
                    "ranks": r,
                    "banks_per_rank": banks_per_rank,
                    "n_banks": sess.n_banks,
                    "scale": scales[r],
                    "bytes_in": nbytes,
                    "seconds": sec,
                    "gbps": gbps,
                    # ratios vs the smallest swept rank count (base_ranks):
                    # strong = time ratio, weak = throughput ratio
                    "base_ranks": counts[0],
                    "speedup_vs_base": base[entry.name][0] / sec,
                    "throughput_vs_base": gbps / base[entry.name][1],
                }
            )
        sess.close()
    return rows


def strong_scaling(
    rank_counts=(1, 2),
    banks_per_rank: int | None = None,
    scale: int = 2,
    workloads=None,
    reps: int = 3,
):
    """Fixed problem, 1..R ranks (paper §5 strong scaling at rank level)."""
    banks_per_rank = banks_per_rank or _default_banks(rank_counts)
    scales = {r: scale for r in rank_counts}
    return _rows("strong", rank_counts, banks_per_rank, scales, workloads, reps)


def weak_scaling(
    rank_counts=(1, 2),
    banks_per_rank: int | None = None,
    base_scale: int = 1,
    workloads=None,
    reps: int = 3,
):
    """Problem ∝ ranks (paper §5 weak scaling): aggregate throughput must
    hold or grow — the invariant ``check_bench.py`` gates on."""
    banks_per_rank = banks_per_rank or _default_banks(rank_counts)
    scales = {r: base_scale * r for r in rank_counts}
    return _rows("weak", rank_counts, banks_per_rank, scales, workloads, reps)


def _default_banks(rank_counts) -> int:
    import jax

    return max(len(jax.devices()) // max(rank_counts), 1)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--devices",
        type=int,
        default=0,
        help="re-exec with N forced host devices",
    )
    ap.add_argument(
        "--ranks",
        type=int,
        nargs="*",
        default=[1, 2],
        help="rank counts to sweep (need ranks*banks_per_rank devices)",
    )
    ap.add_argument("--banks-per-rank", type=int, default=None)
    ap.add_argument(
        "--scale",
        type=int,
        default=2,
        help="strong-scaling problem scale / weak-scaling base",
    )
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument(
        "--workloads",
        nargs="*",
        default=None,
        help="subset of pipelineable registry names",
    )
    args = ap.parse_args()
    if args.devices:
        env = cpu_rehearsal_env(args.devices)
        cmd = [
            sys.executable,
            "-m",
            "benchmarks.scaling",
            "--ranks",
            *map(str, args.ranks),
            "--scale",
            str(args.scale),
            "--reps",
            str(args.reps),
        ]
        if args.banks_per_rank:
            cmd += ["--banks-per-rank", str(args.banks_per_rank)]
        if args.workloads:
            cmd += ["--workloads", *args.workloads]
        raise SystemExit(subprocess.call(cmd, env=env))
    from benchmarks.run import emit

    emit(
        strong_scaling(
            tuple(args.ranks),
            args.banks_per_rank,
            scale=args.scale,
            workloads=args.workloads,
            reps=args.reps,
        )
    )
    emit(
        weak_scaling(
            tuple(args.ranks),
            args.banks_per_rank,
            base_scale=args.scale,
            workloads=args.workloads,
            reps=args.reps,
        )
    )


if __name__ == "__main__":
    enable_compile_cache()
    main()
