"""Microbenchmark harness — the paper's §3 characterization methodology.

Each function mirrors one of the paper's microbenchmarks and returns rows of
measurements taken on the *current JAX backend*: the DMA latency sweep
(Fig. 6) and the calibration sweeps the autotuner and the cost model fit
(DESIGN.md §8, §15).

Measurement discipline: jit + warmup + block_until_ready, median of ``reps``.
"""
from __future__ import annotations

import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from .banked import BankGrid
from . import transfer as tx


def _time(fn: Callable, *args, reps: int = 5) -> float:
    out = fn(*args)
    jax.block_until_ready(out)          # warmup / compile
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


# -- §3.1 arithmetic ops and data types (op_throughput_sweep) --------------

_OPS = {
    "add": lambda x, s: x + s,
    "sub": lambda x, s: x - s,
    "mul": lambda x, s: x * s,
    "div": lambda x, s: x / s if jnp.issubdtype(x.dtype, jnp.floating)
    else x // s,
}
_DTYPES = {"int32": jnp.int32, "int64": jnp.int64,
           "float": jnp.float32, "double": jnp.float64}


# -- §3.2.1 DMA latency model (Fig. 6) ---------------------------------------

def dma_latency_sweep(sizes=(8, 32, 128, 512, 2048, 8192, 65536),
                      reps: int = 20) -> list[dict]:
    """On-device block copy latency vs size; α/β fit per paper Eq. 3."""
    rows = []
    for size in sizes:
        x = jnp.zeros(size, jnp.uint8)
        f = jax.jit(lambda v: v + jnp.uint8(1))
        sec = _time(f, x, reps=reps)
        rows.append({"size": size, "seconds": sec,
                     "mbps": size / sec / 1e6})
    return rows


def fit_dma_model(rows: list[dict], freq_hz: float) -> tuple[float, float]:
    """Recover (alpha_cycles, beta_cycles_per_byte) from a latency sweep."""
    sizes = [r["size"] for r in rows]
    cycles = [r["seconds"] * freq_hz for r in rows]
    from .perfmodel import DpuModel
    return DpuModel.fit_dma(sizes, cycles)


# -- autotune calibration sweeps (DESIGN.md §8) ------------------------------
#
# The autotuner's measured analogues of the paper's Eqs. 1-4: each pipeline
# stage's time for b bytes is affine, t(b) = alpha + b / bw (Eq. 3's shape).
# These sweeps produce the (nbytes, seconds) points the affine fit consumes.

def push_pull_sweep(grid: BankGrid, nbytes=(1 << 18, 1 << 20, 1 << 22),
                    reps: int = 5) -> list[dict]:
    """CPU→bank scatter and bank→CPU retrieve latency vs payload size."""
    rows = []
    for size in nbytes:
        buf = np.zeros((grid.n_banks, max(size // 8 // grid.n_banks, 1)),
                       np.int64)
        push_s = _time(lambda b: tx.push_parallel(grid, b)[0], buf, reps=reps)
        dev, _ = tx.push_parallel(grid, buf)
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            grid.from_banks(dev)
            ts.append(time.perf_counter() - t0)
        rows.append({"nbytes": buf.nbytes, "push_s": push_s,
                     "pull_s": float(np.median(ts))})
    return rows


def op_throughput_sweep(grid: BankGrid, ops=("add", "sub", "mul", "div"),
                        dtypes=("int32", "float"),
                        nbytes=(1 << 16, 1 << 20),
                        reps: int = 5) -> list[dict]:
    """Per-(op, dtype) grid-level issue+execute cost points — §3.1 made
    fit-ready for the cost model (DESIGN.md §15).  One jitted bank-local
    elementwise kernel per pair, timed at each payload size; two sizes
    make the affine fit t(n) = issue_s + n * per_op_s exact.  Rows feed
    :meth:`repro.core.costmodel.CostModel.fit`."""
    np_dt = {"int32": np.int32, "int64": np.int64,
             "float": np.float32, "double": np.float64}
    rows = []
    for dtype in dtypes:
        s = _DTYPES[dtype](3)
        item = np.dtype(np_dt[dtype]).itemsize
        for op in ops:
            fn = _OPS[op]
            local = jax.jit(grid.bank_local(
                lambda x, _fn=fn, _s=s: _fn(x, _s), in_specs=None))
            for size in nbytes:
                per_bank = max(size // item // grid.n_banks, 1)
                buf = grid.to_banks(np.ones((grid.n_banks, per_bank),
                                            np_dt[dtype]))
                sec = _time(local, buf, reps=reps)
                elements = per_bank * grid.n_banks
                rows.append({"op": op, "dtype": dtype,
                             "elements": elements,
                             "nbytes": elements * item,
                             "seconds": sec,
                             "mops": elements / sec / 1e6})
    return rows


def bank_compute_sweep(grid: BankGrid, nbytes=(1 << 18, 1 << 20, 1 << 22),
                       reps: int = 5) -> list[dict]:
    """Bank-local streaming-compute latency vs payload size (one jitted
    elementwise phase per size — the dispatch cost is part of the alpha the
    fit recovers, exactly what the chunk planner must amortize)."""
    rows = []
    local = jax.jit(grid.bank_local(lambda x: x * np.int64(3) + np.int64(1),
                                    in_specs=None))
    for size in nbytes:
        buf = grid.to_banks(np.zeros(
            (grid.n_banks, max(size // 8 // grid.n_banks, 1)), np.int64))
        sec = _time(local, buf, reps=reps)
        leaves = jax.tree_util.tree_leaves(buf)
        rows.append({"nbytes": sum(x.nbytes for x in leaves),
                     "compute_s": sec})
    return rows


# -- rank-level transfer scaling (paper §5; DESIGN.md §10) -------------------

def rank_parallel_sweep(grid, rank_counts=None, nbytes: int = 1 << 22,
                        reps: int = 5) -> list[dict]:
    """CPU↔bank transfer time vs number of concurrently-addressed ranks at
    fixed total payload — the backend's analogue of the paper's rank-level
    CPU-DPU bandwidth scaling (transfers to different ranks proceed in
    parallel, so aggregate bandwidth grows ~×ranks).  ``grid`` must be a
    :class:`~repro.core.banked.RankGrid`; ``rank_counts`` defaults to the
    divisors of its rank count.  The autotuner feeds these rows into the
    rank dimension of every TunedPlan (DESIGN.md §8 and §10)."""
    n_ranks = getattr(grid, "n_ranks", 1)
    if rank_counts is None:
        rank_counts = [r for r in range(1, n_ranks + 1) if n_ranks % r == 0]
    rows = []
    for r in rank_counts:
        banks = r * grid.n_banks // n_ranks
        per_rank = [np.zeros((grid.n_banks // n_ranks,
                              max(nbytes // 8 // banks, 1)), np.int64)
                    for _ in range(r)]
        views = ([grid.rank_view(i) for i in range(r)]
                 if hasattr(grid, "rank_view") else [grid])
        if len(views) < r:
            raise ValueError(f"rank_parallel_sweep needs a RankGrid to "
                             f"address {r} ranks; got a flat grid")

        def push():
            return [v.to_banks(x) for v, x in zip(views, per_rank)]

        push_s = _time(push, reps=reps)
        devs = push()
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            resolve = tx.pull_ranks_async(devs)
            resolve()
            ts.append(time.perf_counter() - t0)
        total = sum(x.nbytes for x in per_rank)
        pull_s = float(np.median(ts))
        rows.append({"ranks": r, "banks": banks, "nbytes": total,
                     "push_s": push_s, "pull_s": pull_s,
                     "push_gbps": total / push_s / 1e9,
                     "pull_gbps": total / pull_s / 1e9})
    return rows
