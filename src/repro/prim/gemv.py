"""PrIM GEMV — Matrix-Vector Multiply (paper §4.2).

Decomposition: consecutive matrix rows → DPU i (parallel transfer); the
input vector is replicated on every bank (broadcast CPU→DPU); each bank
multiply-accumulates its rows (blocked Pallas GEMV on TPU); per-bank output
chunks retrieved and concatenated by the host.
"""
from __future__ import annotations

import functools

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import transfer as tx
from repro.core.banked import AXIS, BankGrid
from repro.kernels import ops
from .common import (ChunkedWorkload, PhaseTimer, matvec, pad_chunks,
                     register_chunked, sync)


def ref(a: np.ndarray, x: np.ndarray) -> np.ndarray:
    return a @ x


def pim(grid: BankGrid, a: np.ndarray, x: np.ndarray, use_kernel: bool = False):
    t = PhaseTimer()
    with t.phase("cpu_dpu"):
        ac, m = pad_chunks(a, grid.n_banks)
        da = sync(grid.to_banks(ac))
        dx = sync(grid.broadcast(np.asarray(x)))

    def local(ab, xb):
        if use_kernel:
            return ops.gemv(ab[0], xb)[None]
        return matvec(ab, xb)

    f = grid.bank_local(local, in_specs=(P(AXIS), P()))
    with t.phase("dpu"):
        out = sync(f(da, dx))
    with t.phase("dpu_cpu"):
        host = grid.from_banks(out).reshape(-1)[:m]
    return host, t.times


# -- chunked phases (pipelined runtime) --------------------------------------
# Row chunks pipeline through the banks; the input vector is broadcast once
# per request during split (it is a per-request constant, not a chunk).

@functools.cache
def _local(grid: BankGrid):
    return jax.jit(grid.bank_local(matvec, in_specs=(P(AXIS), P())))


# The matrix is the residency candidate (DESIGN.md §12): its row chunks are
# the pipeline's chunks, so a warm hit elides the scatter stage entirely and
# only the small vector broadcast remains per request.

def _split_resident(grid, n_chunks, a):
    chunks, m = tx.split_chunks(np.asarray(a), n_chunks)
    return {"m": m, "per": chunks[0].shape[0]}, chunks


def _split_varying(grid, n_chunks, res_meta, a, x):
    return {**res_meta, "dx": grid.broadcast(np.asarray(x))}, None


def _split(grid, n_chunks, a, x):
    res_meta, chunks = _split_resident(grid, n_chunks, a)
    meta, _ = _split_varying(grid, n_chunks, res_meta, a, x)
    return meta, chunks


def _scatter(grid, meta, chunk):
    ac, _ = pad_chunks(chunk, grid.n_banks)
    return grid.to_banks(ac)


def _compute(grid, meta, da):
    return _local(grid)(da, meta["dx"])


def _retrieve(grid, meta, out):
    return grid.from_banks(out).reshape(-1)[:meta["per"]]


def _merge(grid, meta, parts):
    return np.concatenate(parts)[:meta["m"]]


chunked = register_chunked(ChunkedWorkload(
    "GEMV", _split, _scatter, _compute, _retrieve, _merge,
    resident_args=(0,), split_resident=_split_resident,
    split_varying=_split_varying))
