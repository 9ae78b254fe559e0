"""PrIM HST — Image Histogram, short & long variants (paper §4.11).

HST-S: per-tasklet private histograms merged at a barrier → TPU-native: the
one-hot-matmul Pallas histogram (kernels/histogram.py) where each grid block
is a "tasklet" with a private accumulator revisit.
HST-L: one shared mutex-guarded histogram per DPU → TPUs have no mutexes
(DESIGN.md §2); the semantic equivalent is a single jnp scatter-add per bank
(serialized adds, like the mutex), which we implement as bincount.

Both merge per-bank histograms on the host (tiny inter-DPU phase).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import transfer as tx
from repro.core.banked import BankGrid
from repro.kernels import ops
from .common import ChunkedWorkload, PhaseTimer, pad_chunks, register_chunked, sync


def ref(pixels: np.ndarray, nbins: int) -> np.ndarray:
    return np.bincount(np.clip(pixels, 0, nbins - 1),
                       minlength=nbins).astype(np.int32)


def _pim(grid: BankGrid, pixels: np.ndarray, nbins: int, variant: str):
    t = PhaseTimer()
    with t.phase("cpu_dpu"):
        pc, n = pad_chunks(pixels, grid.n_banks, fill=-1)  # -1 ⇒ bin 0, fixed
        pad_total = pc.size - n
        dp = sync(grid.to_banks(pc))

    def local_s(pb):
        return ops.histogram(pb[0], nbins)[None]

    def local_l(pb):
        clipped = jnp.clip(pb[0], 0, nbins - 1)
        return jnp.zeros(nbins, jnp.int32).at[clipped].add(1)[None]

    f = grid.bank_local(local_s if variant == "short" else local_l)
    with t.phase("dpu"):
        parts = sync(f(dp))
    with t.phase("inter_dpu"):
        hist = grid.from_banks(parts).sum(axis=0).astype(np.int32)
        hist[0] -= pad_total          # remove padding sentinel counts
    return hist, t.times


def pim_short(grid: BankGrid, pixels: np.ndarray, nbins: int = 256):
    return _pim(grid, pixels, nbins, "short")


def pim_long(grid: BankGrid, pixels: np.ndarray, nbins: int = 256):
    return _pim(grid, pixels, nbins, "long")


# -- chunked phases (pipelined runtime) --------------------------------------
# Histograms are associative: each chunk yields per-bank partial histograms
# that retrieve sums bank-wise and merge sums chunk-wise.  Both padding kinds
# (split_chunks zeros at the chunk tail, pad_chunks -1 sentinels at the bank
# tail) land in bin 0, so merge subtracts one precomputed spurious count.
# The chunked phase counts with a dense one-hot contraction (MXU and VPU, no
# scatter); only pim_long keeps the serialized scatter-add, as HST-L's mutex
# model (DESIGN.md §2).

def _count(v, nbins: int):
    """Histogram of ``v`` (1-D int32, clipped to ``[0, nbins)``) as a
    two-factor one-hot contraction: bin ``b = hi * lo_n + lo`` with ``lo_n =
    ceil(sqrt(nbins))`` and ``hi_n = ceil(nbins / lo_n)``, so ``onehot(hi)^T
    @ onehot(lo)`` counts every (hi, lo) pair in ``hi_n + lo_n`` compares a
    value, where a scatter-add makes one serialized update.  int8 operands,
    int32 accumulation: exact while a bin holds fewer than 2**31 values."""
    lo_n = math.isqrt(nbins - 1) + 1
    hi_n = -(-nbins // lo_n)
    v = jnp.clip(v, 0, nbins - 1)[:, None]
    hi = (v // lo_n == jnp.arange(hi_n)).astype(jnp.int8)     # (n, hi_n)
    lo = (v % lo_n == jnp.arange(lo_n)).astype(jnp.int8)      # (n, lo_n)
    counts = jax.lax.dot_general(hi, lo, (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.int32)
    return counts.reshape(hi_n * lo_n)[:nbins]


@functools.cache
def _local(grid: BankGrid, nbins: int):
    def local(pb):
        return _count(pb[0], nbins)[None]
    return jax.jit(grid.bank_local(local))


def _split(grid, n_chunks, pixels, nbins=256):
    chunks, n = tx.split_chunks(np.asarray(pixels), n_chunks)
    per = chunks[0].shape[0]
    per_bank = -(-per // grid.n_banks)
    spurious = len(chunks) * per_bank * grid.n_banks - n
    return {"nbins": nbins, "spurious": spurious}, chunks


def _scatter(grid, meta, chunk):
    pc, _ = pad_chunks(chunk, grid.n_banks, fill=-1)
    return grid.to_banks(pc)


def _compute(grid, meta, dp):
    return _local(grid, meta["nbins"])(dp)


def _retrieve(grid, meta, parts):
    return grid.from_banks(parts).sum(axis=0)


def _merge(grid, meta, parts):
    hist = np.sum(parts, axis=0).astype(np.int32)
    hist[0] -= meta["spurious"]
    return hist


chunked = register_chunked(ChunkedWorkload(
    "HST", _split, _scatter, _compute, _retrieve, _merge))
