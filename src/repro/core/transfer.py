"""Host <-> bank transfer engine (paper §2.1 / §3.4).

Reproduces the three CPU↔DPU transfer modes of the UPMEM SDK:

* serial     — ``dpu_copy_to``: one bank at a time; latency grows linearly
               with bank count (paper Fig. 10b, flat bandwidth).
* parallel   — ``dpu_prepare_xfer``/``dpu_push_xfer``: all banks at once;
               requires equal-size buffers per bank (same SDK restriction).
* broadcast  — ``dpu_broadcast_to``: one buffer replicated to every bank.

Plus the "transposition library": main memory uses a flat row-major layout
while PIM-enabled memory needs bank-major chunks; :func:`to_banked` /
:func:`from_banked` perform that relayout (pad + reshape to (banks, chunk)).

Every call returns (result, TransferRecord) so benchmarks can account
CPU-DPU / DPU-CPU time the way the paper's stacked bars do.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Sequence

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from .banked import BankGrid, RankGrid

@dataclasses.dataclass
class TransferRecord:
    kind: str
    nbytes: int
    seconds: float

    @property
    def bandwidth(self) -> float:
        return self.nbytes / self.seconds if self.seconds else float("inf")


def _record(sp, kind: str, nbytes: int, t0: float) -> TransferRecord:
    """The transfer begun at ``t0`` and ending now, as a record and on its
    span (DESIGN.md §11): the span carries the record's own interval."""
    t1 = time.perf_counter()
    sp.stamp(t0, t1)
    sp.tag(bytes=nbytes)
    return TransferRecord(kind, nbytes, t1 - t0)


def _nbytes(x) -> int:
    return int(np.prod(x.shape)) * x.dtype.itemsize if hasattr(x, "shape") else 0


def tree_nbytes(args) -> int:
    """Total payload bytes across a pytree of arrays (MLP passes a *list* of
    layer matrices — a flat top-level scan undercounts it)."""
    return sum(_nbytes(leaf) for leaf in jax.tree_util.tree_leaves(args))


# -- layout conversion ("transposition library") ----------------------------

def to_banked(x: np.ndarray, n_banks: int, axis: int = 0):
    """Pad ``axis`` to a multiple of n_banks and reshape to bank-major:
    (..., d, ...) -> (banks, ..., d/banks, ...). Returns (array, orig_len)."""
    x = np.asarray(x)
    d = x.shape[axis]
    pad = (-d) % n_banks
    if pad:
        widths = [(0, 0)] * x.ndim
        widths[axis] = (0, pad)
        x = np.pad(x, widths)
    new_shape = (x.shape[:axis] + (n_banks, x.shape[axis] // n_banks)
                 + x.shape[axis + 1:])
    moved = np.moveaxis(x.reshape(new_shape), axis, 0)
    return moved, d


def from_banked(x: np.ndarray, orig_len: int, axis: int = 0) -> np.ndarray:
    """Inverse of :func:`to_banked`."""
    x = np.asarray(x)
    x = np.moveaxis(x, 0, axis)
    flat = x.reshape(x.shape[:axis] + (-1,) + x.shape[axis + 2:])
    sl = [slice(None)] * flat.ndim
    sl[axis] = slice(0, orig_len)
    return flat[tuple(sl)]


# -- chunking (pipelined runtime) --------------------------------------------

def split_chunks(x: np.ndarray, n_chunks: int, axis: int = 0):
    """Split ``axis`` into ``n_chunks`` equal pieces for pipelined transfer,
    padding the tail so every chunk has an identical shape (one compiled
    bank-local phase serves all chunks).  Returns (chunks, orig_len)."""
    x = np.asarray(x)
    n = x.shape[axis]
    if n_chunks < 1:
        raise ValueError(f"n_chunks must be >= 1, got {n_chunks}")
    per = -(-n // n_chunks)
    pad = per * n_chunks - n
    if pad:
        widths = [(0, 0)] * x.ndim
        widths[axis] = (0, pad)
        x = np.pad(x, widths)
    sl = [slice(None)] * x.ndim
    chunks = []
    for i in range(n_chunks):
        sl[axis] = slice(i * per, (i + 1) * per)
        chunks.append(x[tuple(sl)])
    return chunks, n


def split_chunks_ranked(x: np.ndarray, n_ranks: int, n_chunks: int,
                        axis: int = 0):
    """Rank-granular :func:`split_chunks`: ``n_ranks`` contiguous groups of
    ``n_chunks`` equal chunks each — rank r's pipeline owns group r, and
    concatenating the groups in rank order restores the flat split order
    (so order-sensitive merges like SCAN's running offset stay correct).
    Returns (per_rank_chunk_lists, orig_len)."""
    if n_ranks < 1:
        raise ValueError(f"n_ranks must be >= 1, got {n_ranks}")
    chunks, n = split_chunks(x, n_ranks * n_chunks, axis)
    return [chunks[r * n_chunks:(r + 1) * n_chunks]
            for r in range(n_ranks)], n


# -- transfer modes ----------------------------------------------------------

def push_parallel(grid: BankGrid, x, spec: P | None = None):
    nbytes = _nbytes(np.asarray(x))
    with span("cpu_dpu_parallel", "transfer") as sp:
        t0 = time.perf_counter()
        out = grid.to_banks(x, spec)
        jax.block_until_ready(out)
        return out, _record(sp, "cpu_dpu_parallel", nbytes, t0)


def push_serial(grid: BankGrid, chunks: Sequence[np.ndarray]):
    nbytes = sum(_nbytes(c) for c in chunks)
    with span("cpu_dpu_serial", "transfer") as sp:
        t0 = time.perf_counter()
        out = grid.serial_to_banks(chunks)
        jax.block_until_ready(out)
        return out, _record(sp, "cpu_dpu_serial", nbytes, t0)


def push_broadcast(grid: BankGrid, x):
    nbytes = _nbytes(np.asarray(x))
    with span("cpu_dpu_broadcast", "transfer") as sp:
        t0 = time.perf_counter()
        out = grid.broadcast(x)
        jax.block_until_ready(out)
        return out, _record(sp, "cpu_dpu_broadcast", nbytes, t0)


def pull_parallel(grid: BankGrid, x):
    with span("dpu_cpu_parallel", "transfer") as sp:
        t0 = time.perf_counter()
        host = grid.from_banks(x)
        return host, _record(sp, "dpu_cpu_parallel", _nbytes(host), t0)


# -- async variants (double-buffering building blocks) -----------------------
#
# The synchronous modes above block until the copy lands — faithful to the
# UPMEM SDK, where a transfer and a kernel launch never overlap.  The async
# variants only *enqueue* the copy: the runtime pipeline issues chunk k+1's
# scatter while chunk k's bank-local phase is still in flight, which is
# exactly the overlap the paper's stacked bars show the SDK leaving on the
# table.  Their records therefore account enqueue cost, not completion.

def push_parallel_async(grid: BankGrid, x, spec: P | None = None):
    """Parallel CPU→bank scatter without the completion barrier."""
    nbytes = _nbytes(np.asarray(x))
    with span("cpu_dpu_async", "transfer") as sp:
        t0 = time.perf_counter()
        out = grid.to_banks(x, spec)
        return out, _record(sp, "cpu_dpu_async", nbytes, t0)


def pull_async(x):
    """Begin an async bank→CPU copy; returns ``resolve()`` which blocks for
    completion and yields (host_array, TransferRecord).  The record's seconds
    measure only the blocking tail, i.e. whatever the overlap didn't hide."""
    try:
        x.copy_to_host_async()
    except AttributeError:
        pass  # non-jax arrays (already host) resolve immediately

    def resolve():
        with span("dpu_cpu_async", "transfer") as sp:
            t0 = time.perf_counter()
            host = np.asarray(jax.device_get(x))
            return host, _record(sp, "dpu_cpu_async", _nbytes(host), t0)
    return resolve


def pull_serial(grid: BankGrid, xs: Sequence):
    with span("dpu_cpu_serial", "transfer") as sp:
        t0 = time.perf_counter()
        host = [np.asarray(jax.device_get(x)) for x in xs]
        return host, _record(sp, "dpu_cpu_serial",
                             sum(_nbytes(h) for h in host), t0)


# -- rank-parallel transfers (DESIGN.md §10) ---------------------------------
#
# On a real UPMEM system CPU↔DPU transfers to *different ranks* proceed in
# parallel, so aggregate CPU-DPU bandwidth grows ~×ranks (paper §5,
# arXiv:2110.01709 Fig. 5).  These helpers reproduce that: one async
# enqueue per rank, none blocking, so the copies to all ranks are in flight
# concurrently.  ``core.characterize.rank_parallel_sweep`` measures the
# achieved scaling and the autotuner consumes it (DESIGN.md §8 and §10).

def push_ranks_async(grid: RankGrid, per_rank: Sequence, spec: P | None = None):
    """Rank-parallel CPU→bank scatter: issue ``per_rank[r]`` to rank ``r``'s
    banks for every rank concurrently (no completion barrier).  Returns
    (per-rank device arrays, TransferRecord accounting enqueue cost)."""
    if len(per_rank) > grid.n_ranks:
        raise ValueError(f"{len(per_rank)} payloads for {grid.n_ranks} ranks")
    nbytes = sum(_nbytes(np.asarray(x)) for x in per_rank)
    with span("cpu_dpu_rank_async", "transfer") as sp:
        t0 = time.perf_counter()
        outs = [grid.rank_view(r).to_banks(x, spec)
                for r, x in enumerate(per_rank)]
        return outs, _record(sp, "cpu_dpu_rank_async", nbytes, t0)


def pull_ranks_async(xs: Sequence):
    """Begin async bank→CPU copies from every rank at once; returns
    ``resolve()`` which blocks for all of them and yields
    (host_arrays, TransferRecord) — the rank-parallel :func:`pull_async`."""
    for x in xs:
        try:
            x.copy_to_host_async()
        except AttributeError:
            pass

    def resolve():
        with span("dpu_cpu_rank_async", "transfer") as sp:
            t0 = time.perf_counter()
            host = [np.asarray(jax.device_get(x)) for x in xs]
            return host, _record(sp, "dpu_cpu_rank_async",
                                 sum(_nbytes(h) for h in host), t0)
    return resolve


# bound last: ``repro.runtime`` imports this module while it initialises,
# so ``repro.runtime.trace`` can only be imported once the names above exist
from repro.runtime.trace import span  # noqa: E402
