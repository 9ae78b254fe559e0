"""Fused GEMV variants for the LLM decode hot path (DESIGN.md §14).

The decode serving engine (``repro.pim.decode``) routes every per-token
matvec — attention q/k/v/o projections and the MLP up/down halves —
through these two workloads.  Both follow GEMV's decomposition (paper
§4.2: consecutive output rows → DPU i, activation vector broadcast), but
fuse the epilogue the model would otherwise run on the host, so one
bank-local launch produces the finished projection:

* ``GEMV-B`` — ``y = W @ x + b``: matvec with bias fusion.  The resident
  operand is a *pytree* ``{"w": (n, d), "b": (n,)}`` — the whole
  projection pins in one call (the satellite pytree-pinning path); a
  layer without a bias passes zeros (exact +0.0).
* ``GEMV-G`` — ``y = silu(Wg @ x) * (Wu @ x)``: the SwiGLU gated hidden,
  both halves' rows sharded together so the gate and up matvecs for an
  output element land on the same bank (no inter-DPU exchange).  The
  silu runs in float32 and casts back, exactly matching
  ``repro.models.layers.swiglu``.

Row chunks are the pipeline's chunks (and the residency chunks): on a
RankGrid the contiguous chunk blocks shard output rows — attention heads,
FFN columns — across ranks, so a warm decode step scatters only the
activation vector broadcast.

The vector operand is either one vector ``(d,)`` or a stack of them as
columns ``(d, n)``, and the result is ``(rows,)`` or ``(rows, n)`` to
match: the operand's ``ndim`` picks the shape inside the same compute
phase, so the matrix is read once for every column.  GEMV-B's bias
broadcasts against ``W @ x`` as numpy has it: ``(rows,)`` for one vector,
a column ``(rows, 1)`` for a stack.  The decode engine sends one stack a
weight matrix a step, a column per stream that needs it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import transfer as tx
from repro.core.banked import AXIS, BankGrid
from .common import (ChunkedWorkload, PhaseTimer, matvec, pad_chunks,
                     register_chunked, sync)


def _silu_f32(g):
    """silu in float32, cast back — the swiglu gate's exact numerics."""
    return jax.nn.silu(g.astype(jnp.float32)).astype(g.dtype)


def _rows(out: np.ndarray) -> np.ndarray:
    """A banked result (banks, per, [n]) as rows (banks * per, [n])."""
    return out.reshape(-1, *out.shape[2:])


def _bias_mv(wb, bb, xb):
    return matvec(wb, xb) + bb


def _gated_mv(gb, ub, xb):
    return _silu_f32(matvec(gb, xb)) * matvec(ub, xb)


# -- GEMV-B: y = W @ x + b ----------------------------------------------------

def ref_b(w: dict, x: np.ndarray) -> np.ndarray:
    return w["w"] @ x + w["b"]


def pim_b(grid: BankGrid, w: dict, x: np.ndarray):
    t = PhaseTimer()
    with t.phase("cpu_dpu"):
        wc, m = pad_chunks(w["w"], grid.n_banks)
        bc, _ = pad_chunks(w["b"], grid.n_banks)
        dw = sync(grid.to_banks(wc))
        db = sync(grid.to_banks(bc))
        dx = sync(grid.broadcast(np.asarray(x)))
    f = grid.bank_local(_bias_mv,
                        in_specs=(P(AXIS), P(AXIS), P()))
    with t.phase("dpu"):
        out = sync(f(dw, db, dx))
    with t.phase("dpu_cpu"):
        host = _rows(grid.from_banks(out))[:m]
    return host, t.times


@functools.cache
def _local_b(grid: BankGrid):
    return jax.jit(grid.bank_local(_bias_mv,
                                   in_specs=(P(AXIS), P(AXIS), P())))


def _split_resident_b(grid, n_chunks, w):
    wch, m = tx.split_chunks(np.asarray(w["w"]), n_chunks)
    bch, _ = tx.split_chunks(np.asarray(w["b"]), n_chunks)
    chunks = [{"w": wc, "b": bc} for wc, bc in zip(wch, bch)]
    return {"m": m, "per": wch[0].shape[0]}, chunks


def _split_varying_b(grid, n_chunks, res_meta, w, x):
    return {**res_meta, "dx": grid.broadcast(np.asarray(x))}, None


def _split_b(grid, n_chunks, w, x):
    res_meta, chunks = _split_resident_b(grid, n_chunks, w)
    meta, _ = _split_varying_b(grid, n_chunks, res_meta, w, x)
    return meta, chunks


def _scatter_b(grid, meta, chunk):
    wc, _ = pad_chunks(chunk["w"], grid.n_banks)
    bc, _ = pad_chunks(chunk["b"], grid.n_banks)
    return grid.to_banks(wc), grid.to_banks(bc)


def _compute_b(grid, meta, bufs):
    dw, db = bufs
    return _local_b(grid)(dw, db, meta["dx"])


def _retrieve_b(grid, meta, out):
    return _rows(grid.from_banks(out))[:meta["per"]]


def _merge_b(grid, meta, parts):
    return np.concatenate(parts)[:meta["m"]]


chunked_b = register_chunked(ChunkedWorkload(
    "GEMV-B", _split_b, _scatter_b, _compute_b, _retrieve_b, _merge_b,
    resident_args=(0,), split_resident=_split_resident_b,
    split_varying=_split_varying_b))


# -- GEMV-G: y = silu(Wg @ x) * (Wu @ x) --------------------------------------

def ref_g(w: dict, x: np.ndarray) -> np.ndarray:
    g = jnp.asarray(w["wg"] @ x)
    u = w["wu"] @ x
    return np.asarray(_silu_f32(g) * u)


def pim_g(grid: BankGrid, w: dict, x: np.ndarray):
    t = PhaseTimer()
    with t.phase("cpu_dpu"):
        gc, m = pad_chunks(w["wg"], grid.n_banks)
        uc, _ = pad_chunks(w["wu"], grid.n_banks)
        dg = sync(grid.to_banks(gc))
        du = sync(grid.to_banks(uc))
        dx = sync(grid.broadcast(np.asarray(x)))
    f = grid.bank_local(_gated_mv,
                        in_specs=(P(AXIS), P(AXIS), P()))
    with t.phase("dpu"):
        out = sync(f(dg, du, dx))
    with t.phase("dpu_cpu"):
        host = _rows(grid.from_banks(out))[:m]
    return host, t.times


@functools.cache
def _local_g(grid: BankGrid):
    return jax.jit(grid.bank_local(_gated_mv,
                                   in_specs=(P(AXIS), P(AXIS), P())))


def _split_resident_g(grid, n_chunks, w):
    gch, m = tx.split_chunks(np.asarray(w["wg"]), n_chunks)
    uch, _ = tx.split_chunks(np.asarray(w["wu"]), n_chunks)
    chunks = [{"wg": gc, "wu": uc} for gc, uc in zip(gch, uch)]
    return {"m": m, "per": gch[0].shape[0]}, chunks


def _split_varying_g(grid, n_chunks, res_meta, w, x):
    return {**res_meta, "dx": grid.broadcast(np.asarray(x))}, None


def _split_g(grid, n_chunks, w, x):
    res_meta, chunks = _split_resident_g(grid, n_chunks, w)
    meta, _ = _split_varying_g(grid, n_chunks, res_meta, w, x)
    return meta, chunks


def _scatter_g(grid, meta, chunk):
    gc, _ = pad_chunks(chunk["wg"], grid.n_banks)
    uc, _ = pad_chunks(chunk["wu"], grid.n_banks)
    return grid.to_banks(gc), grid.to_banks(uc)


def _compute_g(grid, meta, bufs):
    dg, du = bufs
    return _local_g(grid)(dg, du, meta["dx"])


def _retrieve_g(grid, meta, out):
    return _rows(grid.from_banks(out))[:meta["per"]]


def _merge_g(grid, meta, parts):
    return np.concatenate(parts)[:meta["m"]]


chunked_g = register_chunked(ChunkedWorkload(
    "GEMV-G", _split_g, _scatter_g, _compute_g, _retrieve_g, _merge_g,
    resident_args=(0,), split_resident=_split_resident_g,
    split_varying=_split_varying_g))
