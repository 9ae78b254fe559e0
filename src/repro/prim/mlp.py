"""PrIM MLP — Multilayer Perceptron inference (paper §4.9).

Each layer is the GEMV decomposition (§4.2): weight rows split across banks,
input vector broadcast.  Faithful to the paper, the host gathers the layer
output, reconstructs the full vector, and re-broadcasts it as the next
layer's input — that per-layer host round-trip is the "Inter-DPU" cost that
Fig. 13 shows shrinking with parallel transfers.  ReLU after every layer.
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


def ref(weights: list[np.ndarray], x: np.ndarray) -> np.ndarray:
    h = x
    for w in weights:
        h = np.maximum(w @ h, 0)
    return h


def pim(grid: BankGrid, weights: list[np.ndarray], x: np.ndarray):
    t = PhaseTimer()
    f = grid.bank_local(
        lambda wb, hb: jnp.maximum(matvec(wb, hb), 0),
        in_specs=(P(AXIS), P()))
    h = np.asarray(x)
    for li, w in enumerate(weights):
        with t.phase("inter_dpu" if li else "cpu_dpu"):
            wc, m = pad_chunks(w, grid.n_banks)
            dw = sync(grid.to_banks(wc))           # weight distribution
            dh = sync(grid.broadcast(h))           # input vector broadcast
        with t.phase("dpu"):
            out = sync(f(dw, dh))
        with t.phase("dpu_cpu"):
            h = grid.from_banks(out).reshape(-1)[:m]
    return h, t.times


# -- chunked phases (pipelined runtime) --------------------------------------
# The per-layer host round-trip that pim() reproduces (gather layer output,
# re-broadcast as next input) would serialize the pipeline — each layer
# depends on the previous one.  The chunked adaptation (DESIGN.md §4) keeps
# chunks independent by replicating the hidden layers: split broadcasts every
# non-final weight and enqueues the full replicated forward pass (each bank
# redundantly computes the small hidden state, like BS replicates its array),
# then only the *final* layer's rows are chunked across banks.  All of this
# is async enqueue — nothing blocks until retrieve.

@functools.cache
def _local(grid: BankGrid):
    return jax.jit(grid.bank_local(
        lambda wb, hb: jnp.maximum(matvec(wb, hb), 0),
        in_specs=(P(AXIS), P())))


# The weight stack is the residency candidate (DESIGN.md §12): the hidden
# layers stay broadcast on the banks as device constants and the final
# layer's row chunks are the pipeline's chunks, so a warm hit pays only the
# tiny input broadcast + the replicated hidden forward pass per request.

def _split_resident(grid, n_chunks, weights):
    dws = [grid.broadcast(np.asarray(w)) for w in weights[:-1]]
    chunks, m = tx.split_chunks(np.asarray(weights[-1]), n_chunks)
    return {"m": m, "per": chunks[0].shape[0], "dws": dws}, chunks


def _split_varying(grid, n_chunks, res_meta, weights, x):
    h = grid.broadcast(np.asarray(x))
    for dw in res_meta["dws"]:
        h = jnp.maximum(matvec(dw, h), 0)
    return {"m": res_meta["m"], "per": res_meta["per"], "dh": h}, None


def _split(grid, n_chunks, weights, x):
    res_meta, chunks = _split_resident(grid, n_chunks, weights)
    meta, _ = _split_varying(grid, n_chunks, res_meta, weights, x)
    return meta, chunks


def _scatter(grid, meta, chunk):
    wc, _ = pad_chunks(chunk, grid.n_banks)
    return grid.to_banks(wc)


def _compute(grid, meta, dw):
    return _local(grid)(dw, meta["dh"])


def _retrieve(grid, meta, out):
    return grid.from_banks(out).reshape(-1)[:meta["per"]]


def _merge(grid, meta, parts):
    return np.concatenate(parts)[:meta["m"]]


chunked = register_chunked(ChunkedWorkload(
    "MLP", _split, _scatter, _compute, _retrieve, _merge,
    resident_args=(0,), split_resident=_split_resident,
    split_varying=_split_varying))
