"""Prefix-sum Pallas kernel (PrIM §4.13 SCAN-RSS, on-chip form).

The paper's Reduce-Scan-Scan decomposes the array into per-DPU chunks: local
reduce → host scans the per-chunk totals → local scan + offset.  On TPU the
sequential grid makes the middle step a carried total: each block writes
``carry + scan(block)`` and bumps the carry by the block total — a single
pass instead of the paper's 3·N+1 accesses (recorded as a beyond-paper win in
EXPERIMENTS.md §Perf for the SCAN benchmark).

A block is a ``(rows, 128)`` tile.  The in-block scan is two log-step
shift-and-add passes (Hillis-Steele), built from lane/sublane rolls and
masks that the TPU lowers natively: first along the 128 lanes of every row,
then over the row totals down the sublanes.  The carry is kept as a vector
block, every element holding the running total.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .reduce import LANES, MIN_BLOCK, SUBLANES, acc_dtype


def _shift_add_scan(y, axis: int):
    """Inclusive scan of ``y`` along ``axis`` in log2(len) roll+add steps."""
    n = y.shape[axis]
    idx = jax.lax.broadcasted_iota(jnp.int32, y.shape, axis)
    k = 1
    while k < n:
        y = y + jnp.where(idx >= k, pltpu.roll(y, k, axis), 0).astype(y.dtype)
        k *= 2
    return y


def _scan_kernel(x_ref, o_ref, carry_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    rows = x_ref.shape[0]
    y = _shift_add_scan(x_ref[...].astype(carry_ref.dtype), 1)  # per row
    row_tot = jnp.broadcast_to(y[:, LANES - 1:], y.shape)
    incl = _shift_add_scan(row_tot, 0)              # running row totals
    carry = carry_ref[0:1, :]
    o_ref[...] = (y + (incl - row_tot) + carry).astype(o_ref.dtype)
    carry_ref[...] = jnp.broadcast_to(carry + incl[rows - 1:, :],
                                      carry_ref.shape)


def scan_inclusive(x, *, block: int = 4096, interpret: bool = False):
    """Inclusive prefix sum of a 1-D array; len(x) % block == 0 and
    block % MIN_BLOCK == 0 (ops.py pads)."""
    (n,) = x.shape
    assert n % block == 0 and block % MIN_BLOCK == 0, (n, block)
    rows = block // LANES
    out = pl.pallas_call(
        _scan_kernel,
        grid=(n // block,),
        in_specs=[pl.BlockSpec((rows, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n // LANES, LANES), x.dtype),
        scratch_shapes=[pltpu.VMEM((SUBLANES, LANES), acc_dtype(x.dtype))],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(x.reshape(n // LANES, LANES))
    return out.reshape(n)


def scan_exclusive(x, **kw):
    return scan_inclusive(x, **kw) - x
