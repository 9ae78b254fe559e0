"""Reduction Pallas kernel (PrIM §4.12 RED).

The PrIM version does per-tasklet local sums then a tree merge; on TPU the
grid is sequential, so the "tree" collapses into one carried accumulator.
The accumulator is a vector-shaped ``(8, 128)`` output block that every grid
step revisits (the TPU stores vectors, not scalars, to VMEM): each block of
``(rows, 128)`` folds into it with elementwise adds, and the 1024 partials
are summed after the kernel.  Mirrors the paper's finding that the
single-accumulator variant beats tree variants when merge cost dominates.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES, SUBLANES = 128, 8
#: smallest block (elements): whole (8, 128) tiles of 32-bit values, with
#: room for the (16, 128) / (32, 128) tiles of 16- and 8-bit inputs
MIN_BLOCK = 32 * LANES


def acc_dtype(dtype):
    """Accumulator dtype: float32 for floats, the input's own for ints."""
    return jnp.float32 if jnp.issubdtype(dtype, jnp.floating) else dtype


def _reduce_kernel(x_ref, o_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].astype(o_ref.dtype)              # (rows, 128)
    o_ref[...] += x.reshape(-1, SUBLANES, LANES).sum(axis=0)


def reduce_sum(x, *, block: int = 4096, interpret: bool = False):
    """Sum of a 1-D array; len(x) % block == 0 and block % MIN_BLOCK == 0
    (ops.py pads).  Floats accumulate in float32 and return it."""
    (n,) = x.shape
    assert n % block == 0 and block % MIN_BLOCK == 0, (n, block)
    rows = block // LANES
    acc = acc_dtype(x.dtype)
    partials = pl.pallas_call(
        _reduce_kernel,
        grid=(n // block,),
        in_specs=[pl.BlockSpec((rows, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((SUBLANES, LANES), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((SUBLANES, LANES), acc),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(x.reshape(n // LANES, LANES))
    return jnp.sum(partials, dtype=acc)
