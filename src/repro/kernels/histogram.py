"""Histogram Pallas kernel (PrIM §4.11 HST-S, TPU-native).

PrIM's HST-S gives each tasklet a private WRAM histogram merged at a barrier;
HST-L shares one histogram behind a mutex.  TPUs have no mutexes (noted in
DESIGN.md §2), so the TPU-native form is HST-S taken to its limit: each grid
block builds bin counts with a one-hot matmul (MXU-friendly bincount) and
accumulates into the output block, which all grid steps revisit sequentially.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _hist_kernel(x_ref, o_ref, *, nbins):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    v = x_ref[...]                                  # (1, block) int32
    b = v.shape[-1]
    clipped = jnp.clip(v, 0, nbins - 1).reshape(b, 1)
    bins = jax.lax.broadcasted_iota(jnp.int32, (b, nbins), 1)
    onehot = (clipped == bins).astype(jnp.int32)    # (block, nbins)
    o_ref[...] += jnp.sum(onehot, axis=0, keepdims=True)


def histogram(values, nbins: int, *, block: int = 4096,
              interpret: bool = False):
    """values: 1-D int32 in [0, nbins); len % block == 0 (ops.py pads)."""
    (n,) = values.shape
    assert n % block == 0
    nb = n // block
    out = pl.pallas_call(
        functools.partial(_hist_kernel, nbins=nbins),
        grid=(nb,),
        in_specs=[pl.BlockSpec((1, block), lambda i: (0, i))],
        out_specs=pl.BlockSpec((1, nbins), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, nbins), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(values.reshape(1, n))
    return out[0]
