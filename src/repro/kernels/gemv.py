"""Blocked GEMV Pallas kernel (PrIM §4.2 / MLP §4.9 hot loop, TPU-native).

The PrIM DPU implementation streams row blocks MRAM→WRAM and multiply-
accumulates per tasklet.  TPU adaptation: rows tile the parallel grid axis,
the reduction (n) axis is innermost/sequential with an f32 VMEM accumulator —
block sizes default to MXU-aligned (128, 512).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gemv_kernel(a_ref, x_ref, o_ref, acc_ref, *, nn):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...].astype(jnp.float32)          # (bm, bn)
    x = x_ref[...].astype(jnp.float32)          # (1, bn)
    acc_ref[...] += jax.lax.dot_general(
        a, x, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(j == nn - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def gemv(a, x, *, block_m: int = 128, block_n: int = 512,
         interpret: bool = False):
    """y = A @ x.  a: (m, n), x: (n,) — m % block_m == n % block_n == 0
    (ops.py pads arbitrary shapes)."""
    m, n = a.shape
    assert m % block_m == 0 and n % block_n == 0, (m, n, block_m, block_n)
    nm, nn = m // block_m, n // block_n
    x2 = x.reshape(1, n)
    kernel = functools.partial(_gemv_kernel, nn=nn)
    y = pl.pallas_call(
        kernel,
        grid=(nm, nn),
        in_specs=[
            pl.BlockSpec((block_m, block_n), lambda i, j: (i, j)),
            pl.BlockSpec((1, block_n), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, 1), a.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(a, x2)
    return y[:, 0]
