"""Grouped (MoE expert) matmul Pallas kernel.

Capacity-grouped tokens (E, C, d) hit per-expert weights (E, d, f).  Grid is
(experts, token-tiles, f-tiles, d-tiles) with an f32 VMEM accumulator over the
d axis; tiles whose token rows are entirely beyond the expert's live count are
masked at the end. The MoE layer (models/moe.py) routes/permutes tokens, then
calls this for both the up and down projections.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gmm_kernel(cnt_ref, x_ref, w_ref, o_ref, acc_ref, *, nd, bc):
    i = pl.program_id(1)
    kd = pl.program_id(3)

    @pl.when(kd == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[0].astype(jnp.float32)             # (bc, bd)
    w = w_ref[0].astype(jnp.float32)             # (bd, bf)
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    row = i * bc + jax.lax.broadcasted_iota(jnp.int32, acc_ref.shape, 0)
    live = row < cnt_ref[0]

    @pl.when(kd == nd - 1)
    def _done():
        o_ref[0] = jnp.where(live, acc_ref[...], 0.0).astype(o_ref.dtype)


def moe_gmm(xg, w, counts, *, block_c: int = 128, block_f: int = 512,
            block_d: int = 512, interpret: bool = False):
    """xg: (E, C, d); w: (E, d, f); counts: (E,) int32.
    C % block_c == d % block_d == f % block_f == 0 (ops.py pads)."""
    E, C, d = xg.shape
    _, _, f = w.shape
    assert C % block_c == 0 and d % block_d == 0 and f % block_f == 0
    nc, nf, nd = C // block_c, f // block_f, d // block_d
    kernel = functools.partial(_gmm_kernel, nd=nd, bc=block_c)
    return pl.pallas_call(
        kernel,
        grid=(E, nc, nf, nd),
        in_specs=[
            pl.BlockSpec((1,), lambda e, i, j, kd: (e,)),
            pl.BlockSpec((1, block_c, block_d),
                         lambda e, i, j, kd: (e, i, kd)),
            pl.BlockSpec((1, block_d, block_f),
                         lambda e, i, j, kd: (e, kd, j)),
        ],
        out_specs=pl.BlockSpec((1, block_c, block_f),
                               lambda e, i, j, kd: (e, i, j)),
        out_shape=jax.ShapeDtypeStruct((E, C, f), xg.dtype),
        scratch_shapes=[pltpu.VMEM((block_c, block_f), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(counts, xg, w)
