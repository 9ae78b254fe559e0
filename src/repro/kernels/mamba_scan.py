"""Chunked selective-SSM scan Pallas kernel (SSD / Mamba-2 form).

Hardware adaptation (DESIGN.md §2): Mamba's elementwise recurrence is a poor
fit for the MXU, so we use the SSD chunked formulation — within a chunk the
recurrence becomes three matmuls against a lower-triangular decay matrix
(all exponents ≤ 0 ⇒ numerically stable), and the cross-chunk carry is an
(N, P) state held in VMEM scratch across the sequential chunk axis:

  h_t = a_t h_{t-1} + b_t ⊗ x_t ;   y_t = c_t · h_t
  y   = ((C Bᵀ) ∘ D) X  +  exp(cum) · (C h0) ;  D[t,s] = exp(cum_t − cum_s)

Grid: (B, H, n_chunks) — chunks sequential, carrying h.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, a_ref, b_ref, c_ref, y_ref, hout_ref, h_ref, *,
                L, nchunks):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0, :, 0, :].astype(jnp.float32)       # (L, P)
    a = a_ref[0, :, 0].astype(jnp.float32)          # (L,)
    b = b_ref[0].astype(jnp.float32)                # (L, N)
    c = c_ref[0].astype(jnp.float32)                # (L, N)

    la = jnp.log(a).reshape(L, 1)
    cum = jnp.cumsum(la, axis=0)                    # (L, 1) inclusive
    diff = cum - cum.reshape(1, L)                  # cum[t] - cum[s]
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    decay = jnp.where(t_idx >= s_idx, jnp.exp(diff), 0.0)

    g = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (L, L)
    y_intra = jax.lax.dot_general(g * decay, x, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    h0 = h_ref[...]                                 # (N, P)
    y_carry = jnp.exp(cum) * jax.lax.dot_general(
        c, h0, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    y_ref[0, :, 0, :] = (y_intra + y_carry).astype(y_ref.dtype)

    w = jnp.exp(cum[L - 1] - cum)                   # (L, 1)
    h_ref[...] = jnp.exp(cum[L - 1, 0]) * h0 + jax.lax.dot_general(
        b * w, x, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ci == nchunks - 1)
    def _fin():
        hout_ref[0, 0] = h_ref[...].astype(hout_ref.dtype)


def ssd_scan(x, a, b, c, *, chunk: int = 128, interpret: bool = False):
    """x: (B, S, H, P); a: (B, S, H); b, c: (B, S, N).  S % chunk == 0.
    Returns y: (B, S, H, P) and final state (B, H, N, P)."""
    B, S, H, P = x.shape
    N = b.shape[-1]
    assert S % chunk == 0
    nchunks = S // chunk
    kernel = functools.partial(_ssd_kernel, L=chunk, nchunks=nchunks)
    y, h = pl.pallas_call(
        kernel,
        grid=(B, H, nchunks),
        in_specs=[
            pl.BlockSpec((1, chunk, 1, P), lambda bi, hi, ci: (bi, ci, hi, 0)),
            pl.BlockSpec((1, chunk, 1), lambda bi, hi, ci: (bi, ci, hi)),
            pl.BlockSpec((1, chunk, N), lambda bi, hi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, chunk, N), lambda bi, hi, ci: (bi, ci, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, 1, P), lambda bi, hi, ci: (bi, ci, hi, 0)),
            pl.BlockSpec((1, 1, N, P), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, H, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, a, b, c)
    return y, h
