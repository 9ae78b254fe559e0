"""SpMV Pallas kernel, ELL format (PrIM §4.3, TPU-native layout).

The PrIM SpMV uses CSR with per-row fine-grained DMA.  CSR's ragged rows are
hostile to the MXU/VPU, so the TPU adaptation re-lays the matrix out as
padded ELL (rows × max_nnz, col==-1 padding) — the "coarse-grained DMA"
choice of the paper's PR-4, since every row fetch becomes a dense tile.
The x gather is served from a fully VMEM-resident x block (fine-grained
WRAM-side gather — paper Key Obs. 3: WRAM access pattern doesn't matter).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _spmv_kernel(vals_ref, cols_ref, x_ref, o_ref):
    vals = vals_ref[...].astype(jnp.float32)     # (br, k)
    cols = cols_ref[...]                         # (br, k) int32
    x = x_ref[...]                               # (1, n)
    gathered = x[0, jnp.clip(cols, 0)].astype(jnp.float32)
    contrib = jnp.where(cols >= 0, vals * gathered, 0.0)
    o_ref[...] = jnp.sum(contrib, axis=1, keepdims=True).astype(o_ref.dtype)


def spmv_ell(vals, cols, x, *, block_rows: int = 128,
             interpret: bool = False):
    """vals/cols: (rows, k) ELL; x: (n,). rows % block_rows == 0."""
    rows, k = vals.shape
    (n,) = x.shape
    assert rows % block_rows == 0
    y = pl.pallas_call(
        _spmv_kernel,
        grid=(rows // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, k), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, k), lambda i: (i, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, 1), vals.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(vals, cols, x.reshape(1, n))
    return y[:, 0]
