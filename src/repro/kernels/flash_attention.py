"""Flash attention Pallas kernel (TPU target): GQA + causal + sliding window.

Tiling (the paper's MRAM→WRAM staging discipline, PR-1/PR-3 applied to HBM→VMEM):
  grid = (B, H, nq, nk); the kv axis is innermost/sequential, carrying the
  online-softmax state (m, l, acc) in VMEM scratch across kv blocks.
  Blocks: q (bq, D), k/v (bk, D) — D padded to a lane multiple by ops.py;
  all matmul dims are 128-aligned for the MXU when bq=bk=128.

Sliding-window support makes this the sub-quadratic path required by
`long_500k` prefill for SWA archs; fully-masked kv blocks are skipped with
``pl.when`` (block-level causal/window pruning).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale, causal, window, bq, bk, nk, s_valid, t_valid, t_total):
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # global positions (q offset aligns the last valid q with the last valid k)
    qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) \
        + (t_valid - s_valid)
    kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)

    # block-level pruning: skip kv blocks fully outside the causal/window band
    q_max = i * bq + bq - 1 + (t_valid - s_valid)
    q_min = i * bq + (t_valid - s_valid)
    k_min = j * bk
    k_max = j * bk + bk - 1
    live = jnp.bool_(True)
    if causal:
        live &= k_min <= q_max
    if window is not None:
        live &= k_max > q_min - window

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = kpos < t_valid
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_new
        v = v_ref[0, 0].astype(jnp.float32)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv

    @pl.when(j == nk - 1)
    def _finalize():
        lsum = l_ref[...]
        l_safe = jnp.where(lsum == 0.0, 1.0, lsum)
        o_ref[0, 0, :, :] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int | None = None,
                    scale: float | None = None, block_q: int = 128,
                    block_k: int = 128, s_valid: int | None = None,
                    t_valid: int | None = None, interpret: bool = False):
    """q: (B, H, S, D); k, v: (B, KVH, T, D). S, T multiples of block sizes
    and D lane-aligned — ops.py pads arbitrary shapes before calling this."""
    B, H, S, D = q.shape
    _, KVH, T, _ = k.shape
    assert H % KVH == 0 and S % block_q == 0 and T % block_k == 0
    group = H // KVH
    nq, nk = S // block_q, T // block_k
    s_valid = S if s_valid is None else s_valid
    t_valid = T if t_valid is None else t_valid
    scale = float(scale) if scale is not None else float(D) ** -0.5

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        bq=block_q, bk=block_k, nk=nk, s_valid=s_valid, t_valid=t_valid,
        t_total=T)

    grid = (B, H, nq, nk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, i, j: (b, h // group, j, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, i, j: (b, h // group, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
