"""Jit'd public wrappers for all Pallas kernels.

These handle arbitrary shapes (pad → kernel → slice), dtype policy, and the
interpret-mode switch (CPU validation vs TPU execution).  The model stack and
the PrIM suite call only these, never the raw kernels.

``KERNEL_BACKEND``: "pallas" (default on TPU), "interpret" (CPU validation),
or "ref" (pure-jnp oracles — used inside shard_map'd model code where a
kernel isn't profitable or available).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import flash_attention as _fa
from . import gemv as _gemv
from . import histogram as _hist
from . import mamba_scan as _mamba
from . import moe_gmm as _gmm
from . import reduce as _red
from . import ref
from . import scan as _scan
from . import spmv as _spmv

#: resolved on first use, never at import: asking JAX for its backend
#: initialises the device, and on a TPU host that claims the chip
_BACKEND: str | None = None


def set_backend(name: str) -> None:
    global _BACKEND
    assert name in ("pallas", "interpret", "ref")
    if name == "interpret" and jax.default_backend() == "tpu":
        raise ValueError("interpret mode is for CPU validation; a TPU runs "
                         "the compiled kernels")
    _BACKEND = name


def get_backend() -> str:
    global _BACKEND
    if _BACKEND is None:
        _BACKEND = "interpret" if jax.default_backend() == "cpu" \
            else "pallas"
    return _BACKEND


def _interp() -> bool:
    return get_backend() == "interpret"


def _pad_to(x, mult: int, axis: int):
    pad = (-x.shape[axis]) % mult
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


# -- attention ---------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k"))
def attention(q, k, v, *, causal: bool = True, window: int | None = None,
              block_q: int = 128, block_k: int = 128):
    """GQA flash attention; q (B,H,S,D), k/v (B,KVH,T,D), any S/T/D."""
    if get_backend() == "ref":
        return ref.attention(q, k, v, causal=causal, window=window)
    B, H, S, D = q.shape
    T = k.shape[2]
    bq = min(block_q, max(8, 1 << (S - 1).bit_length()))
    bk = min(block_k, max(8, 1 << (T - 1).bit_length()))
    scale = float(D) ** -0.5
    qp = _pad_to(_pad_to(q, bq, 2), 128, 3)
    kp = _pad_to(_pad_to(k, bk, 2), 128, 3)
    vp = _pad_to(_pad_to(v, bk, 2), 128, 3)
    out = _fa.flash_attention(qp, kp, vp, causal=causal, window=window,
                              scale=scale, block_q=bq, block_k=bk,
                              s_valid=S, t_valid=T, interpret=_interp())
    return out[:, :, :S, :D]


def decode_attention(q, k_cache, v_cache, lengths, *, window=None,
                     impl: str = "ref"):
    """Decode path: memory-bound KV gather — pure-jnp is the right shape for
    this (no kernel win on a 1-token matvec).  impl="grouped" is the §Perf
    fast path (no KV repeat / no f32 cache copy)."""
    f = ref.decode_attention_grouped if impl == "grouped" \
        else ref.decode_attention
    return f(q, k_cache, v_cache, lengths, window=window)


# -- gemv ---------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("block_m", "block_n"))
def gemv(a, x, *, block_m: int = 128, block_n: int = 512):
    if get_backend() == "ref":
        return ref.gemv(a, x)
    m, n = a.shape
    bm = min(block_m, max(8, 1 << (m - 1).bit_length()))
    bn = min(block_n, max(128, 1 << (n - 1).bit_length()))
    ap = _pad_to(_pad_to(a, bm, 0), bn, 1)
    xp = _pad_to(x, bn, 0)
    y = _gemv.gemv(ap, xp, block_m=bm, block_n=bn, interpret=_interp())
    return y[:m]


# -- reduce / scan -------------------------------------------------------------

def _block_1d(n: int, block: int) -> int:
    """Block of a 1-D reduce/scan: the smaller of ``block`` and the power of
    two at or above ``n``, but never under ``MIN_BLOCK`` (whole (rows, 128)
    tiles; the zero padding is sum- and scan-safe)."""
    return max(_red.MIN_BLOCK, min(block, 1 << (n - 1).bit_length()))


@functools.partial(jax.jit, static_argnames=("block",))
def reduce_sum(x, *, block: int = 4096):
    if get_backend() == "ref":
        return ref.reduce_sum(x)
    b = _block_1d(x.shape[0], block)
    return _red.reduce_sum(_pad_to(x, b, 0), block=b, interpret=_interp())


@functools.partial(jax.jit, static_argnames=("block",))
def scan_inclusive(x, *, block: int = 4096):
    if get_backend() == "ref":
        return ref.scan_inclusive(x)
    n = x.shape[0]
    b = _block_1d(n, block)
    return _scan.scan_inclusive(_pad_to(x, b, 0), block=b,
                                interpret=_interp())[:n]


@functools.partial(jax.jit, static_argnames=("block",))
def scan_exclusive(x, *, block: int = 4096):
    return scan_inclusive(x, block=block) - x


# -- histogram ------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("nbins", "block"))
def histogram(values, nbins: int, *, block: int = 4096):
    if get_backend() == "ref":
        return ref.histogram(values, nbins)
    n = values.shape[0]
    b = min(block, max(128, 1 << (n - 1).bit_length()))
    pad = (-n) % b
    vp = jnp.pad(values, (0, pad), constant_values=-1)  # -1 ⇒ clipped to bin 0
    h = _hist.histogram(vp, nbins, block=b, interpret=_interp())
    return h.at[0].add(-pad) if pad else h


# -- spmv -----------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("block_rows",))
def spmv_ell(vals, cols, x, *, block_rows: int = 128):
    if get_backend() == "ref":
        return ref.spmv_ell(vals, cols, x)
    rows = vals.shape[0]
    br = min(block_rows, max(8, 1 << (rows - 1).bit_length()))
    vp = _pad_to(vals, br, 0)
    cp = jnp.pad(cols, ((0, vp.shape[0] - rows), (0, 0)), constant_values=-1)
    y = _spmv.spmv_ell(vp, cp, x, block_rows=br, interpret=_interp())
    return y[:rows]


# -- moe grouped matmul ----------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("block_c", "block_f", "block_d"))
def moe_gmm(xg, w, counts, *, block_c: int = 128, block_f: int = 512,
            block_d: int = 512):
    if get_backend() == "ref":
        return ref.moe_gmm(xg, w, counts)
    E, C, d = xg.shape
    f = w.shape[-1]
    bc = min(block_c, max(8, 1 << (C - 1).bit_length()))
    bd = min(block_d, max(128, 1 << (d - 1).bit_length()))
    bf = min(block_f, max(128, 1 << (f - 1).bit_length()))
    xp = _pad_to(_pad_to(xg, bc, 1), bd, 2)
    wp = _pad_to(_pad_to(w, bd, 1), bf, 2)
    y = _gmm.moe_gmm(xp, wp, counts.astype(jnp.int32), block_c=bc,
                     block_f=bf, block_d=bd, interpret=_interp())
    return y[:, :C, :f]


# -- mamba / ssd scan -------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(x, a, b, c, *, chunk: int = 128):
    if get_backend() == "ref":
        return ref.ssd_scan(x, a, b, c)
    B, S, H, P = x.shape
    N = b.shape[-1]
    ch = min(chunk, max(8, 1 << (S - 1).bit_length()))
    pad = (-S) % ch
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        a = jnp.pad(a, ((0, 0), (0, pad), (0, 0)), constant_values=1.0)
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0)))
    y, h = _mamba.ssd_scan(x, a, b, c, chunk=ch, interpret=_interp())
    return y[:, :S], h
