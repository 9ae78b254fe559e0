"""Plain references for the PrIM workloads the benchmark serves, and the
generators of their operands.

Nothing here imports the program.  Each reference is the workload's
semantics written directly in numpy (paper §4, PrIM suite), and each
generator draws one request's operands at the sizes the configuration
gives (PrIM's Table 3 datasets) from the seed's generator:

* VA   ``a + b`` in int32 (wrapping), over int32 uniform in [0, 2**31 - 1),
       as PrIM's host code draws them (``rand()``)
* HST  a ``hst_bins``-bin histogram of ``hst_pixels`` pixels, each pixel
       value one bin, uniform over the bins
* GEMV ``A @ x`` over float32 N(0, 1)

``column_control`` and ``gemv_control`` are the same semantics computed one
precision lower, the step a later change might be tempted to take: float32
arithmetic for the integer workloads (exact only below 2**24, which VA's
operands pass), and for GEMV's float32 at the ``highest`` precision the
three-pass bfloat16 product that ``high`` runs.
"""
from __future__ import annotations

import numpy as np

INT32_MAX = (1 << 31) - 1


def make_column_args(name: str, rng: np.random.Generator, cfg: dict):
    """Operands of one request of column workload ``name``."""
    if name == "VA":
        n = int(cfg["va_elements"])
        return (rng.integers(0, INT32_MAX, n, dtype=np.int32),
                rng.integers(0, INT32_MAX, n, dtype=np.int32))
    if name == "HST":
        bins = int(cfg["hst_bins"])
        return (rng.integers(0, bins, int(cfg["hst_pixels"]),
                             dtype=np.int32), bins)
    raise KeyError(f"no column workload {name!r}")


def column_elements(name: str, cfg: dict) -> int:
    """Elements one request of ``name`` streams through its compute phase."""
    return int(cfg["va_elements"] if name == "VA" else cfg["hst_pixels"])


def gemv_shape(cfg: dict) -> tuple[int, int]:
    return int(cfg["gemv_rows"]), int(cfg["gemv_cols"])


def column_ref(name: str, args):
    """Exact result of column workload ``name`` on ``args``."""
    if name == "VA":
        a, b = args
        return (a.astype(np.int64) + b).astype(np.int32)
    if name == "HST":
        x, bins = args
        return np.bincount(x, minlength=bins).astype(np.int32)
    raise KeyError(name)


def column_control(name: str, args):
    """``column_ref`` computed in float32 on the device (the control)."""
    import jax.numpy as jnp
    f = [jnp.asarray(a, jnp.float32) if isinstance(a, np.ndarray) else a
         for a in args]
    if name == "VA":
        out = np.asarray(f[0] + f[1]).astype(np.int64).astype(np.int32)
    elif name == "HST":
        out = np.asarray(jnp.zeros(f[1], jnp.float32).at[
            f[0].astype(jnp.int32)].add(1.0)).astype(np.int32)
    else:
        raise KeyError(name)
    return out


def same_answer(out, ref) -> bool:
    """Exact equality of one answer: shape and every element."""
    out, ref = np.asarray(out), np.asarray(ref)
    return out.shape == ref.shape and bool(np.array_equal(
        out.astype(np.int64), ref.astype(np.int64)))


def _row_block(a) -> int:
    """Rows per block of the float64 checks: 16 Mi elements (128 MB)."""
    return max(1, (1 << 24) // max(1, a.shape[1]))


def gemv_error(a: np.ndarray, xs: np.ndarray, ys: np.ndarray) -> float:
    """Largest error of the answers ``ys`` (k, m) to ``a @ xs.T``, each
    element measured against its rounding scale ``sum_j |a_ij x_j|`` (the
    bound a float sum of those terms is held to).  float64, in row blocks."""
    xs = np.asarray(xs, np.float64)
    ys = np.asarray(ys)
    block = _row_block(a)
    worst = 0.0
    for r0 in range(0, a.shape[0], block):
        blk = np.asarray(a[r0:r0 + block], np.float64)
        ref = blk @ xs.T                                   # (rows, k)
        scale = np.abs(blk) @ np.abs(xs).T
        err = np.abs(ys[:, r0:r0 + block].T - ref) / np.maximum(scale, 1e-30)
        worst = max(worst, float(err.max(initial=0.0)))
    return worst


def bf16x3_matmul(a, b):
    """``a @ b`` as the three bfloat16 passes of ``precision='high'``:
    hi*hi + hi*lo + lo*hi, each product exact in float32.  A TPU runs that
    natively; elsewhere it is written out (on a TPU the written-out split
    would not survive: XLA folds the float32 -> bfloat16 -> float32 round
    trip, leaving lo = 0 and one pass)."""
    import jax
    import jax.numpy as jnp
    if jax.default_backend() == "tpu":
        return jnp.matmul(jnp.asarray(a, jnp.float32),
                          jnp.asarray(b, jnp.float32),
                          precision=jax.lax.Precision.HIGH)

    def split(x):
        hi = x.astype(jnp.bfloat16)
        lo = (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)
        return hi, lo

    def mm(p, q):
        return jnp.matmul(p, q, preferred_element_type=jnp.float32,
                          precision=jax.lax.Precision.DEFAULT)

    ah, al = split(jnp.asarray(a, jnp.float32))
    bh, bl = split(jnp.asarray(b, jnp.float32))
    return mm(ah, bh) + mm(ah, bl) + mm(al, bh)


def gemv_control(a, xs) -> np.ndarray:
    """``a @ xs.T`` at three bfloat16 passes, in row blocks: (k, m).  ``a``
    may already sit on the device."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(bf16x3_matmul)
    xt = jnp.asarray(np.asarray(xs, np.float32).T)
    block = _row_block(a)
    parts = [np.asarray(f(jnp.asarray(a[r0:r0 + block]), xt))
             for r0 in range(0, a.shape[0], block)]
    return np.concatenate(parts).T
