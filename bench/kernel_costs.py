"""Operations and bytes that each kernel of the served path needs, from its
shapes alone, and the roofline share they give against a kernel's device
time.

Bytes are what the algorithm must move through HBM once: each operand read
and each result written, at its dtype's size.  Operations count a
multiply-add as two.  The least time a chip could take is the larger of
bytes over peak bandwidth and operations over peak rate; the roofline share
is that least time over the measured device time.
"""
from __future__ import annotations

F32 = I32 = 4


def gemv(rows: int, cols: int) -> tuple[float, float]:
    """``y = A x``: A (rows, cols) float32 read once, x read, y written."""
    return 2.0 * rows * cols, F32 * (rows * cols + cols + rows)


def column(name: str, n: int, bins: int = 256) -> tuple[float, float]:
    """One pass of a column workload over ``n`` int32 elements."""
    if name == "VA":                         # read a, b; write a + b
        return float(n), 3.0 * I32 * n
    if name == "HST":                        # read x; write the bins
        return float(n), I32 * (n + bins)
    raise KeyError(name)


def least_time_s(flops: float, nbytes: float, peaks: dict) -> float:
    """Bytes at peak bandwidth or operations at the bf16 peak rate,
    whichever is longer."""
    return max(nbytes / peaks["hbm_bytes_per_s"],
               flops / peaks["bf16_flops_per_s"])


def roofline_share(kernel_time_s: float, least_s: float):
    """Percent of the roofline a kernel reached; None when the trace holds
    no time for it (never 0)."""
    if not kernel_time_s or kernel_time_s <= 0 or least_s <= 0:
        return None
    return 100.0 * least_s / kernel_time_s
