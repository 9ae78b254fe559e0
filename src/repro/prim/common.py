"""Shared machinery for the PrIM workload implementations.

Every PrIM benchmark exposes:
  * ``ref(...)``            — gold semantics (numpy/jnp, single device)
  * ``pim(grid, ...)``      — the paper's DPU decomposition on a BankGrid:
                              parallel CPU→DPU scatter, bank-local kernel
                              phase(s), explicit exchange phase(s), DPU→CPU
                              retrieve.  Returns (result, PhaseTimes).
and mirrors the paper's §4 description of its DPU implementation.

``PhaseTimes`` reproduces the paper's stacked-bar breakdown:
CPU-DPU / DPU / Inter-DPU / DPU-CPU.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

#: Matmul precision of every compute phase: full float32.  A TPU runs a
#: float32 matmul at DEFAULT precision as bfloat16 passes, which misses the
#: registry's float tolerance and decode's token parity; the CPU computes
#: float32 exactly either way.
PRECISION = jax.lax.Precision.HIGHEST


def matvec(a, x):
    """``a @ x`` at :data:`PRECISION` — the one matmul of the compute
    phases (GEMV, GEMV-B/G, MLP)."""
    return jnp.matmul(a, x, precision=PRECISION)


@dataclasses.dataclass
class PhaseTimes:
    """Seconds per paper phase.  A serialized ``pim()`` syncs the device at
    every boundary, so each bucket holds its phase's device time.  The
    chunk pipeline (``runtime/pipeline.py``) never syncs between phases, so
    there they are host time: ``cpu_dpu`` issuing scatters, ``dpu_cpu``
    waiting for chunks and copying them out, ``inter_dpu`` merging."""

    cpu_dpu: float = 0.0
    #: in the pipeline the asynchronous enqueue of the compute phase
    #: (``launch``), not the device's time: that is in the device trace
    dpu: float = 0.0
    inter_dpu: float = 0.0
    dpu_cpu: float = 0.0

    @property
    def total(self) -> float:
        return self.cpu_dpu + self.dpu + self.inter_dpu + self.dpu_cpu

    def row(self, name: str, n_banks: int) -> dict:
        return {"benchmark": name, "banks": n_banks,
                "cpu_dpu_s": self.cpu_dpu, "dpu_s": self.dpu,
                "inter_dpu_s": self.inter_dpu, "dpu_cpu_s": self.dpu_cpu,
                "total_s": self.total}


class PhaseTimer:
    """Accumulates wall time per phase with device sync at boundaries."""

    def __init__(self):
        self.times = PhaseTimes()

    class _Span:
        def __init__(self, outer, phase):
            self.outer, self.phase = outer, phase

        def __enter__(self):
            self.t0 = time.perf_counter()
            return self

        def __exit__(self, *exc):
            dt = time.perf_counter() - self.t0
            setattr(self.outer.times, self.phase,
                    getattr(self.outer.times, self.phase) + dt)

    def phase(self, name: str) -> "_Span":
        return self._Span(self, name)


def pad_chunks(x: np.ndarray, n_banks: int, fill=0) -> tuple[np.ndarray, int]:
    """Split leading axis into n_banks equal chunks (paper: linear chunk
    assignment, chunk i → DPU i), padding the tail."""
    x = np.asarray(x)
    n = x.shape[0]
    per = -(-n // n_banks)
    pad = per * n_banks - n
    if pad:
        x = np.concatenate([x, np.full((pad,) + x.shape[1:], fill, x.dtype)])
    return x.reshape(n_banks, per, *x.shape[1:]), n


def sync(x):
    jax.block_until_ready(x)
    return x


# ---------------------------------------------------------------------------
# chunked phase interface (consumed by repro.runtime.pipeline)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ChunkedWorkload:
    """A PrIM workload factored into pipeline-composable phase callables.

    ``pim()`` stays the faithful serialized baseline — a hard sync at every
    phase boundary, whole problem at once, exactly as the UPMEM SDK forces.
    ``chunked`` re-exposes the *same* decomposition as independent phases
    over input chunks so the runtime pipeline can issue chunk k+1's scatter
    while chunk k's bank-local phase is still in flight.

    Contract: every chunk from ``split`` has the same shape (``split_chunks``
    pads the tail), so one compiled bank-local phase serves all chunks of
    all same-shaped requests.  ``scatter``/``compute`` must only *enqueue*
    device work (no ``block_until_ready``); ``retrieve`` blocks.

      split(grid, n_chunks, *args) -> (meta, [chunk, ...])    host-side
      scatter(grid, meta, chunk)   -> device bufs             CPU→bank
      compute(grid, meta, bufs)    -> device outs             bank-local
      retrieve(grid, meta, outs)   -> host partial            bank→CPU
      merge(grid, meta, parts)     -> result                  host-side

    Residency extension (DESIGN.md §12): a workload whose dominant operand
    is a per-request *constant* (GEMV's matrix, BS's sorted array, SpMV's
    matrix, MLP's weights) declares which positional args are residency
    candidates and factors ``split`` into a resident half and a varying
    half, so the operand cache can keep the expensive part on the banks:

      resident_args                 — positional indices into *args of the
                                      operands worth caching (content-hashed)
      split_resident(grid, total, *res)
          -> (res_meta, res_chunks|None)   device constants + the chunk list
                                      that carries the resident operand
                                      (None when it lives in res_meta only,
                                      e.g. BS's broadcast array)
      split_varying(grid, total, res_meta, *args)
          -> (meta, chunks|None)     per-request meta built *on top of*
                                      res_meta; chunks for the varying
                                      operand, or None when the resident
                                      chunks are the pipeline's chunks

    ``split`` must equal the composition of the two halves; workloads
    without a resident operand leave the three fields at their defaults.
    """
    name: str
    split: Callable
    scatter: Callable
    compute: Callable
    retrieve: Callable
    merge: Callable
    resident_args: tuple = ()
    split_resident: Callable | None = None
    split_varying: Callable | None = None
    #: True when the resident operand lives entirely in the resident meta
    #: (BS's broadcast array) rather than the chunk stream — warm hits then
    #: skip the split-time broadcast but still scatter the varying chunks.
    meta_resident: bool = False

    @property
    def supports_residency(self) -> bool:
        return (bool(self.resident_args)
                and self.split_resident is not None
                and self.split_varying is not None)


#: name -> ChunkedWorkload, filled by workload modules at import time.
CHUNKED: dict[str, ChunkedWorkload] = {}


def register_chunked(w: ChunkedWorkload) -> ChunkedWorkload:
    CHUNKED[w.name] = w
    return w
