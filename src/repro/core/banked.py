"""Bank-local execution model — the paper's DPU discipline as a JAX feature.

UPMEM semantics reproduced here:

* A ``BankGrid`` is a 1-D mesh axis of ``n_banks`` devices; each bank owns an
  exclusive shard of every ``BankedArray`` (its "MRAM bank").
* ``bank_local(fn)`` runs ``fn`` independently per bank via ``shard_map`` —
  the analogue of a DPU kernel launch.  DPUs cannot communicate, so a
  bank-local phase must lower to **zero collective bytes**; this is checked
  by :func:`assert_bank_local`.
* Inter-bank communication only happens in explicit *exchange* phases —
  the analogue of the paper's host-mediated "Inter-DPU" step (retrieve →
  merge on host → redistribute).  Exchanges are costed: every exchange kind
  reports its transferred bytes so benchmarks can render the paper's
  "Inter-DPU" time breakdown.

Two exchange back-ends:
  * ``via="host"``   — literally gather to host, merge, re-distribute (the
                       faithful UPMEM path; used by the PrIM suite to model
                       the paper's bottleneck).
  * ``via="fabric"`` — jax.lax collectives inside shard_map (the TPU-native
                       path the paper *wishes* UPMEM had; used by the LM
                       framework).  The delta between the two is exactly the
                       paper's Key Takeaway 3.
"""
from __future__ import annotations

import dataclasses
import functools
import os
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import hlo

AXIS = "banks"
RANK_AXIS = "ranks"

#: Environment override for the default rank count (CI's rank-shaped tier-1
#: matrix leg exports REPRO_RANKS=2): ``make_bank_grid()`` upgrades to a
#: :class:`RankGrid` when the device count divides evenly, and silently
#: stays flat otherwise (a 1-device dev box must keep working with the
#: variable exported).
RANKS_ENV = "REPRO_RANKS"


def _env_ranks() -> int:
    try:
        return int(os.environ.get(RANKS_ENV) or 1)
    except ValueError:
        return 1


def make_bank_grid(n_banks: int | None = None, *,
                   ranks: int | None = None) -> "BankGrid":
    """Grid over the first ``n_banks`` devices (default: all).  ``ranks``
    (default: the ``REPRO_RANKS`` env var) groups the banks into a two-level
    :class:`RankGrid`; an explicit ``ranks`` that does not divide the bank
    count raises, an env-derived one falls back to the flat grid."""
    devs = jax.devices()
    n = n_banks or len(devs)
    if n > len(devs):
        raise ValueError(f"need {n} devices, have {len(devs)}")
    mesh = Mesh(np.array(devs[:n]), (AXIS,))
    if ranks is None:
        env = _env_ranks()
        ranks = env if env > 1 and n % env == 0 else 1
    if ranks > 1:
        return RankGrid(mesh=mesh, n_ranks=ranks)
    return BankGrid(mesh=mesh)


def make_rank_grid(n_ranks: int, banks_per_rank: int | None = None
                   ) -> "RankGrid":
    """A two-level rank × bank grid: ``n_ranks`` ranks of ``banks_per_rank``
    banks each (default: every available device, split evenly)."""
    devs = jax.devices()
    if n_ranks < 1:
        raise ValueError(f"n_ranks must be >= 1, got {n_ranks}")
    if banks_per_rank is None:
        if len(devs) % n_ranks:
            raise ValueError(f"{len(devs)} devices do not split into "
                             f"{n_ranks} equal ranks; pass banks_per_rank")
        banks_per_rank = len(devs) // n_ranks
    need = n_ranks * banks_per_rank
    if need > len(devs):
        raise ValueError(f"need {need} devices for {n_ranks}x"
                         f"{banks_per_rank} ranks x banks, have {len(devs)}")
    mesh = Mesh(np.array(devs[:need]), (AXIS,))
    return RankGrid(mesh=mesh, n_ranks=n_ranks)


@dataclasses.dataclass(frozen=True)
class BankGrid:
    """A 1-D grid of banks (mesh devices), each owning exclusive shards."""

    mesh: Mesh

    @property
    def n_banks(self) -> int:
        return self.mesh.shape[AXIS]

    # -- data placement ("CPU-DPU transfers", paper §3.4) -------------------
    def sharding(self, spec: P | None = None) -> NamedSharding:
        return NamedSharding(self.mesh, spec if spec is not None else P(AXIS))

    def to_banks(self, x, spec: P | None = None):
        """Parallel CPU→DPU transfer: scatter shards to all banks at once."""
        return jax.device_put(x, self.sharding(spec))

    def broadcast(self, x):
        """dpu_broadcast_to: same buffer replicated onto every bank."""
        return jax.device_put(x, self.sharding(P()))

    def from_banks(self, x) -> np.ndarray:
        """Parallel DPU→CPU transfer: gather all shards to host."""
        return np.asarray(jax.device_get(x))

    def serial_to_banks(self, chunks: Sequence[np.ndarray]):
        """Serial dpu_copy_to: one bank at a time (kept for the Fig.10
        contrast; also the only option for ragged per-bank buffers,
        mirroring SEL/UNI/SpMV in the paper)."""
        devs = list(self.mesh.devices.flat)
        return [jax.device_put(c, d) for c, d in zip(chunks, devs)]

    # -- bank-local phase ----------------------------------------------------
    def bank_local(self, fn: Callable, in_specs=None, out_specs=None,
                   check: bool = False) -> Callable:
        """Run ``fn`` independently on every bank (DPU kernel launch).

        Default specs shard the leading axis across banks. With ``check=True``
        the lowered phase is asserted collective-free (DPUs cannot talk).
        The phase is jitted: one compiled launch per call, where an eager
        ``shard_map`` would compile and dispatch every operation apart."""
        ispec = in_specs if in_specs is not None else P(AXIS)
        ospec = out_specs if out_specs is not None else P(AXIS)
        mapped = jax.jit(jax.shard_map(fn, mesh=self.mesh, in_specs=ispec,
                                       out_specs=ospec, check_vma=False))
        if not check:
            return mapped

        @functools.wraps(fn)
        def wrapped(*args):
            assert_collective_free(mapped, *args)
            return mapped(*args)
        return wrapped

    # -- exchange phases ("Inter-DPU" step) ----------------------------------
    def exchange_sum(self, x, via: str = "fabric"):
        """RED-style final merge: input (banks, ...) partials -> summed (...)."""
        if via == "host":
            return self.from_banks(x).sum(axis=0)
        f = self.bank_local(
            lambda v: jax.lax.psum(v.sum(axis=0), AXIS), out_specs=P())
        return f(x)

    def exchange_scan(self, bank_totals, via: str = "fabric"):
        """SCAN-SSA/RSS inter-bank step: exclusive scan over per-bank totals,
        one scalar back to each bank."""
        if via == "host":
            t = self.from_banks(bank_totals).reshape(self.n_banks)
            excl = np.concatenate([[t.dtype.type(0)], np.cumsum(t)[:-1]])
            return self.to_banks(excl)

        def f(tot):
            allt = jax.lax.all_gather(tot.reshape(()), AXIS)
            idx = jax.lax.axis_index(AXIS)
            mask = jnp.arange(self.n_banks) < idx
            return jnp.sum(jnp.where(mask, allt, 0), dtype=allt.dtype)[None]
        return self.bank_local(f)(bank_totals)

    def exchange_union(self, bitvec, via: str = "fabric"):
        """BFS frontier union: OR-reduce per-bank bit-vectors, result on all."""
        if via == "host":
            parts = self.from_banks(bitvec).reshape(self.n_banks, -1)
            u = functools.reduce(np.bitwise_or, parts)
            return self.broadcast(u)

        def f(v):
            g = jax.lax.all_gather(v, AXIS)        # (banks, ...)
            return jax.lax.reduce(g, jnp.zeros((), g.dtype),
                                  jnp.bitwise_or, (0,))
        return self.bank_local(f, out_specs=P())(bitvec)

    def exchange_concat(self, x, via: str = "fabric"):
        """SEL/UNI-style merge: concatenate per-bank results (full array on
        every bank / host)."""
        if via == "host":
            return self.from_banks(x)
        f = self.bank_local(lambda v: jax.lax.all_gather(v, AXIS, tiled=True),
                            out_specs=P())
        return f(x)


# ---------------------------------------------------------------------------
# rank hierarchy (DESIGN.md §10)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class RankGrid(BankGrid):
    """Two-level rank × bank grid — the real UPMEM topology (DESIGN.md §10).

    A deployed UPMEM system is 32–40 *ranks* of 64 DPUs each, and CPU↔DPU
    transfers to different ranks proceed in parallel (paper §5;
    arXiv:2110.01709).  A ``RankGrid`` reproduces that structure on top of
    the flat bank model:

    * it IS-A :class:`BankGrid` over all ``n_ranks * banks_per_rank``
      devices — the *flat view* — so every existing consumer (serialized
      ``pim()``, characterization sweeps, the transfer engine) keeps
      working unchanged;
    * :meth:`rank_view` exposes each rank as an independent flat
      ``BankGrid`` over its own devices — what the rank-parallel transfer
      engine (``core.transfer``) and the per-rank chunk pipelines
      (``runtime.pipeline.run_pipelined_ranked``) operate on;
    * :attr:`mesh2d` is the explicit 2-D ``(rank, bank)`` mesh for code
      that wants named two-level axes.
    """

    n_ranks: int = 1

    def __post_init__(self):
        total = self.mesh.shape[AXIS]
        if self.n_ranks < 1:
            raise ValueError(f"n_ranks must be >= 1, got {self.n_ranks}")
        if total % self.n_ranks:
            raise ValueError(f"{total} banks do not split into "
                             f"{self.n_ranks} equal ranks")

    @property
    def banks_per_rank(self) -> int:
        return self.n_banks // self.n_ranks

    @functools.cached_property
    def mesh2d(self) -> Mesh:
        """The explicit two-level mesh: shape (n_ranks, banks_per_rank),
        axes (RANK_AXIS, AXIS)."""
        devs = np.array(list(self.mesh.devices.flat))
        return Mesh(devs.reshape(self.n_ranks, self.banks_per_rank),
                    (RANK_AXIS, AXIS))

    @functools.cached_property
    def rank_views(self) -> tuple[BankGrid, ...]:
        """One flat ``BankGrid`` per rank, over that rank's devices only.
        Cached: phase callables jit-cache per view (``@functools.cache``
        keyed on the grid), so views must be stable objects."""
        devs = list(self.mesh.devices.flat)
        b = self.banks_per_rank
        return tuple(
            BankGrid(mesh=Mesh(np.array(devs[r * b:(r + 1) * b]), (AXIS,)))
            for r in range(self.n_ranks))

    def rank_view(self, rank: int) -> BankGrid:
        """Rank ``rank`` as an independent flat grid (its "64 DPUs")."""
        return self.rank_views[rank]


# ---------------------------------------------------------------------------
# verification: a bank-local phase must not communicate
# ---------------------------------------------------------------------------

def lowered_collective_bytes(fn: Callable, *args) -> float:
    lowered = jax.jit(fn).lower(*(jax.ShapeDtypeStruct(a.shape, a.dtype)
                                  if hasattr(a, "shape") else a for a in args))
    return hlo.collective_bytes(lowered.compile().as_text())


def assert_collective_free(fn: Callable, *args) -> None:
    b = lowered_collective_bytes(fn, *args)
    if b > 0:
        raise AssertionError(
            f"bank-local phase lowered to {b} collective bytes — DPUs cannot "
            "communicate; move this traffic into an explicit exchange phase")
