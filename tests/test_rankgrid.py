"""Rank-hierarchy semantics (DESIGN.md §10): flat-view equivalence
(``ranks=1`` ≡ the old flat BankGrid), rank-granular chunking, the
rank-parallel pipeline, and a registry-wide ``run() == ref()`` sweep at
2×4 ranks×banks — in-process when enough devices exist (the CI rank-matrix
leg runs 16) and via an 8-device subprocess always — plus a strong/weak
rank-scaling smoke."""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro import pim
from repro.core import transfer as tx
from repro.core.banked import (BankGrid, RankGrid, make_bank_grid,
                               make_rank_grid)

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


# -- construction & flat-view equivalence -------------------------------------

def test_rank_grid_ranks_1_is_flat_equivalent(bank_grid, rng):
    """ranks=1 ≡ the old BankGrid: same shape, same mesh devices, and the
    single rank view spans every bank."""
    g = make_rank_grid(1)
    assert isinstance(g, BankGrid) and isinstance(g, RankGrid)
    assert g.n_ranks == 1 and g.n_banks == bank_grid.n_banks
    assert g.banks_per_rank == bank_grid.n_banks
    assert list(g.mesh.devices.flat) == list(bank_grid.mesh.devices.flat)
    assert list(g.rank_view(0).mesh.devices.flat) == \
        list(g.mesh.devices.flat)
    x = rng.integers(0, 99, 8 * g.n_banks).astype(np.int32)
    np.testing.assert_array_equal(g.from_banks(g.to_banks(x)), x)


def test_rank_grid_validation():
    n = len(jax.devices())
    with pytest.raises(ValueError):
        make_rank_grid(0)
    with pytest.raises(ValueError):
        make_rank_grid(n + 1, 1)
    with pytest.raises(ValueError):
        RankGrid(mesh=make_bank_grid().mesh, n_ranks=make_bank_grid()
                 .n_banks + 1)


def test_rank_views_partition_the_devices():
    n = len(jax.devices())
    g = make_rank_grid(n, 1)        # n ranks of 1 bank: always constructible
    seen = []
    for r in range(g.n_ranks):
        view = g.rank_view(r)
        assert view.n_banks == g.banks_per_rank
        seen += list(view.mesh.devices.flat)
    assert seen == list(g.mesh.devices.flat)    # disjoint, ordered cover
    assert g.mesh2d.shape == {"ranks": n, "banks": 1}


def test_env_ranks_falls_back_when_indivisible(monkeypatch):
    """REPRO_RANKS only upgrades to a RankGrid when the device count
    divides evenly — a 1-device dev box with the var exported must keep
    working on the flat grid."""
    monkeypatch.setenv("REPRO_RANKS", str(len(jax.devices()) + 7))
    g = make_bank_grid()
    assert getattr(g, "n_ranks", 1) == 1
    monkeypatch.setenv("REPRO_RANKS", "not-a-number")
    assert getattr(make_bank_grid(), "n_ranks", 1) == 1


def test_session_rank_kwargs_validation(bank_grid):
    with pytest.raises(ValueError, match="not both"):
        pim.PimSession(grid=bank_grid, ranks=1)
    with pytest.raises(ValueError, match="needs ranks"):
        pim.session(banks_per_rank=2)
    with pytest.raises(ValueError):
        pim.session(ranks=1, banks_per_rank=len(jax.devices()) + 1)


def test_session_ranks_1_matches_flat(rng):
    """pim.session(ranks=1) keeps today's behavior bit-for-bit."""
    a = rng.integers(0, 99, 4096).astype(np.int32)
    s_flat = pim.session()
    s_rank = pim.session(ranks=1)
    try:
        assert s_rank.n_ranks == 1
        assert s_rank.n_banks == s_flat.n_banks
        np.testing.assert_array_equal(s_rank.run("VA", a, a),
                                      s_flat.run("VA", a, a))
        (rec,) = s_rank.telemetry.records
        assert rec.n_ranks == 1
    finally:
        s_flat.close()
        s_rank.close()


# -- rank-granular chunking ---------------------------------------------------

def test_split_chunks_ranked_restores_flat_order(rng):
    x = rng.integers(0, 999, 1000).astype(np.int32)
    per_rank, n = tx.split_chunks_ranked(x, 2, 3)
    flat, n_flat = tx.split_chunks(x, 6)
    assert n == n_flat == 1000
    assert [len(g) for g in per_rank] == [3, 3]
    for mine, theirs in zip([c for g in per_rank for c in g], flat):
        np.testing.assert_array_equal(mine, theirs)
    with pytest.raises(ValueError):
        tx.split_chunks_ranked(x, 0, 2)


def test_push_pull_ranks_async_roundtrip(rng):
    g = make_rank_grid(len(jax.devices()), 1)
    payloads = [rng.integers(0, 99, (1, 16)).astype(np.int32)
                for _ in range(g.n_ranks)]
    devs, rec = tx.push_ranks_async(g, payloads)
    assert rec.kind == "cpu_dpu_rank_async"
    assert rec.nbytes == sum(p.nbytes for p in payloads)
    host, rec2 = tx.pull_ranks_async(devs)()
    for h, p in zip(host, payloads):
        np.testing.assert_array_equal(h, p)
    assert rec2.nbytes == rec.nbytes
    with pytest.raises(ValueError):
        tx.push_ranks_async(g, payloads + payloads)


# -- plan/rank resolution -----------------------------------------------------

def test_resolve_ranks_semantics():
    """A probed plan is authoritative — including when it adopted 1 rank
    (flat measured best); an unprobed plan defers to the grid."""
    from repro.runtime.pipeline import _resolve_ranks
    from repro.runtime import TunedPlan

    class FakeGrid:
        n_ranks = 4

    def plan(n_ranks, measured):
        return TunedPlan(workload="VA", n_chunks=2, max_batch_requests=1,
                         predicted_serialized_s=1.0,
                         predicted_pipelined_s=1.0, predicted_overlap=1.0,
                         n_ranks=n_ranks, rank_measured_s=measured)

    g = FakeGrid()
    assert _resolve_ranks(g, None, None) == 4           # grid default
    assert _resolve_ranks(g, 2, None) == 2              # caller override
    assert _resolve_ranks(g, None, plan(1, {})) == 4    # unprobed: grid wins
    assert _resolve_ranks(g, None, plan(1, {1: 0.1, 2: 0.2})) == 1  # probed
    assert _resolve_ranks(g, None, plan(2, {1: 0.2, 2: 0.1})) == 2
    assert _resolve_ranks(g, None, plan(8, {8: 0.1})) == 4   # clamped
    assert _resolve_ranks(object(), None, plan(2, {2: 0.1})) == 1  # flat grid


# -- registry-wide 2x4 sweep (in-process; the CI rank leg has 16 devices) -----

@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="2x4 ranks x banks needs >= 8 devices "
                           "(run under XLA_FLAGS="
                           "--xla_force_host_platform_device_count=8)")
def test_run_matches_ref_registry_wide_2x4():
    import zlib
    with_s = pim.session(ranks=2, banks_per_rank=4)
    try:
        assert with_s.n_ranks == 2 and with_s.n_banks == 8
        for name, entry in pim.registry().items():
            rng = np.random.default_rng(zlib.crc32(name.encode()))
            args = entry.make_args(rng, scale=1)
            entry.compare(with_s.run(name, *args), entry.ref(*args))
        recs = {r.workload: r for r in with_s.telemetry.records}
        assert recs["VA"].n_ranks == 2          # rank-sharded pipeline
        assert recs["NW"].n_ranks == 1          # serialized fallback: flat
    finally:
        with_s.close()


# -- 8-device subprocess: 2x4 sweep + rank-scaling smoke ----------------------

SCRIPT = r"""
import sys; sys.path.insert(0, {src!r})
import zlib
import numpy as np
from repro import pim

with pim.session(ranks=2, banks_per_rank=4) as s:
    assert s.n_ranks == 2 and s.banks_per_rank == 4 and s.n_banks == 8
    for name, entry in pim.registry().items():
        rng = np.random.default_rng(zlib.crc32(name.encode()))
        args = entry.make_args(rng, scale=1)
        entry.compare(s.run(name, *args), entry.ref(*args))
        print("RANKEQ-OK", name, flush=True)

# 1 -> 2 ranks at a fixed problem (strong) and at one grown with the ranks
# (weak): every answer matches ref() and each request spans every rank
for ranks in (1, 2):
    with pim.session(ranks=ranks, banks_per_rank=4) as s:
        for name, scale in (("VA", 2), ("VA", 2 * ranks),
                            ("SEL", 2 * ranks), ("SCAN", 2 * ranks)):
            entry = pim.registry()[name]
            rng = np.random.default_rng(zlib.crc32(name.encode()))
            args = entry.make_args(rng, scale=scale)
            req = s.submit(name, *args)
            entry.compare(req.result(timeout=300), entry.ref(*args))
            assert (req.record.n_ranks, req.record.n_banks) == (
                ranks, 4 * ranks), (name, ranks, req.record)
            print("RANKSCALE-OK", name, ranks, scale, flush=True)
print("RANKEQ-DONE")
"""


@pytest.fixture(scope="session")
def rank_subprocess_run():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    env.pop("REPRO_RANKS", None)      # the script sets ranks explicitly
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT.format(src=SRC)],
        env=env, capture_output=True, text=True, timeout=1200)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


@pytest.mark.slow
@pytest.mark.parametrize("name", ["VA", "GEMV", "SpMV", "SEL", "UNI", "BS",
                                  "TS", "BFS", "MLP", "NW", "HST", "RED",
                                  "SCAN", "TRNS"])
def test_rank_equivalence_8_devices(rank_subprocess_run, name):
    assert f"RANKEQ-OK {name}" in rank_subprocess_run


@pytest.mark.slow
@pytest.mark.parametrize("name", ["VA", "SEL", "SCAN"])
def test_rank_scaling_smoke_8_devices(rank_subprocess_run, name):
    """1 -> 2 ranks of 4 banks, at a problem grown with the ranks (and for
    VA also a fixed one): answers match ref() and every request is sharded
    across all of the session's ranks."""
    for ranks in (1, 2):
        assert f"RANKSCALE-OK {name} {ranks} {2 * ranks}" in (
            rank_subprocess_run)
    if name == "VA":
        assert "RANKSCALE-OK VA 2 2" in rank_subprocess_run
    assert "RANKEQ-DONE" in rank_subprocess_run
