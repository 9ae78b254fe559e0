"""The `repro.pim` session façade (DESIGN.md §9): lifecycle, the
UPMEM-shaped verb set, serialized-only fallback, future error propagation,
tuned-plan plumbing, and a registry-wide ``run() == ref()`` equivalence
sweep — in-process and at 8 simulated banks."""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro import pim
from repro.runtime import TunedPlan


@pytest.fixture()
def sess(bank_grid):
    s = pim.PimSession(grid=bank_grid)
    yield s
    s.close()


# -- allocation ---------------------------------------------------------------

def test_session_factory_allocates_and_closes():
    s = pim.session()
    assert s.n_banks >= 1 and not s.closed
    assert "open" in repr(s)
    s.close()
    assert s.closed and "closed" in repr(s)


def test_session_rejects_impossible_bank_count():
    with pytest.raises(ValueError):
        pim.session(banks=1 << 20)


def test_grid_and_banks_are_mutually_exclusive(bank_grid):
    with pytest.raises(ValueError):
        pim.PimSession(grid=bank_grid, banks=1)


def test_workload_view_covers_registry(sess):
    assert set(sess.workloads) == set(pim.registry())
    assert len(pim.registry()) == 16


# -- lifecycle (dpu_free semantics) -------------------------------------------

def test_double_close_is_noop(bank_grid):
    s = pim.PimSession(grid=bank_grid)
    s.close()
    s.close()                                    # second close: no-op
    assert s.closed


def test_verbs_after_close_raise(bank_grid, rng):
    s = pim.PimSession(grid=bank_grid)
    a = rng.integers(0, 9, 64).astype(np.int32)
    s.close()
    for verb in (lambda: s.submit("VA", a, a),
                 lambda: s.run("VA", a, a),
                 lambda: s.map("VA", [(a, a)]),
                 lambda: s.transfer_in(a),
                 lambda: s.drain(),
                 lambda: s.start(),
                 lambda: s.autotune(["VA"])):
        with pytest.raises(RuntimeError, match="closed PimSession"):
            verb()


def test_close_drains_pending_futures(bank_grid, rng):
    """close() may not leave a submitted future dangling forever."""
    s = pim.PimSession(grid=bank_grid)
    a = rng.integers(0, 9, 256).astype(np.int32)
    req = s.submit("VA", a, a)
    assert not req.done()
    s.close()
    assert req.done()
    np.testing.assert_array_equal(req.result(timeout=0), a + a)


def test_context_manager_serves_and_closes(bank_grid, rng):
    a = rng.integers(0, 9, 4096).astype(np.int32)
    with pim.PimSession(grid=bank_grid) as s:
        assert "serving" in repr(s)
        reqs = [s.submit("VA", a, a) for _ in range(3)]
        for r in reqs:
            np.testing.assert_array_equal(r.result(timeout=300), a + a)
    assert s.closed
    with pytest.raises(RuntimeError):
        s.submit("VA", a, a)


# -- launch verbs -------------------------------------------------------------

def test_run_sync_records_telemetry(sess, rng):
    a = rng.integers(0, 99, 4096).astype(np.int32)
    np.testing.assert_array_equal(sess.run("VA", a, a), a + a)
    (rec,) = sess.telemetry.records
    assert rec.workload == "VA" and rec.n_chunks >= 1
    assert sess.stats()["requests"] == 1


def test_run_serialized_only_fallback(sess, rng):
    """NW/BFS have no chunked form: s.run() must auto-pick the faithful
    serialized pim() per the registry, not fail."""
    from repro import prim
    s1 = rng.integers(0, 4, 48).astype(np.int32)
    s2 = rng.integers(0, 4, 40).astype(np.int32)
    adj = prim.bfs.random_graph(101, 3, seed=7)
    np.testing.assert_array_equal(sess.run("NW", s1, s2),
                                  prim.nw.ref(s1, s2))
    np.testing.assert_array_equal(sess.run("BFS", adj, 0),
                                  prim.bfs.ref(adj, 0))
    recs = {r.workload: r for r in sess.telemetry.records}
    assert recs["NW"].phases.total > 0 and recs["BFS"].phases.total > 0


def test_run_unknown_workload_raises(sess):
    with pytest.raises(KeyError, match="FFT"):
        sess.run("FFT", np.zeros(4))


def test_map_streams_in_order(sess, rng):
    streams = [(rng.integers(0, 99, 1000 + i).astype(np.int32),)
               for i in range(4)]
    outs = sess.map("RED", streams)
    assert [int(o) for o in outs] == [int(x[0].sum()) for x in streams]
    assert len(sess.telemetry.records) == 4     # map records telemetry too
    assert sess.map("RED", []) == []


def test_map_serialized_only_falls_back(sess, rng):
    from repro import prim
    pairs = [(rng.integers(0, 4, 32).astype(np.int32),
              rng.integers(0, 4, 32).astype(np.int32)) for _ in range(2)]
    outs = sess.map("NW", pairs)
    for out, (s1, s2) in zip(outs, pairs):
        np.testing.assert_array_equal(out, prim.nw.ref(s1, s2))


def test_map_while_serving_goes_through_worker(bank_grid, rng):
    a = rng.integers(0, 9, 2048).astype(np.int32)
    with pim.PimSession(grid=bank_grid) as s:
        outs = s.map("VA", [(a, a), (a, a + 1)])
    np.testing.assert_array_equal(outs[0], a + a)
    np.testing.assert_array_equal(outs[1], a + a + 1)


# -- error propagation --------------------------------------------------------

def test_future_error_propagates_deterministic(sess, rng):
    A = rng.normal(size=(16, 8)).astype(np.float32)
    bad = sess.submit("GEMV", A, np.ones(5, np.float32))  # shape mismatch
    good = sess.submit("GEMV", A, np.ones(8, np.float32))
    sess.drain()
    with pytest.raises(Exception):
        bad.result(timeout=5)
    assert good.result(timeout=5).shape == (16,)


def test_future_error_propagates_serving(bank_grid, rng):
    A = rng.normal(size=(16, 8)).astype(np.float32)
    with pim.PimSession(grid=bank_grid) as s:
        bad = s.submit("GEMV", A, np.ones(5, np.float32))
        with pytest.raises(Exception):
            bad.result(timeout=60)
    assert s.closed


def test_run_raises_inline(sess, rng):
    with pytest.raises(Exception):
        sess.run("GEMV", rng.normal(size=(4, 4)).astype(np.float32),
                 np.ones(5, np.float32))


# -- transfers (dpu_copy_to / dpu_copy_from escape hatches) -------------------

def test_transfer_roundtrip(sess, rng):
    x = rng.integers(0, 99, 8 * sess.n_banks).astype(np.int32)
    banked = sess.transfer_in(x)
    np.testing.assert_array_equal(sess.transfer_out(banked), x)


def test_transfer_broadcast(sess, rng):
    x = rng.normal(size=16).astype(np.float32)
    rep = sess.transfer_in(x, broadcast=True)
    np.testing.assert_allclose(sess.transfer_out(rep), x)


# -- plans / tuning plumbing --------------------------------------------------

def test_plans_accessor_and_tuned_serving(bank_grid, rng):
    plan = TunedPlan(workload="VA", n_chunks=2, max_batch_requests=3,
                     predicted_serialized_s=1.0, predicted_pipelined_s=0.5,
                     predicted_overlap=2.0)
    s = pim.PimSession(grid=bank_grid, plans={"VA": plan})
    assert s.plans == {"VA": plan} and s.tuning is None
    a = rng.integers(0, 9, 4096).astype(np.int32)
    np.testing.assert_array_equal(s.run("VA", a, a), a + a)
    (rec,) = s.telemetry.records
    assert rec.tuned and rec.n_chunks == 2 and rec.predicted_overlap == 2.0
    assert rec.row(bank_grid.n_banks)["predicted_overlap"] == 2.0
    s.close()


def test_session_accepts_tuning_result(bank_grid, rng):
    """plans= takes a whole TuningResult and keeps it inspectable via
    s.tuning."""
    from repro.runtime import TuningResult
    plan = TunedPlan(workload="VA", n_chunks=3, max_batch_requests=8,
                     predicted_serialized_s=1.0, predicted_pipelined_s=0.5,
                     predicted_overlap=2.0)
    tuning = TuningResult(stages={}, profiles={}, plans={"VA": plan})
    s = pim.PimSession(grid=bank_grid, plans=tuning)
    assert s.tuning is tuning and s.plans["VA"].n_chunks == 3
    a = rng.integers(0, 9, 512).astype(np.int32)
    rec = s.submit("VA", a, a).record
    s.drain()
    assert rec.n_chunks == 3
    s.close()


def test_session_autotune_installs_plans(bank_grid):
    s = pim.PimSession(grid=bank_grid)
    result = s.autotune(["VA"], scale=1, reps=2, probe=False,
                        calib_nbytes=(1 << 14, 1 << 16))
    assert set(result.plans) == {"VA"}
    assert s.plans["VA"] is result.plans["VA"]
    assert s.tuning is result
    s.close()


# -- operand residency through the façade (DESIGN.md §12) ---------------------

def test_stats_reports_cache_counters(sess, rng):
    entry = pim.registry()["GEMV"]
    args = entry.make_args(rng, 1)
    sess.run("GEMV", *args)
    sess.run("GEMV", *args)
    out = sess.stats()
    cs = out["cache"]
    assert (cs["hits"], cs["misses"], cs["entries"]) == (1, 1, 1)
    assert cs["resident_bytes"] > 0 and cs["budget_bytes"] > 0
    assert cs["evictions"] == 0
    # the same counters mirror into the metrics registry (one merge site)
    assert out["counters"]["cache_hits"] == 1
    assert out["counters"]["cache_misses"] == 1
    assert out["counters"]["cache_resident_bytes"] == cs["resident_bytes"]
    assert out["cache_hits"] == 1            # telemetry aggregate side


def test_resident_false_disables_cache(bank_grid, rng):
    s = pim.PimSession(grid=bank_grid, resident=False)
    entry = pim.registry()["GEMV"]
    args = entry.make_args(rng, 1)
    try:
        assert s.cache is None
        for _ in range(2):                   # every request re-scatters
            entry.compare(s.run("GEMV", *args), entry.ref(*args))
        assert "cache" not in s.stats()
        with pytest.raises(RuntimeError, match="resident=False"):
            s.pin("GEMV", *args)
    finally:
        s.close()


def test_resident_budget_from_device_memory(bank_grid):
    """The default budget is each bank's free device memory less the
    in-flight headroom; a backend without memory stats (the CPU) keeps the
    modelled MRAM capacity."""
    import types

    from repro.core.perfmodel import mram_capacity_bytes
    from repro.pim.session import resident_budget

    assert resident_budget(bank_grid, 1 << 20) == \
        mram_capacity_bytes(bank_grid.n_banks)
    with pim.session(grid=bank_grid) as s:
        assert s.cache.budget_bytes == mram_capacity_bytes(s.n_banks)

    class Dev:
        def __init__(self, limit, used):
            self.stats = {"bytes_limit": limit, "bytes_in_use": used}

        def memory_stats(self):
            return self.stats

    def grid(*devs):
        return types.SimpleNamespace(
            n_banks=len(devs),
            mesh=types.SimpleNamespace(devices=np.array(devs, object)))

    gb = 1 << 30
    assert resident_budget(grid(Dev(16 * gb, 4 * gb)), gb) == 11 * gb
    assert resident_budget(grid(Dev(16 * gb, 4 * gb), Dev(16 * gb, 15 * gb)),
                           2 * gb) == 10 * gb        # a full bank adds 0
    none = grid(Dev(16 * gb, 0))
    none.mesh.devices.flat[0].stats = None
    assert resident_budget(none, gb) == mram_capacity_bytes(1)


def test_close_releases_resident_operands(bank_grid, rng):
    entry = pim.registry()["GEMV"]
    args = entry.make_args(rng, 1)
    s = pim.PimSession(grid=bank_grid)
    s.run("GEMV", *args)
    assert len(s.cache) == 1 and s.cache.resident_bytes > 0
    s.close()
    assert len(s.cache) == 0 and s.cache.resident_bytes == 0


def test_cache_spans_start_stop_cycles(bank_grid, rng):
    """A start()/stop-to-deterministic cycle must not drop residents: the
    cache belongs to the session lifetime, not the serving mode."""
    entry = pim.registry()["GEMV"]
    args = entry.make_args(rng, 1)
    s = pim.PimSession(grid=bank_grid)
    try:
        s.run("GEMV", *args)                 # deterministic: fills
        s.start()                            # serving: same cache serves
        entry.compare(s.submit("GEMV", *args).result(timeout=300),
                      entry.ref(*args))
        assert s.cache.stats()["hits"] == 1
    finally:
        s.close()


# -- registry-wide equivalence sweep ------------------------------------------

def test_run_matches_ref_registry_wide(sess):
    """Every servable workload through one session handle: s.run == ref,
    pipelined or serialized fallback picked per registry (canonical args;
    stable per-workload seeds — hash() is salted per process)."""
    import zlib
    for name, entry in pim.registry().items():
        rng = np.random.default_rng(zlib.crc32(name.encode()))
        args = entry.make_args(rng, scale=1)
        entry.compare(sess.run(name, *args), entry.ref(*args))
    assert len(sess.telemetry.records) == len(pim.registry())


# -- 8 simulated banks (single subprocess, parametrized assertions) -----------

SCRIPT = r"""
import sys; sys.path.insert(0, {src!r})
import zlib
import numpy as np
from repro import pim
with pim.session() as s:
    assert s.n_banks == 8, s.n_banks
    for name, entry in pim.registry().items():
        rng = np.random.default_rng(zlib.crc32(name.encode()))
        args = entry.make_args(rng, scale=1)
        entry.compare(s.run(name, *args), entry.ref(*args))
        print("SESSEQ-OK", name, flush=True)
assert s.closed
print("SESSEQ-DONE")
"""


@pytest.fixture(scope="session")
def eight_bank_session_run():
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    out = subprocess.run([sys.executable, "-c", SCRIPT.format(src=src)],
                         env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


@pytest.mark.slow
@pytest.mark.parametrize("name", ["VA", "GEMV", "SpMV", "SEL", "UNI", "BS",
                                  "TS", "BFS", "MLP", "NW", "HST", "RED",
                                  "SCAN", "TRNS"])
def test_session_equivalence_8_banks(eight_bank_session_run, name):
    assert f"SESSEQ-OK {name}" in eight_bank_session_run
