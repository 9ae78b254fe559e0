"""Bank-resident operand cache (DESIGN.md §12): the residency test battery.

Covers the cache's correctness contract end to end:

* fingerprint keying — content / dtype / shape / placement all key the
  entry; equal bytes fingerprint identically;
* warm-hit equivalence — a warm (operand-resident) run is bit-identical to
  the cold run and to ``ref``, for every resident workload (GEMV, BS, SpMV,
  MLP), in-process and at 8 simulated banks (subprocess);
* eviction — a tight budget evicts LRU entries; evicted operands re-scatter
  and still match ref; pinned entries survive eviction pressure;
* mutation safety — mutating the caller's host array changes the
  fingerprint, so the next run misses and recomputes (stale reads are
  impossible; see the resident-module docstring for the cost);
* concurrency — concurrent submits of the same fingerprint push each chunk
  exactly once (trace-span counted), and close() mid-flight drains every
  future and releases every resident buffer;
* rank-aware residency — on a 2x4 RankGrid the warm run pushes nothing
  (zero new ``scatter`` spans, one ``scatter_cached`` per chunk), asserted
  from the trace (subprocess).
"""
import os
import subprocess
import sys
import threading
import zlib

import numpy as np
import pytest

from repro import pim
from repro.runtime import (Metrics, ResidentCache, ResidentHandle,
                           fingerprint)
from repro.runtime.trace import NULL_TRACER, set_tracer

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))

#: the workloads whose registry entries declare a resident operand
RESIDENT = ("GEMV", "GEMV-B", "GEMV-G", "BS", "SpMV", "MLP")

#: one GEMV matrix at make_args scale=1: 512 x 256 float32
GEMV_NBYTES = 512 * 256 * 4


@pytest.fixture(autouse=True)
def _clean_tracer():
    """Start from the disabled default tracer (REPRO_TRACE CI legs leave
    session tracers installed across test files otherwise)."""
    prev = set_tracer(NULL_TRACER)
    yield
    set_tracer(prev)


def _gemv_args(seed=0, scale=1):
    entry = pim.registry()["GEMV"]
    return entry, entry.make_args(np.random.default_rng(seed), scale)


# -- registry declarations ----------------------------------------------------

def test_registry_declares_resident_set():
    reg = pim.registry()
    assert {n for n, e in reg.items() if e.resident} == set(RESIDENT)
    assert reg["GEMV"].resident_args == (0,)
    assert reg["GEMV-B"].resident_args == (0,)    # pytree {"w", "b"} operand
    assert reg["GEMV-G"].resident_args == (0,)    # pytree {"wg", "wu"}
    assert reg["SpMV"].resident_args == (0, 1)
    assert reg["MLP"].resident_args == (0,)
    assert reg["BS"].chunked.meta_resident       # broadcast, not chunks
    assert not reg["GEMV"].chunked.meta_resident
    assert reg["VA"].resident_args == () and not reg["VA"].resident


# -- fingerprint keying -------------------------------------------------------

def test_fingerprint_keys_content_dtype_shape_placement():
    a = np.arange(64, dtype=np.int32)
    f = fingerprint("X", (a,), (8, 1, 4))
    assert f == fingerprint("X", (a.copy(),), (8, 1, 4))
    b = a.copy()
    b[0] += 1
    assert f != fingerprint("X", (b,), (8, 1, 4))
    assert f != fingerprint("X", (a.astype(np.int64),), (8, 1, 4))
    assert f != fingerprint("X", (a.reshape(8, 8),), (8, 1, 4))
    assert f != fingerprint("X", (a,), (8, 2, 8))      # placement keys too
    assert f != fingerprint("Y", (a,), (8, 1, 4))
    # a non-contiguous view hashes its logical bytes, not its buffer
    strided = np.arange(128, dtype=np.int32)[::2]
    assert (fingerprint("X", (strided,), (8, 1, 4))
            == fingerprint("X", (strided.copy(),), (8, 1, 4)))
    # pytree payloads (MLP's weight list) fingerprint leaf-wise
    ws = [np.ones((4, 4), np.float32), np.zeros((2, 4), np.float32)]
    g = fingerprint("MLP", (ws,), (8, 1, 4))
    ws2 = [w.copy() for w in ws]
    assert g == fingerprint("MLP", (ws2,), (8, 1, 4))
    ws2[1][0, 0] = 5.0
    assert g != fingerprint("MLP", (ws2,), (8, 1, 4))


# -- ResidentCache unit behavior ----------------------------------------------

def test_cache_lru_eviction_order_and_counters():
    wl = pim.registry()["GEMV"].chunked
    x = np.ones(4, np.float32)
    mats = [np.full((16, 4), i, np.float32) for i in range(3)]   # 256 B each
    place = (1, 1, 2)
    fps = [fingerprint("GEMV", (m,), place) for m in mats]
    cache = ResidentCache(budget_bytes=512)

    e0, hit = cache.acquire(wl, (mats[0], x), place)
    assert not hit and e0 is not None and not e0.ready
    # mark ready without device work: meta-only, no chunk buffers expected
    e0.set_rank_meta(0, {}, n_chunks=0)
    assert e0.ready and not e0.chunk_resident
    cache.release(e0)                    # request retires: lease back
    e1, _ = cache.acquire(wl, (mats[1], x), place)
    e1.set_rank_meta(0, {}, n_chunks=0)
    cache.release(e1)
    assert cache.resident_bytes == 512 and len(cache) == 2

    eh, hit = cache.acquire(wl, (mats[0], x), place)    # hit, moves to MRU
    assert hit
    cache.release(eh)
    e2, hit = cache.acquire(wl, (mats[2], x), place)    # evicts LRU = mats[1]
    assert not hit and e2 is not None
    cache.release(e2)
    assert cache.lookup(fps[1]) is None and cache.lookup(fps[0]) is not None
    st = cache.stats()
    assert (st["hits"], st["misses"], st["evictions"]) == (1, 3, 1)
    assert st["entries"] == 2 and st["resident_bytes"] == 512
    assert st["budget_bytes"] == 512

    # over-budget operand: uncacheable, never evicts to make room it can't use
    big = np.ones((64, 4), np.float32)                   # 1024 B > budget
    ent, hit = cache.acquire(wl, (big, x), place)
    assert ent is None and not hit and len(cache) == 2

    # all-pinned cache: nothing evictable -> uncacheable
    for fp in (fps[0], fps[2]):
        assert cache.pin(fp)
    ent, _ = cache.acquire(wl, (np.full((16, 4), 9, np.float32), x), place)
    assert ent is None and len(cache) == 2
    assert cache.unpin(fps[0]) and not cache.unpin("nope")

    cache.clear()
    assert len(cache) == 0 and cache.resident_bytes == 0


# -- in-flight leases / eviction safety ---------------------------------------

def test_acquire_leases_block_eviction_until_release():
    wl = pim.registry()["GEMV"].chunked
    x = np.ones(4, np.float32)
    m0 = np.zeros((16, 4), np.float32)                   # 256 B
    m1 = np.ones((32, 4), np.float32)                    # 512 B
    cache = ResidentCache(budget_bytes=512)
    e0, hit = cache.acquire(wl, (m0, x), (1, 1, 2))
    assert not hit and e0.leases == 1
    e0b, _ = cache.acquire(wl, (m0, x), (1, 1, 2))       # same fingerprint
    assert e0b is e0 and e0.leases == 2
    # e0 leased: a reservation that would need its bytes is uncacheable,
    # and nothing is destroyed in the attempt
    ent, _ = cache.acquire(wl, (m1, x), (1, 1, 2))
    assert ent is None and len(cache) == 1
    assert cache.stats()["evictions"] == 0 and not e0.released
    cache.release(e0)
    assert e0.leases == 1
    cache.release(e0)
    cache.release(None)                                  # None-safe
    assert e0.leases == 0
    e1, _ = cache.acquire(wl, (m1, x), (1, 1, 2))        # now evicts e0
    assert e1 is not None and cache.stats()["evictions"] == 1
    assert len(cache) == 1 and e0.released
    cache.release(e1)


def test_failed_reservation_evicts_nothing_and_keeps_gauge():
    """REVIEW regression: when the unpinned entries cannot cover the
    shortfall, acquire() used to evict them anyway before giving up —
    destroying entries for an operand that ends up uncacheable, and
    leaving the resident-bytes gauge stale."""
    wl = pim.registry()["GEMV"].chunked
    x = np.ones(4, np.float32)
    m = Metrics()
    cache = ResidentCache(budget_bytes=512, metrics=m)
    e0, _ = cache.acquire(wl, (np.zeros((16, 4), np.float32), x), (1, 1, 2))
    e1, _ = cache.acquire(wl, (np.ones((16, 4), np.float32), x), (1, 1, 2),
                          pin=True)
    cache.release(e0)
    cache.release(e1)
    assert m.snapshot()["counters"]["cache_resident_bytes"] == 512
    # the 512 B operand needs both entries' bytes but e1 is pinned: must
    # reject up front with the cache (and gauge) untouched
    ent, _ = cache.acquire(wl, (np.ones((32, 4), np.float32), x), (1, 1, 2))
    assert ent is None
    assert len(cache) == 2 and cache.resident_bytes == 512
    assert cache.stats()["evictions"] == 0
    assert m.snapshot()["counters"]["cache_resident_bytes"] == 512


def test_store_into_released_entry_is_noop():
    """An evicted/cleared entry is dead: an in-progress filler must not
    resurrect buffers the cache no longer accounts for."""
    wl = pim.registry()["GEMV"].chunked
    x = np.ones(4, np.float32)
    cache = ResidentCache(budget_bytes=1 << 20)
    ent, _ = cache.acquire(wl, (np.zeros((16, 4), np.float32), x), (1, 1, 2))
    ent.set_rank_meta(0, {"m": 1}, n_chunks=1)
    cache.clear()                        # releases the entry mid-"fill"
    assert ent.released
    ent.store(0, object())               # orphan filler keeps scattering
    assert ent.get(0) is None and not ent.ready
    assert ent.set_rank_meta(0, {"m": 2}, n_chunks=1) == {"m": 2}
    assert ent.rank_meta(0) is None


def test_inflight_warm_hit_survives_batch_eviction_pressure(bank_grid):
    """REVIEW regression (high): in a batched map() every request
    acquires its entry up-front, before any scatter runs.  A later
    request's reservation must not evict an earlier request's warm-hit
    entry — its chunk list is ``[None]`` placeholders whose buffers live
    in that entry, and the old code crashed scattering the placeholder."""
    entry, (A1, x) = _gemv_args(seed=10)
    A2 = np.random.default_rng(11).normal(size=A1.shape).astype(np.float32)
    A3 = np.random.default_rng(12).normal(size=A1.shape).astype(np.float32)
    s = pim.PimSession(grid=bank_grid, resident=GEMV_NBYTES + 1024)
    try:
        s.run("GEMV", A1, x)             # A1 resident + ready
        outs = s.map("GEMV", [(A1, x), (A2, x), (A3, x)])
        for A, out in zip((A1, A2, A3), outs):
            entry.compare(out, entry.ref(A, x))
        cs = s.stats()["cache"]
        recs = list(s.telemetry.records)
        # leases retired with the batch: A1's entry is evictable again
        entry.compare(s.run("GEMV", A2, x), entry.ref(A2, x))
        cs_after = s.stats()["cache"]
    finally:
        s.close()
    assert cs["hits"] == 1               # A1 served warm inside the batch
    assert cs["evictions"] == 0          # the leased entry was untouchable
    assert cs["entries"] == 1 and cs["resident_bytes"] == GEMV_NBYTES
    assert cs["misses"] == 3             # cold A1 + uncacheable A2, A3
    assert recs[1].cache_hit and not recs[2].cache_hit
    assert cs_after["evictions"] == 1    # A2 displaced the unleased A1


# -- warm-hit equivalence (in-process, every resident workload) ---------------

@pytest.mark.parametrize("name", RESIDENT)
def test_warm_hit_bit_identical_and_matches_ref(bank_grid, name):
    entry = pim.registry()[name]
    rng = np.random.default_rng(zlib.crc32(name.encode()))
    args = entry.make_args(rng, 1)
    s = pim.PimSession(grid=bank_grid)
    try:
        cold = s.run(name, *args)
        warm = s.run(name, *args)
        cs = s.stats()["cache"]
        recs = list(s.telemetry.records)
    finally:
        s.close()
    entry.compare(cold, entry.ref(*args))
    np.testing.assert_array_equal(np.asarray(cold), np.asarray(warm))
    assert (cs["hits"], cs["misses"], cs["entries"]) == (1, 1, 1)
    assert cs["resident_bytes"] > 0
    assert not recs[0].cache_hit and recs[1].cache_hit


# -- eviction / pinning / budget ----------------------------------------------

def test_eviction_under_tight_budget_rescatters_and_matches(bank_grid):
    entry, (A1, x) = _gemv_args(seed=1)
    A2 = np.random.default_rng(2).normal(size=A1.shape).astype(np.float32)
    # budget fits exactly one GEMV matrix: every new matrix evicts the last
    s = pim.PimSession(grid=bank_grid, resident=GEMV_NBYTES + 1024)
    try:
        for A in (A1, A2, A1):           # A1 again after its eviction
            out = s.run("GEMV", A, x)
            entry.compare(out, entry.ref(A, x))
        cs = s.stats()["cache"]
    finally:
        s.close()
    assert cs["hits"] == 0 and cs["misses"] == 3
    assert cs["evictions"] == 2 and cs["entries"] == 1
    assert cs["resident_bytes"] == GEMV_NBYTES


def test_pin_survives_eviction_pressure_and_unpin_releases(bank_grid):
    entry, (A1, x) = _gemv_args(seed=3)
    A2 = np.random.default_rng(4).normal(size=A1.shape).astype(np.float32)
    s = pim.PimSession(grid=bank_grid, resident=GEMV_NBYTES + 1024)
    try:
        fp = s.pin("GEMV", A1, x)
        assert s.cache.lookup(fp) is not None and s.cache.lookup(fp).ready
        # A2 cannot evict the pinned A1: uncacheable, but still correct
        entry.compare(s.run("GEMV", A2, x), entry.ref(A2, x))
        assert len(s.cache) == 1 and s.cache.lookup(fp) is not None
        # the pinned prefill serves the first real A1 request warm
        entry.compare(s.run("GEMV", A1, x), entry.ref(A1, x))
        assert s.cache.stats()["hits"] == 1
        assert s.telemetry.records[-1].cache_hit
        # unpin: A1 is evictable again, A2 can now displace it
        assert s.unpin(fp)
        entry.compare(s.run("GEMV", A2, x), entry.ref(A2, x))
        assert s.cache.lookup(fp) is None
    finally:
        s.close()


def test_pin_rejects_non_resident_workload_and_over_budget(bank_grid, rng):
    s = pim.PimSession(grid=bank_grid, resident=1024)
    try:
        a = rng.integers(0, 9, 64).astype(np.int32)
        with pytest.raises(ValueError, match="no resident operand"):
            s.pin("VA", a, a)
        entry, (A, x) = _gemv_args(seed=5)
        with pytest.raises(RuntimeError, match="residency budget"):
            s.pin("GEMV", A, x)
    finally:
        s.close()


def test_larger_than_budget_operand_uncacheable_but_correct(bank_grid):
    entry, (A, x) = _gemv_args(seed=6)
    s = pim.PimSession(grid=bank_grid, resident=1024)    # nothing fits
    try:
        for _ in range(2):
            entry.compare(s.run("GEMV", A, x), entry.ref(A, x))
        cs = s.stats()["cache"]
    finally:
        s.close()
    assert cs["entries"] == 0 and cs["resident_bytes"] == 0
    assert cs["hits"] == 0 and cs["misses"] == 2


# -- caller-owned mutation ----------------------------------------------------

def test_host_mutation_changes_fingerprint_and_misses(bank_grid):
    """The fingerprint hashes content at acquire time: mutating the host
    array yields a new key, so the stale resident entry can never serve the
    mutated operand (the documented caller-owned-mutation contract)."""
    entry, (A, x) = _gemv_args(seed=7)
    s = pim.PimSession(grid=bank_grid)
    try:
        entry.compare(s.run("GEMV", A, x), entry.ref(A, x))
        A[0, :] += 1.0                       # in-place caller mutation
        entry.compare(s.run("GEMV", A, x), entry.ref(A, x))
        cs = s.stats()["cache"]
    finally:
        s.close()
    assert cs["hits"] == 0 and cs["misses"] == 2 and cs["entries"] == 2


# -- ResidentHandle: opt-in identity token ------------------------------------

def test_resident_handle_skips_rehash_and_shares_the_entry(bank_grid,
                                                           monkeypatch):
    from repro.runtime import resident as res_mod
    entry, (A, x) = _gemv_args(seed=13)
    h = pim.ResidentHandle(A)
    place = (bank_grid.n_banks, 1, 4)
    # the handle fingerprints identically to the raw array it wraps
    assert fingerprint("GEMV", (h,), place) == fingerprint("GEMV", (A,),
                                                           place)
    # ... without rehashing the bytes (content_digest must not be called)
    def boom(_value):
        raise AssertionError("content rehash on the handle fast path")
    monkeypatch.setattr(res_mod, "content_digest", boom)
    fingerprint("GEMV", (h,), place)
    monkeypatch.undo()

    ref_out = entry.ref(A, x)
    s = pim.PimSession(grid=bank_grid)
    try:
        entry.compare(s.run("GEMV", h, x), ref_out)      # cold, via handle
        entry.compare(s.run("GEMV", h, x), ref_out)      # warm, no rehash
        entry.compare(s.run("GEMV", A, x), ref_out)      # raw arg: same entry
        cs = s.stats()["cache"]
        rec0 = s.telemetry.records[0]
    finally:
        s.close()
    assert (cs["hits"], cs["misses"], cs["entries"]) == (2, 1, 1)
    assert rec0.bytes_in == A.nbytes + x.nbytes          # sizing unwraps


# -- pytree operands: whole weight dicts pin in one call ----------------------

def _gemv_b_args(seed=7):
    entry = pim.registry()["GEMV-B"]
    return entry, entry.make_args(np.random.default_rng(seed))


def test_pytree_handle_pins_weight_dict_in_one_call(bank_grid):
    """Satellite: ResidentHandle wraps a whole pytree (GEMV-B's {"w","b"}
    dict) — one digest pass over the leaves at construction, pin() places
    it, and every subsequent run is warm without rehashing."""
    from repro.runtime import resident as res_mod
    entry, (w, x) = _gemv_b_args()
    h = pim.ResidentHandle(w)
    ref_out = entry.ref(w, x)
    s = pim.PimSession(grid=bank_grid)
    try:
        fp = s.pin("GEMV-B", h, np.zeros_like(x))
        assert isinstance(fp, str) and fp
        entry.compare(s.run("GEMV-B", h, x), ref_out)    # first run: warm
        entry.compare(s.run("GEMV-B", h, x), ref_out)
        cs = s.stats()["cache"]
    finally:
        s.close()
    assert (cs["hits"], cs["misses"], cs["entries"]) == (2, 1, 1)
    # a raw dict with equal bytes keys the same entry as the handle
    place = (bank_grid.n_banks, 1, 4)
    assert fingerprint("GEMV-B", (h,), place) == fingerprint(
        "GEMV-B", ({"w": w["w"].copy(), "b": w["b"].copy()},), place)
    # mutating a leaf changes the pytree fingerprint
    w2 = {"w": w["w"].copy(), "b": w["b"].copy()}
    w2["b"][0] += 1
    assert fingerprint("GEMV-B", (w2,), place) != fingerprint(
        "GEMV-B", (w,), place)
    # the top-level-handle fast path holds for pytree values too
    def boom(_value):
        raise AssertionError("content rehash on the pytree handle path")
    prev = res_mod.content_digest
    res_mod.content_digest = boom
    try:
        fingerprint("GEMV-B", (h,), place)
    finally:
        res_mod.content_digest = prev


def test_handles_nested_inside_pytree_operands_unwrap(bank_grid):
    """Handles may also sit *inside* a dict operand (leaf-wise wrapping):
    unwrap is recursive, results match ref, and the nested form keys its
    own entry (the digest string stands in for the leaf bytes)."""
    from repro.runtime.resident import unwrap_handles
    entry, (w, x) = _gemv_b_args(seed=8)
    nested = {"w": pim.ResidentHandle(w["w"]), "b": pim.ResidentHandle(w["b"])}
    uw, ux = unwrap_handles((nested, x))
    assert uw["w"] is w["w"] and uw["b"] is w["b"] and ux is x
    s = pim.PimSession(grid=bank_grid)
    try:
        entry.compare(s.run("GEMV-B", nested, x), entry.ref(w, x))
        entry.compare(s.run("GEMV-B", nested, x), entry.ref(w, x))
        cs = s.stats()["cache"]
    finally:
        s.close()
    assert (cs["hits"], cs["misses"]) == (1, 1)


# -- concurrency --------------------------------------------------------------

def test_concurrent_submits_same_fingerprint_scatter_exactly_once(bank_grid):
    """N threads submit the same operand to a serving session: every chunk
    must be pushed exactly once (counted from trace spans), every other
    serve must be a ``scatter_cached``, and every result must match ref."""
    entry, (A, x) = _gemv_args(seed=8)
    ref_out = entry.ref(A, x)
    n_threads = 4
    with pim.PimSession(grid=bank_grid, trace=True) as s:
        futs, flock = [], threading.Lock()
        gate = threading.Barrier(n_threads)

        def submitter():
            gate.wait()
            f = s.submit("GEMV", A, x)
            with flock:
                futs.append(f)

        threads = [threading.Thread(target=submitter)
                   for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        outs = [f.result(timeout=600) for f in futs]
    for out in outs:
        entry.compare(out, ref_out)
    names = [sp.name for sp in s.tracer.spans]
    depths = {r.n_chunks for r in s.telemetry.records}
    assert len(depths) == 1
    n = depths.pop()
    assert names.count("scatter") == n, (names.count("scatter"), n)
    assert names.count("scatter_cached") == (n_threads - 1) * n
    fps = {sp.args["fingerprint"] for sp in s.tracer.spans
           if sp.name == "scatter_cached"}
    assert len(fps) == 1


def test_close_mid_flight_drains_and_releases_residents(bank_grid):
    entry, (A, x) = _gemv_args(seed=9)
    ref_out = entry.ref(A, x)
    s = pim.PimSession(grid=bank_grid).start()
    reqs = [s.submit("GEMV", A, x) for _ in range(4)]
    s.close()                                # mid-flight: must drain
    for r in reqs:
        entry.compare(r.result(timeout=0), ref_out)
    assert len(s.cache) == 0 and s.cache.resident_bytes == 0
    assert s.cache.stats()["resident_bytes"] == 0


# -- autotune warm plans ------------------------------------------------------

def test_autotune_learns_warm_plans_for_chunk_resident_only(bank_grid):
    from repro.runtime.autotune import TunedPlan
    s = pim.PimSession(grid=bank_grid)
    try:
        result = s.autotune(["GEMV", "BS"], scale=1, reps=2, probe=False,
                            calib_nbytes=(1 << 14, 1 << 16))
    finally:
        s.close()
    warm = result.plans["GEMV"]
    assert warm.warm_n_chunks >= 1
    assert warm.warm_predicted_pipelined_s > 0
    assert warm.warm_predicted_overlap > 0
    assert warm.warm_candidate_s
    # round-trips through the artifact dict form
    back = TunedPlan.from_dict(warm.as_dict())
    assert back.warm_n_chunks == warm.warm_n_chunks
    assert back.warm_predicted_overlap == warm.warm_predicted_overlap
    # BS is meta-resident: its scatter stage (query chunks) survives warm
    # hits, so the push-elided warm model does not apply
    assert result.plans["BS"].warm_n_chunks == 0


def test_old_plan_dicts_load_without_warm_fields():
    from repro.runtime.autotune import TunedPlan
    plan = TunedPlan(workload="VA", n_chunks=2, max_batch_requests=3,
                     predicted_serialized_s=1.0, predicted_pipelined_s=0.5,
                     predicted_overlap=2.0)
    d = plan.as_dict()
    for key in list(d):
        if key.startswith("warm_"):
            d.pop(key)                     # a pre-residency artifact
    back = TunedPlan.from_dict(d)
    assert back.warm_n_chunks == 0 and back.warm_predicted_overlap == 0.0


# -- 8 simulated banks: resident sweep (subprocess) ---------------------------

SCRIPT8 = r"""
import sys; sys.path.insert(0, {src!r})
import zlib
import numpy as np
from repro import pim
with pim.session() as s:
    assert s.n_banks == 8, s.n_banks
    names = ("GEMV", "GEMV-B", "GEMV-G", "BS", "SpMV", "MLP")
    for name in names:
        entry = pim.registry()[name]
        rng = np.random.default_rng(zlib.crc32(name.encode()))
        args = entry.make_args(rng, 1)
        cold = s.run(name, *args)
        warm = s.run(name, *args)
        entry.compare(cold, entry.ref(*args))
        np.testing.assert_array_equal(np.asarray(cold), np.asarray(warm))
        print("RESID8-OK", name, flush=True)
    cs = s.stats()["cache"]
    assert cs["hits"] == len(names) and cs["misses"] == len(names), cs
    assert cs["entries"] == len(names) and cs["resident_bytes"] > 0, cs
print("RESID8-DONE")
"""


@pytest.fixture(scope="session")
def eight_bank_resident_run():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    env.pop("REPRO_TRACE", None)
    out = subprocess.run([sys.executable, "-c", SCRIPT8.format(src=SRC)],
                         env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "RESID8-DONE" in out.stdout
    return out.stdout


@pytest.mark.slow
@pytest.mark.parametrize("name", RESIDENT)
def test_warm_hit_8_banks(eight_bank_resident_run, name):
    assert f"RESID8-OK {name}" in eight_bank_resident_run


# -- rank-aware residency: 2x4 RankGrid, trace-asserted (subprocess) ----------

SCRIPT_RANKED = r"""
import sys; sys.path.insert(0, {src!r})
import numpy as np
from repro import pim
rng = np.random.default_rng(0)
s = pim.session(ranks=2, banks_per_rank=4, trace=True)   # deterministic mode
entry = pim.registry()["GEMV"]
args = entry.make_args(rng, 1)
cold = s.run("GEMV", *args)
n_cold = sum(1 for sp in s.tracer.spans if sp.name == "scatter")
assert n_cold >= 2, n_cold
warm = s.run("GEMV", *args)
n_scatter = sum(1 for sp in s.tracer.spans if sp.name == "scatter")
n_cached = sum(1 for sp in s.tracer.spans if sp.name == "scatter_cached")
assert n_scatter == n_cold, (n_scatter, n_cold)   # warm run pushed NOTHING
assert n_cached == n_cold, (n_cached, n_cold)     # every warm chunk served
fps = set()
for sp in s.tracer.spans:
    if sp.name == "scatter_cached":
        assert sp.cat == "cpu_dpu", sp.cat
        fps.add(sp.args["fingerprint"])
assert len(fps) == 1, fps
np.testing.assert_array_equal(np.asarray(cold), np.asarray(warm))
entry.compare(warm, entry.ref(*args))
rec_cold, rec_warm = list(s.telemetry.records)
assert not rec_cold.cache_hit and rec_warm.cache_hit
assert rec_warm.n_ranks == 2, rec_warm.n_ranks
s.close()
assert len(s.cache) == 0
print("RESID-RANKED-OK", flush=True)
"""


@pytest.mark.slow
def test_ranked_residency_skips_push_2x4():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    env.pop("REPRO_TRACE", None)
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT_RANKED.format(src=SRC)],
        env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "RESID-RANKED-OK" in out.stdout
