"""Pipelined runtime *internal layer* (DESIGN.md §5): result equivalence
vs the gold refs under concurrent submission, scheduling policy (priority /
FIFO / batching), and telemetry.

Sessions are constructed through the `repro.pim` façade (DESIGN.md §9) and
unit-level policy tests reach the scheduler underneath via
``PimSession.scheduler`` — the façade itself is covered in
``tests/test_session.py``."""
import warnings

import numpy as np
import pytest

from repro import pim, prim
from repro.pim import RequestOptions
from repro.prim.common import CHUNKED
from repro.prim.registry import REGISTRY
from repro.runtime import Telemetry, run_pipelined


def _sched(bank_grid, **kwargs):
    """A scheduler obtained the façade way (deterministic session)."""
    return pim.PimSession(grid=bank_grid, **kwargs).scheduler


def _cases(rng):
    """(workload, args, gold) for all 4 ported workloads."""
    a = rng.integers(0, 100, 10007).astype(np.int32)
    b = rng.integers(0, 100, 10007).astype(np.int32)
    A = rng.normal(size=(131, 64)).astype(np.float32)
    x = rng.normal(size=64).astype(np.float32)
    xr = rng.integers(0, 100, 5001).astype(np.int32)
    xs = rng.integers(0, 1000, 1509).astype(np.int32)
    return [("VA", (a, b), prim.va.ref(a, b)),
            ("GEMV", (A, x), prim.gemv.ref(A, x)),
            ("RED", (xr,), prim.red.ref(xr)),
            ("SEL", (xs,), prim.sel.ref(xs))]


def _check(out, gold):
    np.testing.assert_allclose(np.asarray(out), np.asarray(gold),
                               rtol=1e-4, atol=1e-4)


# -- pipeline layer -----------------------------------------------------------

@pytest.mark.parametrize("n_chunks", [1, 3, 5])
def test_pipelined_matches_ref(bank_grid, rng, n_chunks):
    for name, args, gold in _cases(rng):
        res = run_pipelined(bank_grid, CHUNKED[name], *args,
                            n_chunks=n_chunks)
        _check(res.value, gold)
        assert res.makespan > 0
        assert res.n_chunks == n_chunks


def test_pipelined_vs_serialized_pim(bank_grid, rng):
    """Same decomposition, two execution disciplines, identical results."""
    mods = {"VA": prim.va, "GEMV": prim.gemv, "RED": prim.red,
            "SEL": prim.sel}
    for name, args, _ in _cases(rng):
        serial, _ = mods[name].pim(bank_grid, *args)
        piped = run_pipelined(bank_grid, CHUNKED[name], *args).value
        _check(piped, serial)


# -- scheduler: correctness under concurrent submission -----------------------

def test_concurrent_mixed_submission(bank_grid, rng):
    sched = _sched(bank_grid, n_chunks=3)
    submitted = []
    for rep in range(3):                 # interleave all 4 workloads
        for name, args, gold in _cases(rng):
            submitted.append((sched.submit(
                name, *args, options=RequestOptions(priority=rep)), gold))
    assert sched.pending() == len(submitted)
    assert sched.drain() == len(submitted)
    for req, gold in submitted:
        assert req.done()
        _check(req.result(), gold)


def test_threaded_serving(bank_grid, rng):
    cases = _cases(rng)
    with pim.PimSession(grid=bank_grid, n_chunks=2) as sess:
        submitted = [(sess.submit(name, *args), gold)
                     for name, args, gold in cases for _ in range(2)]
        for req, gold in submitted:
            _check(req.result(timeout=300), gold)
    assert len(sess.telemetry) == len(submitted)


# -- scheduler: policy --------------------------------------------------------

def test_priority_then_fifo(bank_grid, rng):
    sched = _sched(bank_grid, n_chunks=2, max_batch_requests=1)
    a = rng.integers(0, 9, 64).astype(np.int32)
    low = sched.submit("VA", a, a, options=RequestOptions(priority=0))
    mid = sched.submit("RED", a, options=RequestOptions(priority=1))
    high = sched.submit("SEL", a, options=RequestOptions(priority=2))
    mid2 = sched.submit("GEMV", a.astype(np.float32).reshape(8, 8),
                        np.ones(8, np.float32),
                        options=RequestOptions(priority=1))
    sched.drain()
    order = sorted(sched.telemetry.records, key=lambda r: r.t_start)
    ids = [r.request_id for r in order]
    assert ids == [high.record.request_id, mid.record.request_id,
                   mid2.record.request_id, low.record.request_id]


def test_same_workload_batching(bank_grid, rng):
    sched = _sched(bank_grid, n_chunks=2, max_batch_requests=4)
    a = rng.integers(0, 9, 256).astype(np.int32)
    for _ in range(5):
        sched.submit("VA", a, a)
    sched.drain()
    batches = {r.batch_id for r in sched.telemetry.records}
    assert len(batches) == 2             # 4 coalesced + 1 leftover
    sizes = sorted([r.batch_id for r in sched.telemetry.records].count(b)
                   for b in batches)
    assert sizes == [1, 4]


def test_size_aware_batching(bank_grid, rng):
    a = rng.integers(0, 9, 1024).astype(np.int32)
    sched = _sched(bank_grid, n_chunks=2, max_batch_requests=8,
                   max_batch_bytes=3 * a.nbytes * 2)  # fits 3 VA pairs
    for _ in range(4):
        sched.submit("VA", a, a)
    sched.drain()
    sizes = sorted([r.batch_id for r in sched.telemetry.records]
                   .count(b) for b in
                   {r.batch_id for r in sched.telemetry.records})
    assert sizes == [1, 3]


def test_batching_never_jumps_higher_priority(bank_grid, rng):
    """Coalescing stops at the first non-matching entry: a same-workload
    request queued *behind* a higher-priority request must not be pulled
    ahead of it."""
    sched = _sched(bank_grid, n_chunks=2)
    a = rng.integers(0, 9, 64).astype(np.int32)
    va_hi = sched.submit("VA", a, a, options=RequestOptions(priority=2))
    red_mid = sched.submit("RED", a, options=RequestOptions(priority=1))
    va_lo = sched.submit("VA", a, a, options=RequestOptions(priority=0))
    sched.drain()
    order = sorted(sched.telemetry.records, key=lambda r: r.t_start)
    assert [r.request_id for r in order] == [va_hi.record.request_id,
                                            red_mid.record.request_id,
                                            va_lo.record.request_id]
    assert va_hi.record.batch_id != va_lo.record.batch_id


def test_bad_request_does_not_poison_batch(bank_grid, rng):
    """A malformed request coalesced into a batch fails alone; the healthy
    requests in the same batch still complete."""
    sched = _sched(bank_grid, n_chunks=2)
    A = rng.normal(size=(16, 8)).astype(np.float32)
    x = rng.normal(size=8).astype(np.float32)
    good1 = sched.submit("GEMV", A, x)
    bad = sched.submit("GEMV", A, np.ones(5, np.float32))  # shape mismatch
    good2 = sched.submit("GEMV", A, x)
    sched.drain()
    _check(good1.result(timeout=5), prim.gemv.ref(A, x))
    _check(good2.result(timeout=5), prim.gemv.ref(A, x))
    with pytest.raises(Exception):
        bad.result(timeout=5)


def test_unknown_workload_rejected(bank_grid):
    sched = _sched(bank_grid)
    with pytest.raises(KeyError):
        sched.submit("NOPE", np.arange(4))


# -- telemetry ----------------------------------------------------------------

@pytest.mark.parametrize("workload", list(REGISTRY))
def test_telemetry_records(bank_grid, rng, workload):
    entry = REGISTRY[workload]
    args = entry.make_args(rng, 1)
    sink = Telemetry()
    sched = _sched(bank_grid, n_chunks=3, telemetry=sink)
    req = sched.submit(workload, *args, options=RequestOptions(priority=7))
    sched.drain()
    out = req.result(timeout=0)
    entry.compare(out, entry.ref(*args))
    (rec,) = sink.records
    assert rec is req.record
    assert rec.workload == workload and rec.priority == 7
    assert rec.n_items > 0 and rec.bytes_in == entry.arg_nbytes(args)
    if workload == "VA":                 # elementwise: items and bytes exact
        assert rec.n_items == args[0].size and rec.bytes_out == out.nbytes
    assert rec.n_chunks == (3 if entry.pipelineable else 1)
    assert rec.t_submit <= rec.t_start <= rec.t_finish
    assert rec.queue_wait >= 0 and rec.latency_s >= rec.service_s
    assert rec.achieved_gbps > 0
    assert rec.phases.total > 0
    row = rec.row(bank_grid.n_banks)
    assert row["workload"] == workload and row["banks"] == bank_grid.n_banks

    agg = sink.aggregate()
    assert agg["requests"] == 1
    assert agg["requests_per_s"] > 0
    assert agg["bytes_moved"] == rec.bytes_in + rec.bytes_out


def test_telemetry_empty_aggregate():
    assert Telemetry().aggregate() == {"requests": 0}


def test_request_error_propagates(bank_grid):
    sched = _sched(bank_grid)
    bad = sched.submit("GEMV", np.ones((4, 4), np.float32),
                       np.ones(5, np.float32))   # shape mismatch
    sched.drain()
    with pytest.raises(Exception):
        bad.result(timeout=5)


# -- request sizing -----------------------------------------------------------

def test_nitems_is_pytree_aware(rng):
    """MLP's args lead with a *list* of layer matrices: size-aware batching
    must count the batch's leading dim (first array leaf), not fall through
    to the bias vector (satellite fix, mirrors tree_nbytes)."""
    from repro.runtime.scheduler import _nitems
    e = pim.registry()["MLP"]
    args = e.make_args(rng, 1)
    assert _nitems(args) == args[0][0].shape[0]     # 256, not len(bias)=512
    assert _nitems(args) != args[1].shape[0]
    a = rng.integers(0, 9, 7).astype(np.int32)
    assert _nitems((a, a)) == 7                     # flat args unchanged
    assert _nitems((3.5,)) == 0                     # scalars have no items


def test_scheduler_records_mlp_batch_items(bank_grid, rng):
    e = pim.registry()["MLP"]
    args = e.make_args(rng, 1)
    sess = pim.PimSession(grid=bank_grid)
    req = sess.submit("MLP", *args)
    sess.close()
    assert req.record.n_items == args[0][0].shape[0]
    e.compare(req.result(timeout=0), e.ref(*args))


# -- runtime namespace --------------------------------------------------------

def test_runtime_flat_reexports_are_first_class():
    """elastic/straggler graduated from deprecated train-side shims to live
    serving-tier dependencies (DESIGN.md §13): the flat names resolve
    warning-free and are the same objects as the submodules'."""
    import repro.runtime as rt
    from repro.runtime import elastic, straggler
    with warnings.catch_warnings():
        warnings.simplefilter("error")     # any DeprecationWarning fails
        assert rt.carve_mesh is elastic.carve_mesh
        assert rt.RankAllocator is elastic.RankAllocator
        assert rt.StepMonitor is straggler.StepMonitor
        assert rt.Watchdog is straggler.Watchdog
    for name in ("carve_mesh", "RankAllocator", "StepMonitor", "Watchdog",
                 "RequestOptions", "QueueFull", "DeadlineExpired"):
        assert name in rt.__all__
    with pytest.raises(AttributeError):
        rt.no_such_name
