"""Multi-tenant serving tier (DESIGN.md §13): weighted-fair dispatch,
EDF-vs-FIFO deadline behavior, the three shed policies, exact outcome
accounting under open-loop overload, drain-on-close under a full queue,
the QoS request-surface contract (RequestOptions + legacy ``priority=``
shim), elastic rank allocation, concurrent ``stats()`` consistency, and —
at 8 simulated banks — per-tenant Perfetto trace tracks."""
import dataclasses
import os
import subprocess
import sys
import threading
import time
import warnings

import numpy as np
import pytest

from repro import pim
from repro.pim import DeadlineExpired, QueueFull, RequestOptions
from repro.runtime.elastic import RankAllocator
from repro.runtime.qos import TenantState, resolve_options


def _args(rng, n=256):
    a = rng.integers(0, 9, n).astype(np.int32)
    return a, a


# -- the QoS request surface (satellite: API redesign) ------------------------

def test_request_options_validation():
    assert RequestOptions().tenant == "default"
    with pytest.raises(ValueError):
        RequestOptions(deadline_s=0.0)
    with pytest.raises(ValueError):
        RequestOptions(deadline_s=-1.0)
    with pytest.raises(ValueError):
        RequestOptions(weight=0.0)


def test_legacy_priority_shim_warns_and_maps():
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        opts = resolve_options(priority=3)
    assert opts == RequestOptions(priority=3)
    assert len(w) == 1 and issubclass(w[0].category, DeprecationWarning)
    assert "RequestOptions" in str(w[0].message)
    with pytest.raises(ValueError, match="not both"):
        resolve_options(RequestOptions(priority=1), priority=2)
    with warnings.catch_warnings():
        warnings.simplefilter("error")     # options= path must not warn
        assert resolve_options(RequestOptions(priority=4)).priority == 4
        assert resolve_options() == RequestOptions()


def test_session_verbs_accept_options_and_shim(bank_grid, rng):
    s = pim.PimSession(grid=bank_grid)
    a, b = _args(rng)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        req = s.submit("VA", a, b, options=RequestOptions(tenant="t1"))
        out = s.run("VA", a, b, options=RequestOptions(priority=2))
        outs = s.map("VA", [(a, b)], options=RequestOptions(tenant="t1"))
    np.testing.assert_array_equal(req.result(timeout=0), a + b)
    np.testing.assert_array_equal(out, a + b)
    np.testing.assert_array_equal(outs[0], a + b)
    assert req.record.tenant == "t1"
    with pytest.deprecated_call():
        s.submit("VA", a, b, priority=1)
    with pytest.deprecated_call():
        s.run("VA", a, b, priority=1)
    s.close()


def test_map_direct_path_stamps_tenant(bank_grid, rng):
    """The deterministic map() fast path bypasses the queue but its
    telemetry records must still carry the request's tenant."""
    s = pim.PimSession(grid=bank_grid)
    a, b = _args(rng)
    s.map("VA", [(a, b), (a, b)], options=RequestOptions(tenant="mapper"))
    recs = s.telemetry.snapshot_records()
    assert [r.tenant for r in recs] == ["mapper", "mapper"]
    assert s.stats()["tenants"]["mapper"]["completed"] == 2
    s.close()


# -- weighted-fair dispatch ---------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TenantSpec:
    """One tenant of the saturating probe."""

    name: str
    weight: float


def run_saturating(session, specs, n_per_tenant: int) -> dict:
    """Weighted-fair goodput under saturation.

    Pre-fills ``n_per_tenant`` same-shape VA requests per tenant, drains,
    and counts each tenant's dispatches inside the *fair window*: the
    prefix of dispatches up to the first tenant running out of backlog.
    Weighted fairness is only defined while every tenant is backlogged.

    Service is charged per dispatched batch, so the window quantizes at
    the session's ``max_batch_requests``: open the session with a small
    one.  VA is warmed once (under the default tenant) before the
    prefill, so compilation is not billed to the first tenant.
    """
    args = pim.registry()["VA"].make_args(np.random.default_rng(0), 1)
    session.run("VA", *args)
    reqs: dict[str, list] = {s.name: [] for s in specs}
    for spec in specs:
        opts = RequestOptions(tenant=spec.name, weight=spec.weight)
        for _ in range(n_per_tenant):
            reqs[spec.name].append(session.submit("VA", *args, options=opts))
    session.drain()

    order = sorted(((rec.t_start, rec.tenant)
                    for rec in session.telemetry.snapshot_records()
                    if rec.tenant in reqs), key=lambda p: p[0])
    served = dict.fromkeys(reqs, 0)
    window = dict(served)
    for _, tenant in order:
        served[tenant] += 1
        if served[tenant] == n_per_tenant:   # first tenant exhausted
            window = dict(served)
            break
    total = sum(window.values()) or 1
    wsum = sum(s.weight for s in specs)
    rows = [{"tenant": s.name, "window_share": window[s.name] / total,
             "fair_share": s.weight / wsum} for s in specs]
    return {"tenants": rows,
            "measured_ratio": (window[specs[0].name]
                               / max(1, window[specs[1].name])),
            "expected_ratio": specs[0].weight / specs[1].weight,
            "shed": sum(isinstance(r._error, QueueFull)
                        for rs in reqs.values() for r in rs)}


def test_weighted_fair_goodput_ratio(bank_grid, rng):
    """Under saturation (both tenants pre-filled), the completion ratio in
    the window where both stay backlogged must track the 2:1 weights.
    Virtual time is charged from *measured* service, so a host-noise spike
    can skew one window, so one retry is allowed."""
    specs = (TenantSpec(name="gold", weight=2.0),
             TenantSpec(name="free", weight=1.0))
    for attempt in range(2):
        s = pim.PimSession(grid=bank_grid, max_batch_requests=2,
                           tenants={"gold": 2.0, "free": 1.0})
        res = run_saturating(s, specs, n_per_tenant=16)
        s.close()
        assert res["shed"] == 0
        assert res["expected_ratio"] == pytest.approx(2.0)
        if abs(res["measured_ratio"] - 2.0) <= 0.5 or attempt:
            break
    assert res["measured_ratio"] == pytest.approx(2.0, rel=0.25)


def test_weighted_fair_three_tenants(bank_grid, rng):
    """Three tenants at 3:2:1 — every tenant's share of the fair window
    must track its weight fraction, not just the top pair's ratio."""
    specs = (TenantSpec(name="a", weight=3.0),
             TenantSpec(name="b", weight=2.0),
             TenantSpec(name="c", weight=1.0))
    weights = {t.name: t.weight for t in specs}
    for attempt in range(2):
        s = pim.PimSession(grid=bank_grid, max_batch_requests=1,
                           tenants=weights)
        res = run_saturating(s, specs, n_per_tenant=12)
        s.close()
        assert res["shed"] == 0
        ok = all(abs(row["window_share"] - row["fair_share"])
                 <= 0.25 * row["fair_share"] for row in res["tenants"])
        if ok or attempt:
            break
    for row in res["tenants"]:
        assert row["window_share"] == pytest.approx(row["fair_share"],
                                                    rel=0.25), res


def test_idle_tenant_accrues_no_credit(bank_grid, rng):
    """An idle tenant catches up to the virtual clock on re-activation: it
    must not bank service credit and then starve the busy tenant."""
    s = pim.PimSession(grid=bank_grid, max_batch_requests=1,
                       tenants={"busy": 1.0, "lazy": 1.0})
    sched = s.scheduler
    a, b = _args(rng)
    for _ in range(4):
        s.submit("VA", a, b, options=RequestOptions(tenant="busy"))
    s.drain()
    busy_vt = sched.tenants()["busy"]["vtime"]
    assert busy_vt > 0
    s.submit("VA", a, b, options=RequestOptions(tenant="lazy"))
    assert sched.tenants()["lazy"]["vtime"] >= busy_vt  # caught up, not 0
    s.close()


def test_fifo_policy_ignores_priority_and_tenants(bank_grid, rng):
    """policy="fifo" is the baseline: global submission order, priorities
    and weights inert."""
    s = pim.PimSession(grid=bank_grid, policy="fifo", max_batch_requests=1,
                       tenants={"a": 5.0, "b": 1.0})
    a, b = _args(rng, 64)
    first = s.submit("VA", a, b, options=RequestOptions(tenant="b"))
    second = s.submit("RED", a, options=RequestOptions(tenant="a",
                                                       priority=9))
    s.drain()
    order = sorted(s.telemetry.snapshot_records(), key=lambda r: r.t_start)
    assert [r.request_id for r in order] == [first.record.request_id,
                                             second.record.request_id]
    s.close()


# -- deadlines: EDF beats FIFO ------------------------------------------------

def _deadline_miss_count(bank_grid, rng, policy):
    """One bulk tenant floods the queue; a latency tenant submits tight-
    deadline requests behind it.  The deadline is calibrated to half the
    *measured* bulk drain time, so qos (which dispatches the latency
    tenant after ~one bulk batch) meets it and fifo (which serves all
    bulk work first, in submission order) burns it."""
    n_bulk = 10
    s = pim.PimSession(grid=bank_grid, policy=policy, max_batch_requests=1)
    a, b = _args(rng, 1 << 19)
    s.run("VA", a, b)                    # compile both workloads up front
    s.run("RED", a)
    t0 = time.perf_counter()
    for _ in range(n_bulk):
        s.submit("VA", a, b)
    s.drain()
    deadline = (time.perf_counter() - t0) / 2
    bulk = [s.submit("VA", a, b) for _ in range(n_bulk)]
    tight = [s.submit("RED", a,
                      options=RequestOptions(tenant="latency",
                                             deadline_s=deadline))
             for _ in range(2)]
    s.drain()
    for r in bulk:
        r.result(timeout=0)
    missed = 0
    for r in tight:
        try:
            r.result(timeout=0)
        except DeadlineExpired:
            missed += 1
    s.close()
    return missed


def test_edf_beats_fifo_on_deadline_misses(bank_grid, rng):
    assert _deadline_miss_count(bank_grid, rng, "qos") == 0
    assert _deadline_miss_count(bank_grid, rng, "fifo") >= 1


def test_expired_request_counted_and_raised(bank_grid, rng):
    s = pim.PimSession(grid=bank_grid)
    a, b = _args(rng)
    req = s.submit("VA", a, b, options=RequestOptions(
        tenant="t", deadline_s=0.01))
    time.sleep(0.03)
    assert s.drain() == 0                # dropped, not run
    with pytest.raises(DeadlineExpired) as ei:
        req.result(timeout=0)
    assert ei.value.tenant == "t" and ei.value.late_s > 0
    st = s.stats()
    assert st["expired"] == 1
    assert st["tenants"]["t"]["expired"] == 1
    assert st["counters"].get("expired") == 1
    s.close()


# -- backpressure + shedding --------------------------------------------------

def test_shed_reject_raises_and_counts(bank_grid, rng):
    s = pim.PimSession(grid=bank_grid, max_queue_depth=2, shed="reject")
    a, b = _args(rng)
    keep = [s.submit("VA", a, b) for _ in range(2)]
    with pytest.raises(QueueFull) as ei:
        s.submit("VA", a, b)
    assert ei.value.max_depth == 2
    s.drain()
    for r in keep:                       # admitted requests still complete
        np.testing.assert_array_equal(r.result(timeout=0), a + b)
    st = s.stats()
    assert st["shed"] == 1 and st["tenants"]["default"]["shed"] == 1
    s.close()


def test_shed_drop_evicts_least_urgent(bank_grid, rng):
    s = pim.PimSession(grid=bank_grid, max_queue_depth=2, shed="drop")
    a, b = _args(rng)
    victim = s.submit("VA", a, b, options=RequestOptions(priority=0))
    keeper = s.submit("VA", a, b, options=RequestOptions(priority=5))
    newcomer = s.submit("VA", a, b, options=RequestOptions(priority=3))
    assert victim.done()                 # evicted synchronously
    with pytest.raises(QueueFull):
        victim.result(timeout=0)
    s.drain()
    np.testing.assert_array_equal(keeper.result(timeout=0), a + b)
    np.testing.assert_array_equal(newcomer.result(timeout=0), a + b)
    # a newcomer that is itself the least urgent is the one refused
    s.submit("VA", a, b, options=RequestOptions(priority=5))
    s.submit("VA", a, b, options=RequestOptions(priority=5))
    with pytest.raises(QueueFull):
        s.submit("VA", a, b, options=RequestOptions(priority=-1))
    s.close()


def test_shed_block_applies_backpressure(bank_grid, rng):
    """shed=False blocks the submitter until the worker drains below the
    bound — every request eventually completes, none is refused."""
    s = pim.PimSession(grid=bank_grid, max_queue_depth=2, shed=False)
    s.start()
    a, b = _args(rng)
    reqs = [s.submit("VA", a, b) for _ in range(10)]
    for r in reqs:
        np.testing.assert_array_equal(r.result(timeout=60), a + b)
    assert s.stats()["shed"] == 0
    s.close()


def _open_loop_gaps(arrivals: str, n: int, rng) -> np.ndarray:
    """Inter-arrival gaps (seconds): ``steady`` Poisson at 0.2 ms,
    ``bursty`` back-to-back bursts of 8 with 2 ms between bursts."""
    if arrivals == "steady":
        return rng.exponential(2e-4, n)
    gaps = np.zeros(n)
    gaps[::8] = 2e-3
    return gaps


@pytest.mark.parametrize("arrivals", ["steady", "bursty"])
@pytest.mark.parametrize("shed", ["reject", "drop"])
def test_open_loop_overload_accounts_every_request(bank_grid, rng, shed,
                                                   arrivals):
    """Open-loop arrivals far above capacity into a depth-4 queue, at mixed
    priorities so that ``drop`` evicts admitted requests: every offered
    request ends completed, shed or expired, exactly once, and the
    session's counters agree with what the callers saw."""
    n, deadline = 48, 5.0
    arrivals_rng = np.random.default_rng(7)
    a, b = _args(rng, 1 << 16)
    s = pim.PimSession(grid=bank_grid, max_queue_depth=4, shed=shed,
                       max_batch_requests=1)
    s.run("VA", a, b)                    # compile outside the overload
    s.telemetry.reset()
    s.start()
    reqs, shed_n = [], 0
    for gap in _open_loop_gaps(arrivals, n, arrivals_rng):
        time.sleep(gap)
        opts = RequestOptions(tenant="load", deadline_s=deadline,
                              priority=int(arrivals_rng.integers(0, 4)))
        try:
            reqs.append(s.submit("VA", a, b, options=opts))
        except QueueFull:
            shed_n += 1
    completed = expired = 0
    for r in reqs:
        try:
            np.testing.assert_array_equal(r.result(timeout=60), a + b)
            completed += 1
        except QueueFull:                # evicted after admission (drop)
            shed_n += 1
        except DeadlineExpired:
            expired += 1
    st = s.stats()
    s.close()
    assert completed + shed_n + expired == n
    assert 0 < shed_n < n
    assert (st["requests"], st["shed"], st["expired"]) == (
        completed, shed_n, expired)
    assert st["tenants"]["load"]["completed"] == completed
    assert st["tenants"]["load"]["shed"] == shed_n


def test_close_drains_full_queue(bank_grid, rng):
    """Drain-on-close under a full queue: every admitted future settles."""
    s = pim.PimSession(grid=bank_grid, max_queue_depth=4, shed="reject")
    a, b = _args(rng)
    reqs = [s.submit("VA", a, b) for _ in range(4)]
    with pytest.raises(QueueFull):
        s.submit("VA", a, b)
    s.close()
    for r in reqs:
        np.testing.assert_array_equal(r.result(timeout=0), a + b)


def test_serving_mode_close_drains_full_queue(bank_grid, rng):
    with pim.PimSession(grid=bank_grid, max_queue_depth=4,
                        shed="reject") as s:
        a, b = _args(rng)
        reqs = []
        for _ in range(12):              # worker races the submitter; some
            try:                         # submits may land on a full queue
                reqs.append(s.submit("VA", a, b))
            except QueueFull:
                pass
    assert reqs
    for r in reqs:
        np.testing.assert_array_equal(r.result(timeout=0), a + b)


def test_bad_depth_and_policy_rejected(bank_grid):
    with pytest.raises(ValueError):
        pim.PimSession(grid=bank_grid, max_queue_depth=0)
    with pytest.raises(ValueError):
        pim.PimSession(grid=bank_grid, policy="lifo")
    with pytest.raises(ValueError):
        pim.PimSession(grid=bank_grid, shed="maybe")


# -- elastic rank allocation (unit level) -------------------------------------

def test_rank_allocator_shares_track_weighted_demand():
    ra = RankAllocator(8, alpha=1.0)     # no smoothing: direct assertions
    ra.update({"a": 100.0, "b": 100.0})
    w = {"a": 3.0, "b": 1.0}
    assert ra.ranks_for("a", w) == 6     # 3/4 of 8
    assert ra.ranks_for("b", w) == 2
    ra.update({"a": 100.0, "b": 0.0})    # b went idle -> a is sole tenant
    assert ra.ranks_for("a", w) is None  # no elastic opinion
    assert ra.ranks_for("b", w) is None


def test_rank_allocator_straggler_cap_halves_and_relaxes():
    ra = RankAllocator(8, alpha=1.0)
    ra.update({"a": 100.0, "b": 100.0})
    w = {"a": 1.0, "b": 1.0}
    assert ra.ranks_for("a", w) == 4
    ra.on_straggle(0, 1.0, 0.1)
    ra.on_straggle(1, 1.0, 0.1)
    assert ra.cap == 2
    assert ra.ranks_for("a", w) == 2     # capped below the fair share
    ra.update({"a": 100.0, "b": 0.0})
    assert ra.ranks_for("a", w) == 2     # sole tenant, but the cap binds
    for _ in range(6):
        ra.relax()
    assert ra.cap == 8
    assert ra.ranks_for("a", w) is None  # cap released -> default again


def test_tenant_state_charge_is_weight_scaled():
    t = TenantState("t", weight=2.0)
    assert t.charge(1.0) == pytest.approx(0.5)
    t.activate(10.0)                     # empty queue: catch up to vclock
    assert t.vtime == 10.0


# -- stats() consistency under concurrent submitters (satellite fix) ----------

def test_stats_consistent_under_concurrent_load(bank_grid, rng):
    """Hammer stats() from a thread while the worker drains: every
    snapshot's top-level counts must equal the sum of its per-workload and
    per-tenant breakdowns (they are computed under one lock now)."""
    s = pim.PimSession(grid=bank_grid)
    a, b = _args(rng, 64)
    stop = threading.Event()
    bad: list = []

    def hammer():
        while not stop.is_set():
            st = s.stats()
            n = st["requests"]
            if n == 0:
                continue
            by_wl = sum(w["requests"] for w in st["workloads"].values())
            by_tn = sum(t.get("completed", 0)
                        for t in st.get("tenants", {}).values())
            if not (n == by_wl == by_tn == st["counters"]["requests"]):
                bad.append((n, by_wl, by_tn, st["counters"]["requests"]))

    thread = threading.Thread(target=hammer)
    thread.start()
    try:
        with s:
            reqs = [s.submit("VA", a, b,
                             options=RequestOptions(
                                 tenant=("x", "y")[i % 2]))
                    for i in range(40)]
            for r in reqs:
                r.result(timeout=60)
    finally:
        stop.set()
        thread.join()
    assert not bad, bad[:5]


def test_tenant_rows_merge_queue_and_completion_sides(bank_grid, rng):
    s = pim.PimSession(grid=bank_grid, tenants={"gold": 2.0})
    a, b = _args(rng)
    s.run("VA", a, b, options=RequestOptions(tenant="gold"))
    row = s.stats()["tenants"]["gold"]
    assert row["completed"] == 1 and row["submitted"] == 1
    assert row["weight"] == 2.0 and row["queued"] == 0
    assert row["mean_latency_s"] > 0
    s.close()


# -- 8 banks: per-tenant trace tracks (single subprocess) ---------------------

SCRIPT = r"""
import json, sys; sys.path.insert(0, {src!r})
import numpy as np
from repro import pim
from repro.pim import RequestOptions
s = pim.session(tenants={{"gold": 2.0, "free": 1.0}}, trace="trace_qos.json")
assert s.n_banks == 8, s.n_banks
a = np.arange(4096, dtype=np.int32)
for i in range(6):
    s.submit("VA", a, a, options=RequestOptions(
        tenant=("gold", "free")[i % 2]))
s.drain()
assert s.stats()["tenants"]["gold"]["completed"] == 3
s.close()
events = json.load(open("trace_qos.json"))["traceEvents"]
names = [e["args"]["name"] for e in events
         if e["ph"] == "M" and e["name"] == "thread_name"]
assert "tenant-gold" in names and "tenant-free" in names, names
# tenant lanes are ordered after the rank lanes, before anything else
gold_tid = [e["tid"] for e in events if e["ph"] == "M"
            and e["name"] == "thread_name"
            and e["args"]["name"] == "tenant-gold"][0]
serves = [e for e in events if e.get("ph") == "X" and e["tid"] == gold_tid
          and e["name"] == "serve"]
assert len(serves) == 3, serves
assert all(e["args"]["tenant"] == "gold" for e in serves)
print("QOS-TRACE-OK")
"""


@pytest.fixture(scope="session")
def eight_bank_qos_trace(tmp_path_factory):
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    out = subprocess.run([sys.executable, "-c", SCRIPT.format(src=src)],
                         env=env, capture_output=True, text=True,
                         timeout=900, cwd=tmp_path_factory.mktemp("qos"))
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


@pytest.mark.slow
def test_per_tenant_trace_tracks_8_banks(eight_bank_qos_trace):
    assert "QOS-TRACE-OK" in eight_bank_qos_trace
