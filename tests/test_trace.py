"""Span tracer + Perfetto export (DESIGN.md §11): the disabled fast path
allocates nothing, spans nest/order correctly, tracks resolve per thread,
the ring buffer bounds memory, exports are valid ``trace_event`` JSON, the
session façade owns the install/export/restore lifecycle, and the ranked
pipeline separates per-rank tracks (8-device subprocess)."""
import json
import os
import subprocess
import sys
import threading
import time

import pytest

from repro.prim.registry import PIPELINEABLE
from repro.runtime.trace import (NULL_SPAN, NULL_TRACER, Span, Tracer,
                                 get_tracer, set_tracer)

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


@pytest.fixture(autouse=True)
def _clean_tracer():
    """Start every test from the disabled default: under REPRO_TRACE (the
    CI 8-bank leg) earlier test files' sessions install tracers, and
    last-opened-wins means one left open would otherwise leak in here."""
    prev = set_tracer(NULL_TRACER)
    yield
    set_tracer(prev)


# -- disabled fast path -------------------------------------------------------

def test_default_tracer_is_null_and_allocation_free():
    tr = get_tracer()
    assert tr is NULL_TRACER and not tr.enabled and len(tr) == 0
    # span() returns the ONE shared no-op context manager — no allocation
    assert tr.span("x", "cat", workload="VA") is NULL_SPAN
    assert tr.track("rank-0") is NULL_SPAN
    with tr.span("x"):
        pass
    tr.emit("x", "cat", 0.0, 1.0)               # no-op, records nothing
    assert len(tr) == 0


def test_set_tracer_installs_and_returns_previous():
    t = Tracer()
    prev = set_tracer(t)
    try:
        assert get_tracer() is t and t.enabled
    finally:
        assert set_tracer(prev) is t
    assert get_tracer() is prev


# -- recording ----------------------------------------------------------------

def test_span_context_manager_records_interval_and_args():
    tr = Tracer()
    with tr.span("work", "dpu", track="rank-0", req=3, bytes=64):
        time.sleep(0.001)
    (s,) = tr.spans
    assert s.name == "work" and s.cat == "dpu" and s.track == "rank-0"
    assert s.args == {"req": 3, "bytes": 64}
    assert s.dur >= 0.001 and s.t1 >= s.t0


def test_spans_nest_inner_exits_first():
    tr = Tracer()
    with tr.span("outer", "session"):
        with tr.span("inner", "dpu"):
            pass
    inner, outer = tr.spans
    assert (inner.name, outer.name) == ("inner", "outer")
    assert outer.t0 <= inner.t0 and inner.t1 <= outer.t1


def test_track_resolution_thread_name_override_and_explicit():
    tr = Tracer()
    tr.emit("a", "dpu", 0.0, 1.0)                       # MainThread -> host
    with tr.track("rank-0"):                            # thread-local wins
        tr.emit("b", "dpu", 0.0, 1.0)
        tr.emit("c", "dpu", 0.0, 1.0, track="session")  # explicit wins more
    tr.emit("d", "dpu", 0.0, 1.0)                       # override restored

    def worker():
        tr.emit("e", "dpu", 0.0, 1.0)                   # pim-X -> X

    t = threading.Thread(target=worker, name="pim-rank-7")
    t.start()
    t.join()
    assert [s.track for s in tr.spans] == \
        ["host", "rank-0", "session", "host", "rank-7"]


def test_ring_buffer_bounds_spans_and_counts_drops():
    tr = Tracer(max_spans=4)
    for i in range(7):
        tr.emit(f"s{i}", "dpu", float(i), float(i) + 0.5)
    assert len(tr) == 4 and tr.dropped == 3
    assert [s.name for s in tr.spans] == ["s3", "s4", "s5", "s6"]
    assert tr.to_json()["otherData"]["dropped_spans"] == 3


def test_span_dur_clamps_negative():
    assert Span("x", "dpu", 2.0, 1.0, "host").dur == 0.0


# -- Perfetto export ----------------------------------------------------------

def test_export_is_valid_trace_event_json(tmp_path):
    tr = Tracer()
    tr.emit("launch", "dpu", tr.t_origin + 0.001, tr.t_origin + 0.003,
            track="rank-1", req=0, chunk=2)
    tr.emit("scatter", "cpu_dpu", tr.t_origin, tr.t_origin + 0.001,
            track="rank-0")
    tr.emit("merge", "inter_dpu", tr.t_origin, tr.t_origin + 0.002,
            track="host")
    path = tr.export(tmp_path / "t.json")
    doc = json.loads(path.read_text())
    events = doc["traceEvents"]
    assert doc["displayTimeUnit"] == "ms"
    meta = [e for e in events if e["ph"] == "M"]
    spans = [e for e in events if e["ph"] == "X"]
    names = {e["args"]["name"] for e in meta if e["name"] == "thread_name"}
    assert names == {"host", "rank-0", "rank-1"}
    # deterministic track layout: host first, then ranks numerically
    tids = {e["args"]["name"]: e["tid"] for e in meta
            if e["name"] == "thread_name"}
    assert tids["host"] < tids["rank-0"] < tids["rank-1"]
    for e in spans:
        assert e["ts"] >= 0 and e["dur"] >= 0 and e["tid"] in tids.values()
    launch = next(e for e in spans if e["name"] == "launch")
    assert launch["cat"] == "dpu"
    assert launch["args"] == {"req": 0, "chunk": 2}
    assert launch["dur"] == pytest.approx(2000.0, rel=0.01)   # µs


# -- session lifecycle --------------------------------------------------------

def test_session_trace_lifecycle(bank_grid, rng, tmp_path):
    from repro import pim

    assert get_tracer() is NULL_TRACER
    s = pim.PimSession(grid=bank_grid, trace=True)
    assert s.tracer is not None and get_tracer() is s.tracer
    entry = pim.registry()["VA"]
    args = entry.make_args(rng, 1)
    entry.compare(s.run("VA", *args), entry.ref(*args))
    names = {sp.name for sp in s.tracer.spans}
    cats = {sp.cat for sp in s.tracer.spans}
    assert "run:VA" in names and {"session", "queue", "sched"} <= cats
    assert {"split", "scatter", "launch", "device_wait", "copy_out",
            "merge"} <= names
    st = s.stats()
    assert st["trace"]["spans"] == len(s.tracer.spans)
    path = s.trace_export(tmp_path / "va.json")
    assert json.loads(path.read_text())["traceEvents"]
    s.close()
    assert get_tracer() is NULL_TRACER          # restored on close


def test_untraced_session_has_no_tracer(bank_grid):
    from repro import pim

    s = pim.PimSession(grid=bank_grid, trace=False)
    assert s.tracer is None and "trace" not in s.stats()
    with pytest.raises(RuntimeError):
        s.trace_export("nope.json")
    s.close()


def test_trace_path_autoexports_at_close(bank_grid, rng, tmp_path):
    from repro import pim

    out = tmp_path / "auto.json"
    s = pim.PimSession(grid=bank_grid, trace=str(out))
    entry = pim.registry()["VA"]
    s.run("VA", *entry.make_args(rng, 1))
    assert not out.exists()
    s.close()
    assert json.loads(out.read_text())["traceEvents"]


def test_repro_trace_env_hook(bank_grid, rng, tmp_path, monkeypatch):
    from repro import pim

    out = tmp_path / "env.json"
    monkeypatch.setenv("REPRO_TRACE", str(out))
    s = pim.PimSession(grid=bank_grid)          # trace=None -> env hook
    entry = pim.registry()["VA"]
    s.run("VA", *entry.make_args(rng, 1))
    s.close()
    assert json.loads(out.read_text())["traceEvents"]
    monkeypatch.setenv("REPRO_TRACE", "")
    s2 = pim.PimSession(grid=bank_grid)         # empty -> disabled
    assert s2.tracer is None
    s2.close()


def test_serialized_fallback_emits_span(bank_grid, rng):
    from repro import pim

    s = pim.PimSession(grid=bank_grid, trace=True)
    entry = pim.registry()["NW"]                # serialized-only workload
    s.run("NW", *entry.make_args(rng, 1))
    assert any(sp.name == "serialized" and sp.cat == "dpu"
               for sp in s.tracer.spans)
    s.close()


def test_transfer_records_mirror_to_spans(bank_grid, rng):
    from repro.core import transfer as tx

    tr = Tracer()
    prev = set_tracer(tr)
    try:
        x = rng.integers(0, 99, 8 * bank_grid.n_banks).astype("int32")
        banked, rec = tx.push_parallel(bank_grid, x)
        _, rec2 = tx.pull_parallel(bank_grid, banked)
    finally:
        set_tracer(prev)
    kinds = [s.name for s in tr.spans]
    assert kinds == ["cpu_dpu_parallel", "dpu_cpu_parallel"]
    assert all(s.cat == "transfer" for s in tr.spans)
    assert tr.spans[0].args["bytes"] == rec.nbytes
    assert tr.spans[0].dur == pytest.approx(rec.seconds, rel=1e-6)


# -- trace_view ---------------------------------------------------------------

def test_trace_view_summary_and_top(tmp_path):
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import trace_view

    tr = Tracer()
    t0 = tr.t_origin
    for k in range(4):                  # overlapped 2-stage pipeline shape
        tr.emit("scatter", "cpu_dpu", t0 + k * 0.01, t0 + k * 0.01 + 0.004,
                track="rank-0")
        tr.emit("launch", "dpu", t0 + k * 0.01 + 0.004,
                t0 + (k + 1) * 0.01, track="rank-0")
    path = tr.export(tmp_path / "v.json")
    spans, tracks = trace_view.split_events(trace_view.load_events(path))
    summ = trace_view.stage_summary(spans)
    assert summ["bottleneck"] == "dpu"
    assert 0.0 < summ["overlap_efficiency"] <= 1.0
    top = trace_view.top_slowest(spans, tracks, 3)
    assert len(top) == 3 and top[0]["ms"] >= top[-1]["ms"]
    text = trace_view.render(path, top=3)
    md = trace_view.render(path, top=3, markdown=True)
    assert "bottleneck stage dpu" in text and "| stage |" in md
    assert trace_view.main([str(path), "--top", "2", "--summary"]) == 0


# -- ranked pipeline: per-rank track separation (8-device subprocess) ---------

SCRIPT = r"""
import sys; sys.path.insert(0, {src!r}); sys.path.insert(0, {root!r})
import json
import numpy as np
from repro import pim

rng = np.random.default_rng(0)
s = pim.session(ranks=2, banks_per_rank=4, trace=True)   # deterministic
entry = pim.registry()["VA"]
s.map("VA", [entry.make_args(rng, 1) for _ in range(3)])
s.trace_export("{out}")
s.close()
doc = json.load(open("{out}"))
tids = {{e["args"]["name"]: e["tid"] for e in doc["traceEvents"]
        if e.get("ph") == "M" and e.get("name") == "thread_name"}}
by_track = {{}}
for e in doc["traceEvents"]:
    if e.get("ph") == "X":
        by_track.setdefault(e["tid"], []).append(e)
for rank in ("rank-0", "rank-1"):
    evs = by_track[tids[rank]]
    names = {{e["name"] for e in evs}}
    assert {{"scatter", "launch", "device_wait", "copy_out"}} <= names, \
        (rank, names)
    assert all("chunk" in e["args"] for e in evs), rank
# within a rank track the spans are sequential host-observed windows
# (scatter and launch = async enqueues, device_wait = await); the
# concurrency the trace must SHOW is *across* tracks — rank-0 and rank-1
# pipelines busy at the same time (the paper's rank-parallel transfers,
# DESIGN.md §10)
r0, r1 = by_track[tids["rank-0"]], by_track[tids["rank-1"]]
overlapped = any(
    a["ts"] < b["ts"] + b["dur"] and b["ts"] < a["ts"] + a["dur"]
    for a in r0 for b in r1)
assert overlapped, "rank-0 and rank-1 spans never overlap"
assert {{"merge"}} <= {{e["name"] for e in by_track[tids["host"]]}}
print("TRACE-RANKED-OK", len(doc["traceEvents"]), flush=True)
"""


def test_ranked_tracks_8_devices(tmp_path):
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    env.pop("REPRO_TRACE", None)        # explicit trace=True must suffice
    out = subprocess.run(
        [sys.executable, "-c",
         SCRIPT.format(src=SRC, root=ROOT, out=tmp_path / "ranked.json")],
        capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "TRACE-RANKED-OK" in out.stdout


# -- one span call, two sinks: the profiler's clock ---------------------------

def test_span_with_no_sink_is_null_span():
    from repro.runtime.trace import profiling, span, tracing
    assert not profiling() and not tracing()
    assert span("scatter", "cpu_dpu", req=3, chunk=0, bytes=64) is NULL_SPAN
    with span("wait") as sp:
        sp.tag(req=1)                       # no-op, like the span itself
        sp.stamp(0.0, 1.0)


def test_span_records_to_the_ring_buffer_with_tags():
    from repro.runtime.trace import span
    tr = Tracer()
    prev = set_tracer(tr)
    try:
        with span("pop", "sched", workload="VA") as sp:
            sp.tag(req=5, requests=2)
        with span("copy", "transfer") as sp:
            sp.stamp(tr.t_origin, tr.t_origin + 0.25)
    finally:
        set_tracer(prev)
    pop, copy = tr.spans
    assert (pop.name, pop.cat, pop.track) == ("pop", "sched", "host")
    assert pop.args == {"workload": "VA", "req": 5, "requests": 2}
    assert copy.dur == pytest.approx(0.25)


def _profiled_lines(path) -> list:
    """Each host thread's ``pim.*`` events as (name, start, end, stats)."""
    from jax.profiler import ProfileData
    lines = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                    dict(e.stats)) for e in line.events
                   if e.name.startswith("pim.")]
            if evs:
                lines.append(evs)
    return lines


@pytest.mark.parametrize("workload", PIPELINEABLE)
def test_profiler_spans_of_one_served_request(bank_grid, rng, tmp_path,
                                              workload):
    """Under a profiler session alone (no tracer), one request served by the
    scheduler thread leaves nested ``pim.*`` spans that all carry its id,
    and the client's ``pim.submit`` carries the same id."""
    import jax

    from repro import pim

    entry = pim.registry()[workload]
    args = entry.make_args(rng, 1)
    s = pim.PimSession(grid=bank_grid, trace=False).start()
    try:
        s.run(workload, *args)              # compile outside the profile
        with jax.profiler.trace(str(tmp_path)):
            out = s.run(workload, *args)
    finally:
        s.close()
    entry.compare(out, entry.ref(*args))
    (rec,) = [r for r in s.telemetry.records][-1:]
    lines = _profiled_lines(next(tmp_path.rglob("*.xplane.pb")))
    (serving,) = [ln for ln in lines
                  if any(e[0] == "pim.batch" for e in ln)]
    submits = [e[3]["req"] for ln in lines for e in ln
               if e[0] == "pim.submit" and ln is not serving]
    assert submits == [rec.request_id]
    names = {e[0] for e in serving}
    assert {"pim.pop", "pim.batch", "pim.split", "pim.launch",
            "pim.device_wait", "pim.copy_out", "pim.merge",
            "pim.fulfill"} <= names
    assert names & {"pim.scatter", "pim.scatter_cached"}
    assert {e[3].get("req") for e in serving} == {rec.request_id}
    (batch,) = [e for e in serving if e[0] == "pim.batch"]
    for name, t0, t1, _ in serving:         # properly nested, one thread
        if name not in ("pim.batch", "pim.pop", "pim.wait"):
            assert batch[1] <= t0 <= t1 <= batch[2], name
        for other in serving:
            disjoint = t1 <= other[1] or other[2] <= t0
            inside = other[1] <= t0 and t1 <= other[2]
            outside = t0 <= other[1] and other[2] <= t1
            assert disjoint or inside or outside, (name, other[0])
    chunks = {e[3]["chunk"] for e in serving if e[0] == "pim.launch"}
    assert chunks == set(range(rec.n_chunks))


def test_host_self_and_device_wait_fit_in_the_service_time(bank_grid, rng):
    from repro import pim

    s = pim.PimSession(grid=bank_grid, trace=False)
    for workload in ("GEMV", "VA", "HST"):
        entry = pim.registry()[workload]
        s.map(workload, [entry.make_args(rng, 1) for _ in range(3)])
    s.close()
    recs = list(s.telemetry.records)
    assert len(recs) == 9
    for rec in recs:
        assert rec.host_self_s > 0 and rec.device_wait_s >= 0
        assert rec.host_self_s + rec.device_wait_s <= rec.service_s
        assert rec.device_wait_s <= rec.phases.dpu_cpu


def test_trace_view_summarises_a_session_export(bank_grid, rng, tmp_path):
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import trace_view

    from repro import pim

    s = pim.PimSession(grid=bank_grid, trace=True)
    entry = pim.registry()["GEMV"]
    args = entry.make_args(rng, 1)
    s.run("GEMV", *args)
    s.run("GEMV", *args)                    # warm: served from residency
    path = s.trace_export(tmp_path / "gemv.json")
    s.close()
    spans, _ = trace_view.split_events(trace_view.load_events(path))
    assert trace_view.residency_summary(spans)["cached_spans"] > 0
    stages = trace_view.stage_summary(spans)["stages"]
    assert {"split", "cpu_dpu", "dpu", "dpu_cpu", "inter_dpu", "sched",
            "queue", "session"} <= set(stages)
    assert "bottleneck stage" in trace_view.render(path)
