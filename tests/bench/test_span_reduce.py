"""The device's idle time named by the program's spans, on traced windows
recorded on a v5e chip through the harness (as ``bench/run.py --trace 1``
runs them) at the cells' own sizes: 0.4 s of ``prim-resident-gemv`` and
1 s of ``prim-stream-cols`` (``<cell>.spans.xplane.pb``: the file
``harness.Window`` wrote under ``bench_out/trace/<cell>/``); and on the two
recordings of a program that had no such spans (``<cell>.xplane.pb``)."""
import shutil
import types

import pytest
from benchcase import REPO

import harness
import span_reduce
import trace_reduce

DATA = REPO / "tests" / "bench" / "data"
CELLS = ["prim-resident-gemv", "prim-stream-cols"]


def _spans_file(cell):
    return DATA / f"{cell}.spans.xplane.pb"


@pytest.fixture(scope="module", params=CELLS)
def recorded(request):
    path = str(_spans_file(request.param))
    return (request.param, path, trace_reduce.reduce(path),
            span_reduce.reduce(path))


def test_stages_and_rest_make_the_idle_share(recorded):
    _, _, summary, got = recorded
    parts = (got.share(got.pipeline_s) + got.share(got.scheduler_s)
             + got.share(got.rest_s))
    assert parts == pytest.approx(100.0 * summary.idle_share, abs=0.5)
    assert got.window_s == pytest.approx(summary.window_s, rel=1e-12)
    assert sum(got.spans.values()) == pytest.approx(got.idle_s, rel=1e-9)


def test_stages_cover_nine_tenths_of_the_idle_time(recorded):
    _, _, _, got = recorded
    assert got.idle_s > 0 and got.rest_s >= 0
    assert got.pipeline_s + got.scheduler_s >= 0.9 * got.idle_s
    assert got.pipeline_s > 0 and got.scheduler_s > 0


def test_new_readers_read_numbers(recorded, tmp_path, monkeypatch):
    cell, path, summary, got = recorded
    where = tmp_path / "bench_out" / "trace" / cell / "plugins"
    where.mkdir(parents=True)
    shutil.copy(path, where / "host.xplane.pb")
    monkeypatch.setattr(harness, "ROOT", tmp_path)
    records = [types.SimpleNamespace(t_start=1.0, host_self_s=s)
               for s in (0.001, 0.003)]
    run = harness.Run(trace=summary, cell=types.SimpleNamespace(name=cell),
                      records=records)
    pipe = harness.load_metric("device.idle_in_pipeline.prim").read(run)
    sched = harness.load_metric("device.idle_in_sched.prim").read(run)
    assert pipe == pytest.approx(got.share(got.pipeline_s))
    assert sched == pytest.approx(got.share(got.scheduler_s))
    assert harness.load_metric("pipeline.host_self_ms").read(run) == \
        pytest.approx(2.0)


def test_readers_read_nothing_where_there_is_nothing(recorded, tmp_path,
                                                     monkeypatch):
    """Untraced; no trace file; a file of another window; records of a
    program without the counter."""
    cell, path, summary, _ = recorded
    monkeypatch.setattr(harness, "ROOT", tmp_path)
    cell_ns = types.SimpleNamespace(name=cell)
    pipe = harness.load_metric("device.idle_in_pipeline.prim")
    assert pipe.read(harness.Run(cell=cell_ns)) is None
    assert pipe.read(harness.Run(trace=summary, cell=cell_ns)) is None
    where = tmp_path / "bench_out" / "trace" / cell
    where.mkdir(parents=True)
    shutil.copy(path, where / "host.xplane.pb")
    other = types.SimpleNamespace(window_s=summary.window_s + 1.0)
    assert pipe.read(harness.Run(trace=other, cell=cell_ns)) is None
    old = [types.SimpleNamespace(t_start=1.0)]
    assert harness.load_metric("pipeline.host_self_ms").read(
        harness.Run(records=old)) is None


def test_every_serving_span_carries_a_request(recorded):
    """Inside the window, every ``pim.*`` span of the serving thread is
    tagged with a request, and the serving thread works on exactly the
    requests that the clients' ``pim.submit`` spans sent."""
    from jax.profiler import ProfileData
    _, path, _, _ = recorded
    win, serving, submitted = None, [], set()
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                    dict(e.stats)) for e in line.events]
            win = win or next((e[1:3] for e in evs
                               if e[0] == "bench.window"), None)
            ours = [e for e in evs if e[0] in span_reduce.STAGE]
            if ours:
                serving += ours
            submitted |= {e[3]["req"] for e in evs if e[0] == "pim.submit"}
    inside = [e for e in serving if win[0] <= e[1] and e[2] <= win[1]]
    assert inside and all("req" in e[3] for e in inside)
    served = {e[3]["req"] for e in inside}
    assert served <= submitted and len(served) > 10


@pytest.mark.parametrize("cell", CELLS)
def test_a_program_without_spans_gives_no_shares(cell):
    assert span_reduce.reduce(str(DATA / f"{cell}.xplane.pb")) is None


def test_innermost_span_names_each_instant():
    spans = [(0, 100, "pim.batch"), (10, 40, "pim.split"),
             (20, 30, "pim.cpu_dpu_async"), (50, 60, "pim.fulfill"),
             (120, 130, "pim.wait")]
    assert span_reduce._segments(spans) == [
        (0, 10, "pim.batch", 1), (10, 20, "pim.split", 2),
        (20, 30, "pim.cpu_dpu_async", 2), (30, 40, "pim.split", 2),
        (40, 50, "pim.batch", 1), (50, 60, "pim.fulfill", 1),
        (60, 100, "pim.batch", 1), (120, 130, "pim.wait", 1)]


def test_a_pipeline_span_on_any_serving_thread_wins():
    sched = span_reduce._segments([(0, 100, "pim.batch")])
    rank = span_reduce._segments([(30, 60, "pim.device_wait")])
    got = span_reduce._overlay([sched, rank])
    assert got == [(0, 30, "pim.batch", 1), (30, 60, "pim.device_wait", 2),
                   (60, 100, "pim.batch", 1)]
    totals, names = {}, {}
    span_reduce._attribute([(20, 40), (90, 120)], got, totals, names)
    assert totals == {1: 20, 2: 10}
    assert names == {"pim.batch": 20, "pim.device_wait": 10}
