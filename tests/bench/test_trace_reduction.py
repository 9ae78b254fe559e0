"""The trace reduction on small traces recorded on a v5e chip: a
``prim-resident-gemv`` window at scale 8 (4096 x 256 matrix, two clients,
95 requests of 4 chunks) and a ``prim-stream-cols`` window at scale 1 (two
clients, 56 requests), each 0.3 s, recorded by the harness's traced window.
"""
import types

import pytest
from benchcase import REPO

import harness
import trace_reduce

DATA = REPO / "tests" / "bench" / "data"
PEAKS = harness.load_peaks("TPU v5 lite")


@pytest.fixture(scope="module")
def gemv():
    return trace_reduce.reduce(str(DATA / "prim-resident-gemv.xplane.pb"))


@pytest.fixture(scope="module")
def cols():
    return trace_reduce.reduce(str(DATA / "prim-stream-cols.xplane.pb"))


@pytest.mark.parametrize("name", ["gemv", "cols"])
def test_busy_and_idle_partition_the_window(request, name):
    s = request.getfixturevalue(name)
    assert s.chips == 1
    assert 0 < s.busy_s < s.window_s
    assert 0.0 < s.idle_share < 1.0
    idle = sum(sec for _, sec in s.idle_gaps)
    assert idle == pytest.approx(s.window_s - s.busy_s, rel=1e-6)
    assert all(n.startswith("client.") for n, _ in s.idle_gaps)
    b = s.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_gemv_kernel_time_and_roofline(gemv):
    secs, runs = gemv.kernel_time(r"^jit_matvec$")
    assert runs == 380 and secs == pytest.approx(gemv.busy_s, rel=0.01)
    reader = harness.load_metric("gemv_roofline.prim")
    cell = harness.resolve(harness.load_benchmark(), "prim-resident-gemv")
    run = harness.Run(trace=gemv, peaks=PEAKS, cell=cell,
                      facts={"n_chunks": 4, "n_banks": 1,
                             "gemv_shape": (4096, 256)},
                      records=[types.SimpleNamespace(workload="GEMV")] * 95)
    share = reader.read(run)
    # 4096 x 256 float32 in 1024-row chunks: 1 MiB per run, about 1.3 us at
    # 819 GB/s, against a few us of launch-bound device time per run
    assert 5.0 < share < 105.0


def test_column_kernels_found(cols):
    secs, runs = cols.kernel_time(harness.load_metric(
        "columns_roofline").MODULES)
    # a module spans its operations and the short gaps between them
    assert runs > 0 and secs == pytest.approx(cols.busy_s, rel=0.05)
    assert cols.kernel_time(r"^jit_matvec$") == (0, 0)


def test_idle_share_reader(gemv):
    run = harness.Run(trace=gemv)
    got = harness.load_metric("device.idle_share.prim").read(run)
    assert got == pytest.approx(100.0 * gemv.idle_share)
    assert harness.load_metric("device.idle_share.prim").read(
        harness.Run()) is None


def test_missing_window_is_an_error():
    with pytest.raises(ValueError):
        trace_reduce.reduce(str(DATA / "prim-stream-cols.xplane.pb"),
                            window="no.such.span")
