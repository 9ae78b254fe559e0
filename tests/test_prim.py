"""PrIM suite: every workload's banked implementation vs its gold ref
(single-bank here, but for chunked HST also at 4 banks; 8-bank agreement
in test_multibank.py)."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro import prim


def test_va(bank_grid, rng):
    a = rng.integers(0, 100, 1003).astype(np.int32)
    b = rng.integers(0, 100, 1003).astype(np.int32)
    out, times = prim.va.pim(bank_grid, a, b)
    assert (out == prim.va.ref(a, b)).all()
    assert times.total > 0


def test_gemv(bank_grid, rng):
    A = rng.normal(size=(67, 33)).astype(np.float32)
    x = rng.normal(size=33).astype(np.float32)
    out, _ = prim.gemv.pim(bank_grid, A, x)
    np.testing.assert_allclose(out, prim.gemv.ref(A, x), rtol=1e-4, atol=1e-5)


def test_gemv_kernel_path(bank_grid, rng):
    A = rng.normal(size=(64, 128)).astype(np.float32)
    x = rng.normal(size=128).astype(np.float32)
    out, _ = prim.gemv.pim(bank_grid, A, x, use_kernel=True)
    np.testing.assert_allclose(out, prim.gemv.ref(A, x), rtol=1e-4, atol=1e-4)


def test_spmv(bank_grid, rng):
    ip, ix, dv = prim.spmv.random_csr(53, 40, 6, seed=1)
    vals, cols = prim.spmv.csr_to_ell(ip, ix, dv, 53)
    x = rng.normal(size=40).astype(np.float32)
    out, _ = prim.spmv.pim(bank_grid, vals, cols, x)
    np.testing.assert_allclose(out, prim.spmv.ref(vals, cols, x),
                               rtol=1e-4, atol=1e-5)


def test_sel(bank_grid, rng):
    x = rng.integers(0, 1000, 509).astype(np.int32)
    out, _ = prim.sel.pim(bank_grid, x)
    assert (out == prim.sel.ref(x)).all()


def test_uni(bank_grid, rng):
    x = np.sort(rng.integers(0, 50, 515)).astype(np.int32)
    out, _ = prim.uni.pim(bank_grid, x)
    assert (out == prim.uni.ref(x)).all()


def test_bs(bank_grid, rng):
    arr = np.sort(rng.integers(0, 10000, 1000)).astype(np.int32)
    qs = rng.integers(0, 10000, 101).astype(np.int32)
    out, _ = prim.bs.pim(bank_grid, arr, qs)
    assert (out == prim.bs.ref(arr, qs)).all()


def test_ts(bank_grid, rng):
    series = rng.normal(size=507).astype(np.float32)
    query = rng.normal(size=16).astype(np.float32)
    (dmin, darg), _ = prim.ts.pim(bank_grid, series, query)
    rmin, rarg = prim.ts.ref(series, query)
    assert abs(dmin - rmin) < 1e-3 and darg == rarg


def test_bfs(bank_grid):
    adj = prim.bfs.random_graph(101, 3, seed=2)
    out, _ = prim.bfs.pim(bank_grid, adj, 0)
    assert (out == prim.bfs.ref(adj, 0)).all()


def test_mlp(bank_grid, rng):
    ws = [rng.normal(size=(33, 24)).astype(np.float32),
          rng.normal(size=(17, 33)).astype(np.float32)]
    x = rng.normal(size=24).astype(np.float32)
    out, _ = prim.mlp.pim(bank_grid, ws, x)
    np.testing.assert_allclose(out, prim.mlp.ref(ws, x), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("m,n,block", [(50, 70, 16), (33, 65, 32)])
def test_nw(bank_grid, rng, m, n, block):
    s1 = rng.integers(0, 4, m).astype(np.int32)
    s2 = rng.integers(0, 4, n).astype(np.int32)
    out, _ = prim.nw.pim(bank_grid, s1, s2, block=block)
    assert (out == prim.nw.ref(s1, s2)).all()


@pytest.mark.parametrize("variant", ["short", "long"])
def test_hist(bank_grid, rng, variant):
    px = rng.integers(0, 256, 5003).astype(np.int32)
    f = prim.hist.pim_short if variant == "short" else prim.hist.pim_long
    out, _ = f(bank_grid, px)
    assert (out == prim.hist.ref(px, 256)).all()


# Chunked HST (the pipelined runtime's phases) against ref: values below 0
# and at or above nbins, a length that divides by neither chunks nor banks,
# so both the zero chunk-tail padding and (at 4 banks) the -1 bank padding
# land in bin 0.  Four banks need four devices, fixed at jax init: one
# subprocess computes every 4-bank case for the module.
HIST_N, HIST_CHUNKS = 5003, 4
HIST_BINS = [256, 100, 257]

HIST_SCRIPT = r"""
import sys; sys.path.insert(0, {src!r})
import json
from repro.core import make_bank_grid
sys.path.insert(0, {tests!r})
from test_prim import HIST_BINS, hist_chunked_case
g = make_bank_grid()
assert g.n_banks == 4, g.n_banks
print(json.dumps({{nb: hist_chunked_case(g, nb) for nb in HIST_BINS}}))
"""


def hist_chunked_case(grid, nbins):
    """Drive ``hist.chunked`` split -> scatter -> compute -> retrieve ->
    merge on a seeded image; returns (result, ref) as lists."""
    h = prim.hist.chunked
    px = np.random.default_rng(nbins).integers(
        -20, nbins + 20, HIST_N).astype(np.int32)
    meta, chunks = h.split(grid, HIST_CHUNKS, px, nbins)
    parts = [h.retrieve(grid, meta,
                        h.compute(grid, meta, h.scatter(grid, meta, c)))
             for c in chunks]
    out = h.merge(grid, meta, parts)
    return out.tolist(), prim.hist.ref(px, nbins).tolist()


@pytest.fixture(scope="module")
def hist_on_4_banks():
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    script = HIST_SCRIPT.format(src=os.path.join(here, "..", "src"),
                                tests=here)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return {int(k): v for k, v in json.loads(out.stdout.splitlines()[-1])
            .items()}


@pytest.mark.parametrize("banks", [1, 4])
@pytest.mark.parametrize("nbins", HIST_BINS)
def test_hist_chunked_exact(request, bank_grid, banks, nbins):
    if banks == 1:
        out, ref = hist_chunked_case(bank_grid, nbins)
    else:
        out, ref = request.getfixturevalue("hist_on_4_banks")[nbins]
    assert len(out) == nbins
    assert out == ref


def test_hist_chunked_phase_has_no_scatter(bank_grid):
    """The chunked compute phase counts with a dense contraction; a return
    to the serialized scatter-add would put a ``stablehlo.scatter`` back."""
    dp = bank_grid.to_banks(np.zeros((bank_grid.n_banks, 1024), np.int32))
    text = prim.hist._local(bank_grid, 256).lower(dp).as_text()
    assert "stablehlo.dot_general" in text
    assert "stablehlo.scatter" not in text


# GEMV-B / GEMV-G with a stack of vectors (the decode engine's one request
# per weight matrix): equal, to float32 rounding (a matrix-matrix product
# sums in another order than a matvec), to one request per column, GEMV-B's
# bias a column for the stack and a vector for the columns.  A row count
# that divides by no chunking, pinned and sent operands, 1 to 3 chunks; the
# 4-bank cases run in one subprocess, as for chunked HST.
STACK_M, STACK_D, STACK_N = 37, 24, 5
STACK_CASES = [(wl, k, op) for wl in ("GEMV-B", "GEMV-G")
               for k in (1, 2, 3) for op in ("resident", "sent")]

STACK_SCRIPT = r"""
import sys; sys.path.insert(0, {src!r})
import json
sys.path.insert(0, {tests!r})
from test_prim import STACK_CASES, stacked_gemv_case
print(json.dumps([stacked_gemv_case(4, *c) for c in STACK_CASES]))
"""


def stacked_gemv_case(banks, workload, n_chunks, operand):
    """One stacked request of ``workload`` against the column-by-column
    requests on a session of ``banks`` banks: (shape, largest difference,
    whether they agree, whether every request hit the pinned entry)."""
    from repro import pim
    rng = np.random.default_rng(n_chunks)
    mats = ("w", "b") if workload == "GEMV-B" else ("wg", "wu")
    w = {k: rng.normal(size=(STACK_M, STACK_D) if k != "b" else STACK_M)
         .astype(np.float32) for k in mats}
    stack = {k: v.reshape(-1, 1) if k == "b" else v for k, v in w.items()}
    x = rng.normal(size=(STACK_D, STACK_N)).astype(np.float32)
    resident = operand == "resident"
    with pim.session(banks=banks, n_chunks=n_chunks,
                     resident=resident) as s:
        ops = [pim.ResidentHandle(o) if resident else o for o in (stack, w)]
        if resident:
            for op in ops:
                s.pin(workload, op, np.zeros(STACK_D, np.float32))
        before = s.stats()["cache"]["hits"] if resident else 0
        y = s.run(workload, ops[0], x)
        cols = np.stack([s.run(workload, ops[1], x[:, j])
                         for j in range(STACK_N)], 1)
        hits = (s.stats()["cache"]["hits"] - before == STACK_N + 1
                if resident else True)
    return (list(y.shape), float(np.abs(y - cols).max()),
            bool(np.allclose(y, cols, rtol=1e-5, atol=1e-5)), hits)


@pytest.fixture(scope="module")
def stacked_on_4_banks():
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    script = STACK_SCRIPT.format(src=os.path.join(here, "..", "src"),
                                 tests=here)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return dict(zip(STACK_CASES, json.loads(out.stdout.splitlines()[-1])))


@pytest.mark.parametrize("banks", [1, 4])
@pytest.mark.parametrize("case", STACK_CASES,
                         ids=lambda c: "-".join(map(str, c)))
def test_stacked_gemv_equals_one_request_per_column(request, banks, case):
    if banks == 1:
        shape, err, close, hits = stacked_gemv_case(1, *case)
    else:
        shape, err, close, hits = request.getfixturevalue(
            "stacked_on_4_banks")[case]
    assert shape == [STACK_M, STACK_N]
    assert close, err
    assert hits


def test_stacked_gemv_runs_in_the_matvec_phases(bank_grid, monkeypatch):
    """A stack of vectors takes the same jitted phases as one vector
    (``_local_b``/``_local_g``), so the device trace still names their
    modules ``jit__bias_mv`` and ``jit__gated_mv``."""
    from repro import pim
    from repro.prim import gemv_fused
    seen = []
    real = {n: getattr(gemv_fused, n) for n in ("_local_b", "_local_g")}
    for name, make in real.items():
        def spy(grid, make=make, name=name):
            fn = make(grid)

            def call(*args):
                seen.append((name, args[-1].ndim))
                return fn(*args)
            return call
        monkeypatch.setattr(gemv_fused, name, spy)
    rng = np.random.default_rng(0)
    a = rng.normal(size=(9, 6)).astype(np.float32)
    x = rng.normal(size=(6, 3)).astype(np.float32)
    with pim.session(banks=1, n_chunks=2) as s:
        s.run("GEMV-B", {"w": a, "b": a[:, :1]}, x)
        s.run("GEMV-G", {"wg": a, "wu": a}, x)
    assert set(seen) == {("_local_b", 2), ("_local_g", 2)}
    w = bank_grid.to_banks(a[None])
    b = bank_grid.to_banks(a[None, :, :1])
    dx = bank_grid.broadcast(x)
    assert real["_local_b"](bank_grid).lower(w, b, dx).as_text() \
        .startswith("module @jit__bias_mv ")
    assert real["_local_g"](bank_grid).lower(w, w, dx).as_text() \
        .startswith("module @jit__gated_mv ")


@pytest.mark.parametrize("via", ["host", "fabric"])
def test_red(bank_grid, rng, via):
    x = rng.integers(0, 100, 5001).astype(np.int32)
    out, _ = prim.red.pim(bank_grid, x, via=via)
    assert out == prim.red.ref(x)


@pytest.mark.parametrize("variant", ["ssa", "rss"])
@pytest.mark.parametrize("via", ["host", "fabric"])
def test_scan(bank_grid, rng, variant, via):
    x = rng.integers(0, 10, 3001).astype(np.int32)
    f = prim.scan.pim_ssa if variant == "ssa" else prim.scan.pim_rss
    out, _ = f(bank_grid, x, via=via)
    assert (out == prim.scan.ref(x)).all()


def test_trns(bank_grid, rng):
    # N = 128, n = 8 -> N' = 16: divides any simulated bank count up to 16
    x = rng.normal(size=(64, 128)).astype(np.float32)
    out, _ = prim.trns.pim(bank_grid, x, m=8, n=8)
    assert (out == prim.trns.ref(x)).all()


@pytest.mark.parametrize("variant", ["single", "tree-barrier",
                                     "tree-handshake"])
def test_red_variants(bank_grid, rng, variant):
    """Paper appendix 9.2.3: all three RED merge variants agree."""
    x = rng.integers(0, 100, 4099).astype(np.int32)
    out, _ = prim.red.pim(bank_grid, x, variant=variant)
    assert out == prim.red.ref(x)
