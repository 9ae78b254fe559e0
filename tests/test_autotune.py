"""Characterization-driven autotuner (runtime/autotune.py, DESIGN.md §8):
calibration fits are finite/positive, the plan solver behaves at the model
level, plans integrate with the scheduler/telemetry, and — at 8 simulated
banks — the probed tuned chunk count beats or ties the fixed default on VA
and GEMV."""
import math
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.runtime.autotune import (CHUNK_CANDIDATES, DEFAULT_N_CHUNKS,
                                    StageFit, TunedPlan, TuningResult,
                                    WorkloadProfile, autotune, calibrate,
                                    plan_for, probe_candidates)


def _assert_fit_sane(fit: StageFit):
    assert math.isfinite(fit.alpha_s) and fit.alpha_s >= 0
    assert math.isfinite(fit.bytes_per_s) and fit.bytes_per_s > 0


# -- stage fits ---------------------------------------------------------------

def test_stagefit_from_points_recovers_affine():
    fit = StageFit.from_points([100, 200, 400], [1.1, 2.1, 4.1])
    assert fit.alpha_s == pytest.approx(0.1, abs=1e-9)
    assert fit.bytes_per_s == pytest.approx(100.0, rel=1e-9)
    assert fit.time(1000) == pytest.approx(10.1, rel=1e-9)


def test_stagefit_degenerate_slope_guard():
    # flat sweep (all fixed cost): bandwidth must clamp positive, not blow up
    flat = StageFit.from_points([100, 200, 400], [0.5, 0.5, 0.5])
    _assert_fit_sane(flat)
    assert flat.time(1 << 30) == pytest.approx(0.5, rel=1e-6)
    # negative slope (noise): same guard
    noisy = StageFit.from_points([100, 400], [0.5, 0.4])
    _assert_fit_sane(noisy)


def test_calibrate_fits_finite_positive(bank_grid):
    stages = calibrate(bank_grid, nbytes=(1 << 14, 1 << 16, 1 << 18), reps=2)
    assert set(stages) == {"push", "compute", "pull"}
    for fit in stages.values():
        _assert_fit_sane(fit)


# -- plan solver (model level) ------------------------------------------------

def _profile(alpha, bw, bytes_in=1 << 20, bytes_out=1 << 20, serialized=0.0):
    fit = StageFit(alpha, bw)
    return WorkloadProfile("X", bytes_in, bytes_out, push=fit, compute=fit,
                           pull=fit, serialized_s=serialized)


def test_plan_zero_alpha_prefers_many_chunks():
    # free dispatch: every extra chunk hides more transfer, max C wins
    plan = plan_for(_profile(alpha=0.0, bw=1e6))
    assert plan.n_chunks == max(CHUNK_CANDIDATES)


def test_plan_huge_alpha_prefers_one_chunk():
    # dispatch dominates: chunking only adds fixed cost, C=1 wins
    plan = plan_for(_profile(alpha=1.0, bw=1e12))
    assert plan.n_chunks == 1


def test_plan_fields_positive_and_overlap_vs_t1():
    plan = plan_for(_profile(alpha=1e-4, bw=1e8))
    assert plan.n_chunks in set(CHUNK_CANDIDATES) | {1}
    assert 1 <= plan.max_batch_requests <= 16
    assert plan.predicted_pipelined_s > 0
    assert plan.predicted_serialized_s > 0
    # without a measured baseline the reference is the model's own T(1),
    # and the argmin includes 1 — so the predicted overlap is >= 1
    assert plan.predicted_overlap >= 1.0
    assert plan.candidate_s[plan.n_chunks] == min(plan.candidate_s.values())


def test_plan_uses_measured_serialized_baseline():
    plan = plan_for(_profile(alpha=1e-4, bw=1e8, serialized=123.0))
    assert plan.predicted_serialized_s == 123.0
    assert plan.predicted_overlap == pytest.approx(
        123.0 / plan.predicted_pipelined_s)


def test_probe_candidates_always_include_default_and_pick():
    plan = plan_for(_profile(alpha=1e-4, bw=1e8))
    cand = probe_candidates(plan)
    assert DEFAULT_N_CHUNKS in cand
    assert plan.n_chunks in cand


# -- end to end on the live backend ------------------------------------------

def test_autotune_va_gemv(bank_grid):
    from repro.prim.registry import REGISTRY
    res = autotune(bank_grid, [REGISTRY["VA"], REGISTRY["GEMV"]], scale=1,
                   reps=2, calib_nbytes=(1 << 14, 1 << 16, 1 << 18))
    assert set(res.plans) == {"VA", "GEMV"}
    for name, plan in res.plans.items():
        prof = res.profiles[name]
        for stage in (prof.push, prof.compute, prof.pull):
            _assert_fit_sane(stage)
        assert prof.bytes_in > 0 and prof.serialized_s > 0
        assert plan.n_chunks >= 1 and plan.max_batch_requests >= 1
        assert math.isfinite(plan.predicted_overlap)
        assert plan.predicted_overlap > 0


def test_tuning_result_json_round_trip(bank_grid):
    import json

    from repro.prim.registry import REGISTRY
    res = autotune(bank_grid, [REGISTRY["VA"]], scale=1, reps=2,
                   calib_nbytes=(1 << 14, 1 << 16))
    d = res.as_dict()
    restored = TuningResult.from_dict(json.loads(json.dumps(d)))
    assert restored.as_dict() == d
    assert restored.plans["VA"].n_chunks == res.plans["VA"].n_chunks


def test_scheduler_serves_under_tuned_plan(bank_grid, rng):
    from repro import pim
    e = pim.registry()["VA"]
    args = e.make_args(rng, 1)
    plan = TunedPlan(workload="VA", n_chunks=2, max_batch_requests=3,
                     predicted_serialized_s=1.0, predicted_pipelined_s=0.5,
                     predicted_overlap=2.0)
    sched = pim.PimSession(grid=bank_grid, plans={"VA": plan}).scheduler
    reqs = [sched.submit("VA", *args) for _ in range(4)]
    sched.drain()
    for r in reqs:
        np.testing.assert_array_equal(r.result(), e.ref(*args))
        assert r.record.tuned
        assert r.record.n_chunks == 2              # plan overrode the default
        assert r.record.predicted_overlap == 2.0
    # plan's batch limit (3) splits the 4 requests into two batches
    assert len({r.record.batch_id for r in reqs}) == 2
    agg = sched.telemetry.aggregate()
    assert agg["tuned_requests"] == 4


def test_run_pipelined_stamps_plan_on_record(bank_grid, rng):
    from repro.prim.registry import REGISTRY
    from repro.runtime import RequestRecord, run_pipelined
    e = REGISTRY["VA"]
    args = e.make_args(rng, 1)
    plan = TunedPlan(workload="VA", n_chunks=3, max_batch_requests=8,
                     predicted_serialized_s=1.0, predicted_pipelined_s=0.5,
                     predicted_overlap=2.0)
    rec = RequestRecord(request_id=0, workload="VA")
    res = run_pipelined(bank_grid, e.chunked, *args, plan=plan, record=rec)
    np.testing.assert_array_equal(res.value, e.ref(*args))
    assert res.n_chunks == 3            # plan overrode the default
    assert rec.tuned and rec.predicted_overlap == 2.0


# -- 8 simulated banks: tuned beats or ties the fixed default -----------------

SCRIPT = r"""
import sys; sys.path.insert(0, {src!r})
import numpy as np
from repro.core import make_bank_grid
from repro.prim.registry import REGISTRY
from repro.runtime import autotune
from repro.runtime.autotune import DEFAULT_N_CHUNKS, probe_plan

g = make_bank_grid()
assert g.n_banks == 8, g.n_banks
rng = np.random.default_rng(0)
entries = [REGISTRY["VA"], REGISTRY["GEMV"]]
res = autotune(g, entries, scale=1, reps=2)
for e in entries:
    plan = res.plans[e.name]
    args = e.make_args(rng, 1)
    probed = probe_plan(g, e, plan, [args, args])
    default_s = probed.measured_s[DEFAULT_N_CHUNKS]
    tuned_s = probed.measured_s[probed.n_chunks]
    assert tuned_s <= default_s, (e.name, probed.measured_s)
    print("TUNE-OK", e.name, probed.n_chunks,
          round(default_s / tuned_s, 2), flush=True)
print("TUNE-DONE")
"""


@pytest.fixture(scope="session")
def eight_bank_tune():
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    out = subprocess.run([sys.executable, "-c", SCRIPT.format(src=src)],
                         env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


@pytest.mark.slow
@pytest.mark.parametrize("name", ["VA", "GEMV"])
def test_tuned_beats_or_ties_default_8_banks(eight_bank_tune, name):
    assert f"TUNE-OK {name}" in eight_bank_tune
