#!/usr/bin/env python
"""Unified benchmark harness — one CLI, one schema-versioned JSON artifact.

Wraps the four benchmark drivers behind a single entry point and emits a
machine-readable ``BENCH_*.json`` (EXPERIMENTS.md §Bench-artifacts).  All
grid/scheduler/plan assembly goes through the ``repro.pim`` session façade
(DESIGN.md §9):

* ``benchmarks/throughput.py`` — serialized ``pim()`` vs fixed-chunk vs
  autotuned pipeline for the full registry (the tuned plans come from
  ``PimSession.autotune``, DESIGN.md §8; the fitted model parameters are
  embedded in the artifact);
* ``benchmarks/prim_scaling.py`` — strong-scaling phase breakdown over the
  bank axis;
* ``benchmarks/scaling.py`` — rank-level strong/weak scaling
  (``pim.session(ranks=r)``, DESIGN.md §10); the weak rows carry the
  monotone weak-scaling invariant ``check_bench.py`` gates on
  (EXPERIMENTS.md §Scaling);
* ``benchmarks/microbench.py`` — the characterization slice (model vs
  measured backend limits);
* ``benchmarks/roofline.py`` — the LM roofline table from the dry-run
  records (embedded when ``experiments/dryrun/`` has records, and exposed
  as the ``roofline`` subcommand: ``tools/bench.py roofline [--cell ...]``).

The artifact is what CI uploads and gates on: ``tools/check_bench.py``
validates its schema and compares it against the committed baseline.
``--smoke`` keeps everything CI-sized (small scale, few requests, the
characterization slice only).

The artifact also embeds an ``observability`` object (DESIGN.md §11): the
measured tracing overhead (traced vs untraced best-of-reps — gated < 5% by
``check_bench.py``), span counts/tracks from the traced leg, and the
p50/p90/p99 latency stats the upgraded ``session.stats()`` reports.

A ``residency`` object (DESIGN.md §12) measures the resident-operand cache:
cold (cache cleared per rep) vs warm (operand resident) best-of-reps run
time on the first resident workload, the cache hit ratio, and the scatter
seconds the warm hits elided — ``check_bench.py`` gates warm <= cold and
warm-hit scatter-seconds ~ 0.

A ``serving`` object (DESIGN.md §13, ``benchmarks/loadgen.py``) measures
the multi-tenant tier: a saturating two-tenant 2:1 fairness leg (measured
goodput ratio vs the weight ratio, gated via ``fairness_gated``) and an
overloaded open-loop shed leg (exact outcome accounting, sane shed rate).

A ``cost_model`` object (DESIGN.md §15) embeds the instruction-level cost
model: the fitted per-(op, dtype) issue+execute constants and push/pull
transfer constants, one predicted-vs-measured stage-seconds row per tuned
workload (cold path, best-of-reps), the geomean accuracy ratio gated by
``check_bench.py`` (``COST_MODEL_GATE``), and the per-workload analytical
roofline rows — every artifact doubles as a model validation set, rendered
by ``tools/whatif.py table``.

A ``decode`` object (DESIGN.md §14) measures the LLM decode serving tier:
cold (every step re-scatters every weight) vs warm (weights pinned once at
setup) tokens/sec on a tiny float32 decoder, both legs token-checked
against the pure-JAX ``greedy_generate`` — ``check_bench.py`` gates warm
weight-scatter bytes ~ 0 and warm tokens/sec >= cold.

    PYTHONPATH=src python tools/bench.py --smoke --banks 8 --out BENCH_PR10.json
    PYTHONPATH=src python tools/bench.py roofline            # 4th subcommand
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import platform
import subprocess
import sys

_HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(_HERE.parent / "src"))
sys.path.insert(0, str(_HERE.parent))
sys.path.insert(0, str(_HERE))

from check_bench import SCHEMA, validate  # noqa: E402

from repro.runtime.autotune import DEFAULT_N_CHUNKS  # noqa: E402
from repro.launch.cli import cpu_rehearsal_env, enable_compile_cache  # noqa: E402


def env_info() -> dict:
    import jax
    import numpy as np
    devs = jax.devices()
    return {
        "python": platform.python_version(),
        "jax": jax.__version__,
        "numpy": np.__version__,
        "platform": platform.platform(),
        "n_devices": len(devs),
        "device_kind": devs[0].device_kind if devs else "none",
        "xla_flags": os.environ.get("XLA_FLAGS", ""),
    }


def _workload_doc(row: dict, entry) -> dict:
    d = {
        "pipelineable": row["pipelineable"] == "yes",
        "section": entry.section,
        "serialized_s": row["serialized_s"],
        "serialized_rps": row["serialized_rps"],
    }
    if not d["pipelineable"]:
        d["reason"] = entry.reason
        return d
    d["fixed"] = {
        "n_chunks": row["chunks"],
        "pipelined_s": row["pipelined_s"],
        "overlap_speedup": row["overlap_speedup"],
    }
    d["tuned"] = {
        "n_chunks": row["tuned_chunks"],
        "max_batch_requests": row["tuned_batch"],
        "pipelined_s": row["tuned_s"],
        "overlap_speedup": row["tuned_speedup"],
        "predicted_overlap": row["predicted_overlap"],
        "adopted": row["adopted"],
    }
    return d


def _scaling_section(session, names, smoke: bool) -> dict:
    """The artifact's ``scaling`` object: the bank-axis phase breakdown
    (``prim_scaling``) plus the rank-level strong/weak tables
    (``benchmarks/scaling.py``, DESIGN.md §10).  Rank rows need >= 2
    devices; the weak rows are restricted to the workloads whose weak
    scaling a host simulation can sustain (``WEAK_GATE_WORKLOADS``) so
    ``check_bench.py``'s monotone invariant gates the runtime, not the
    runner's core count."""
    from benchmarks import prim_scaling as ps
    from benchmarks import scaling as rs
    from check_bench import _check_weak_scaling

    banks = ps.strong_scaling(
        bank_counts=sorted({1, session.n_banks}),
        scale=1 if smoke else 4,
        workloads=("VA", "GEMV") if smoke else None)
    from repro import pim as _pim

    rank_strong: list = []
    rank_weak: list = []
    registry = _pim.registry()
    pipelineable = [n for n in names if registry[n].pipelineable]
    reps = 2 if smoke else 3
    if session.n_banks >= 2:
        rank_counts = (1, 2)
        bpr = session.n_banks // 2
        if pipelineable:
            strong_wl = ([n for n in ("VA", "RED") if n in pipelineable]
                         or pipelineable[:1]) if smoke else pipelineable
            rank_strong = rs.strong_scaling(
                rank_counts, banks_per_rank=bpr, scale=2 if smoke else 4,
                workloads=strong_wl, reps=reps)
        # the weak gate set is a machine property, independent of the
        # workload subset requested for the throughput tables (gating a
        # compute-bound substitute would violate the invariant by design)
        # — always emitted on >= 2 banks, matching validate()'s requirement
        weak_wl = list(rs.WEAK_GATE_WORKLOADS)
        rank_weak = rs.weak_scaling(
            rank_counts, banks_per_rank=bpr, base_scale=8,
            workloads=weak_wl, reps=reps)
        noisy: list = []
        _check_weak_scaling(rank_weak, "rank_weak", noisy)
        if noisy:
            # timing on shared CI hosts is noisy; one re-measure before the
            # artifact (and its monotone invariant) is finalized
            rank_weak = rs.weak_scaling(
                rank_counts, banks_per_rank=bpr, base_scale=8,
                workloads=weak_wl, reps=reps + 1)
    # whether THIS host sustained the monotone invariant is itself a
    # measured machine property: an oversubscribed simulated host (more
    # banks than physical cores) may not, and the validator only enforces
    # the invariant on artifacts that claim it (weak_gated).  compare()
    # still flags losing the property on the same environment.
    failed: list = []
    _check_weak_scaling(rank_weak, "rank_weak", failed)
    return {"banks": banks, "rank_strong": rank_strong,
            "rank_weak": rank_weak, "weak_gated": not failed}


def _observability_section(grid, names, smoke: bool) -> dict:
    """The artifact's ``observability`` object (DESIGN.md §11): tracing
    overhead measured as best-of-reps traced vs untraced ``map()`` time on
    one pipelineable workload (alternating legs so clock drift hits both
    sides), plus span counts/tracks from the traced legs and the
    percentile / per-stage / counter stats the session reported."""
    import time

    import numpy as np

    from repro import pim
    from repro.runtime.trace import NULL_TRACER, Tracer, set_tracer

    registry = pim.registry()
    wl = next((n for n in names if registry[n].pipelineable), None)
    if wl is None:
        return {"workload": None}     # nothing to measure; validator skips
    entry = registry[wl]
    rng = np.random.default_rng(0)
    n_req = 3 if smoke else 6
    args_list = [entry.make_args(rng, 1 if smoke else 2)
                 for _ in range(n_req)]

    # trace=False: the session must not install its own tracer (REPRO_TRACE
    # may be set in CI) — the legs below switch the active tracer explicitly
    sess = pim.PimSession(grid=grid, trace=False)
    sess.map(wl, args_list)              # warm this chunk shape's compile
    sess.telemetry.reset()
    tracer = Tracer()
    # enough alternating legs for both mins to converge on a noisy shared
    # host — at 5 reps the measured overhead swung from +1% to +11%
    reps, untraced, traced = 11, float("inf"), float("inf")
    prev = set_tracer(NULL_TRACER)
    try:
        for _ in range(reps):
            set_tracer(NULL_TRACER)
            t0 = time.perf_counter()
            sess.map(wl, args_list)
            untraced = min(untraced, time.perf_counter() - t0)
            set_tracer(tracer)
            t0 = time.perf_counter()
            sess.map(wl, args_list)
            traced = min(traced, time.perf_counter() - t0)
    finally:
        set_tracer(prev)
    agg = sess.stats()
    sess.close()
    # the relative overhead is the headline, but on a smoke run the map legs
    # are single-digit ms while host noise is ±ms-scale — the ratio cannot
    # resolve a few-hundred-µs true delta.  The gate's stable fallback is
    # the *directly measured* per-span emission cost: a tight loop over a
    # representative tagged emit, immune to scheduler noise and exactly the
    # thing the "near-free when on" promise is about
    probe = Tracer()
    n_probe = 10000
    t0 = time.perf_counter()
    for i in range(n_probe):
        probe.emit("launch", "dpu", 0.0, 1.0, workload=wl, req=0, chunk=i)
    emit_us = (time.perf_counter() - t0) / n_probe * 1e6
    return {
        "workload": wl,
        "requests": n_req,
        "reps": reps,
        "untraced_s": untraced,
        "traced_s": traced,
        "overhead_frac": traced / untraced - 1.0,
        "emit_us_per_span": emit_us,
        "spans": len(tracer.spans),
        "dropped_spans": tracer.dropped,
        "tracks": sorted({s.track for s in tracer.spans}),
        "stats": {"percentiles": agg.get("percentiles", {}),
                  "stage_seconds": agg.get("stage_seconds", {}),
                  "counters": agg.get("counters", {})},
    }


def _residency_section(grid, names, smoke: bool) -> dict:
    """The artifact's ``residency`` object (DESIGN.md §12): cold vs warm
    ``run()`` time on the first resident workload (GEMV preferred — the
    paper's canonical reuse case), the cache hit ratio, and the scatter
    seconds per request on the best cold vs best warm rep.  Cold reps clear
    the cache first (every rep re-scatters); warm reps run against a filled
    cache (the fill is one extra run, not timed).  Both legs' outputs are
    checked against ``ref`` so the timing can never come from a wrong
    answer."""
    import time

    import numpy as np

    from repro import pim

    registry = pim.registry()
    resident = [n for n in names if registry[n].resident]
    wl = "GEMV" if "GEMV" in resident else (resident[0] if resident else None)
    if wl is None:
        return {"workload": None}     # nothing resident; validator skips
    entry = registry[wl]
    rng = np.random.default_rng(7)
    args = entry.make_args(rng, 2 if smoke else 4)
    ref_out = entry.ref(*args)

    sess = pim.PimSession(grid=grid, trace=False)
    reps = 3 if smoke else 5
    sess.run(wl, *args)                  # compile warmup

    def one_run():
        sess.telemetry.reset()
        t0 = time.perf_counter()
        out = sess.run(wl, *args)
        dt = time.perf_counter() - t0
        return out, dt, sess.telemetry.snapshot_records()[-1]

    cold_s, cold_scatter = float("inf"), 0.0
    for _ in range(reps):
        sess.cache.clear()
        out, dt, rec = one_run()
        if dt < cold_s:
            cold_s, cold_scatter = dt, rec.phases.cpu_dpu
    entry.compare(out, ref_out)

    sess.cache.clear()
    sess.run(wl, *args)                  # fill: the miss the warm reps hit on
    warm_s, warm_scatter, warm_hits = float("inf"), 0.0, 0
    for _ in range(reps):
        out, dt, rec = one_run()
        if dt < warm_s:
            warm_s, warm_scatter = dt, rec.phases.cpu_dpu
        warm_hits += rec.cache_hit
    entry.compare(out, ref_out)
    cs = sess.cache.stats()
    sess.close()
    return {
        "workload": wl,
        "reps": reps,
        "cold_s": cold_s,
        "warm_s": warm_s,
        "warm_speedup": cold_s / warm_s if warm_s else 0.0,
        "warm_hit_reps": warm_hits,
        "cold_scatter_s": cold_scatter,
        "warm_scatter_s": warm_scatter,
        "hits": cs["hits"],
        "misses": cs["misses"],
        "hit_ratio": cs["hits"] / max(1, cs["hits"] + cs["misses"]),
        "evictions": cs["evictions"],
        "resident_bytes": cs["resident_bytes"],
    }


def _cost_model_section(grid, tuning, cm, names, smoke: bool) -> dict:
    """The artifact's ``cost_model`` object (DESIGN.md §15): the fitted
    constants plus one predicted-vs-measured row per tuned workload.  Each
    row runs the workload through a ``resident=False`` session (the model
    prices the cold path — every chunk scatters) at the plan's chunk count,
    best-of-reps, and compares the telemetry stage buckets against the
    model's per-stage predictions.  The headline is the geomean of the
    per-workload accuracy ratios max(pred/meas, meas/pred) on total stage
    seconds — scale-free, >= 1, and gated generously by ``check_bench.py``
    (``COST_MODEL_GATE``) in the same non-flaky spirit as the µs/span
    probe.  The per-workload analytical roofline rows ride along."""
    import time

    import numpy as np

    from check_bench import COST_MODEL_GATE
    from repro import pim
    from repro.core.costmodel import geomean_ratio, roofline_rows

    registry = pim.registry()
    rng = np.random.default_rng(11)
    todo = [n for n in names if n in tuning.plans]
    out = {"gate": COST_MODEL_GATE, "constants": cm.as_dict(),
           "rows": [], "geomean_ratio": 1.0, "roofline": []}
    if not todo:
        return out                       # nothing tuned; validator skips
    # resident=False: no operand cache, so the cold path the model prices
    # (every chunk scatters, plan.n_chunks effective) is what runs
    sess = pim.PimSession(grid=grid, trace=False, resident=False)
    sess.plans.update(tuning.plans)
    reps = 2 if smoke else 3
    rows, profiles = [], []
    for name in todo:
        entry = registry[name]
        args = entry.make_args(rng, 1 if smoke else 2)
        prof = entry.cost_profile(grid, args)
        profiles.append(prof)
        plan = tuning.plans[name]
        pred = cm.predict_plan(prof, plan)
        sess.run(name, *args)            # compile warmup at this chunk shape
        best_s, best_rec = float("inf"), None
        for _ in range(reps):
            sess.telemetry.reset()
            t0 = time.perf_counter()
            sess.run(name, *args)
            dt = time.perf_counter() - t0
            rec = sess.telemetry.snapshot_records()[-1]
            if dt < best_s:
                best_s, best_rec = dt, rec
        meas_total = (best_rec.phases.cpu_dpu + best_rec.phases.dpu
                      + best_rec.phases.dpu_cpu)
        pred_total = sum(pred.stage_s.values())
        ratio = max(pred_total / max(meas_total, 1e-9),
                    meas_total / max(pred_total, 1e-9))
        rows.append({
            "workload": name,
            "n_chunks": plan.n_chunks,
            "predicted": {"cpu_dpu_s": pred.stage_s["cpu_dpu"],
                          "dpu_s": pred.stage_s["dpu"],
                          "dpu_cpu_s": pred.stage_s["dpu_cpu"],
                          "total_s": pred_total,
                          "makespan_s": pred.makespan_s,
                          "energy_j": pred.energy_j},
            "measured": {"cpu_dpu_s": best_rec.phases.cpu_dpu,
                         "dpu_s": best_rec.phases.dpu,
                         "dpu_cpu_s": best_rec.phases.dpu_cpu,
                         "total_s": meas_total,
                         "service_s": best_rec.service_s},
            "accuracy_ratio": ratio,
            "profile": prof.as_dict(),
        })
    sess.close()
    out["rows"] = rows
    out["geomean_ratio"] = geomean_ratio(r["accuracy_ratio"] for r in rows)
    out["roofline"] = roofline_rows(cm, profiles)
    return out


def _serving_section(grid, smoke: bool) -> dict:
    """The artifact's ``serving`` object (DESIGN.md §13): delegated to the
    load harness — a saturating two-tenant fairness leg plus an overloaded
    shed leg on fresh sessions over the shared grid."""
    from benchmarks.loadgen import serving_section
    return serving_section(grid, smoke=smoke)


def _decode_section(grid, smoke: bool) -> dict:
    """The artifact's ``decode`` object (DESIGN.md §14): LLM decode
    tokens/sec end to end on a tiny float32 decoder, cold vs warm.  The
    cold leg opens a ``resident=False`` session — every step re-scatters
    every weight; the warm leg pins all projections once and each step
    moves only activations.  Each leg is a fresh traced session over the
    shared grid, best-of-reps on tokens/sec, with the weight bytes that
    crossed the boundary summed from the leg's ``scatter`` /
    ``scatter_cached`` spans.  Both legs' tokens are checked against the
    pure-JAX ``greedy_generate`` so the timing can never come from a wrong
    answer — ``check_bench.py`` gates warm scatter ~ 0 and warm tokens/sec
    >= cold."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import pim
    from repro.configs import get_config
    from repro.launch import serve as serve_mod
    from repro.models import transformer
    from repro.runtime.elastic import carve_mesh

    layers, streams, prompt_len = (2, 2, 4) if smoke else (4, 4, 8)
    max_new = 6 if smoke else 16
    reps = 2 if smoke else 3
    cfg = dataclasses.replace(
        get_config("tinyllama-1.1b", smoke=True), n_layers=layers,
        d_model=128, n_heads=4, n_kv_heads=2, d_ff=256, vocab=256,
        dtype=jnp.float32, fast_decode=True)
    params, specs = transformer.init(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(1),
                                (streams, prompt_len), 0, cfg.vocab)
    mesh = carve_mesh(jax.devices(), model_parallel=1)
    ref = np.asarray(serve_mod.greedy_generate(params, cfg, mesh, specs,
                                               prompt, max_new=max_new))

    def leg(resident: bool) -> dict:
        sess = pim.PimSession(grid=grid, trace=True, resident=resident)
        best, setup_s = None, 0.0
        try:
            for _ in range(reps):
                eng = pim.DecodeEngine(params, cfg, session=sess)
                out = eng.generate(np.asarray(prompt), max_new)
                assert (out == ref).all(), "PIM decode diverged from ref"
                rep = eng.report()
                if best is None or rep["tokens_per_s"] > best["tokens_per_s"]:
                    best = rep
                setup_s = max(setup_s, eng.setup_s)
                for fp in eng.pins:       # re-pin cleanly on the next rep
                    sess.unpin(fp)
                if sess.cache is not None:
                    sess.cache.clear()
            spans = sess.tracer.spans
        finally:
            sess.close()
        return {
            "tokens_per_s": best["tokens_per_s"],
            "time_per_output_token_s": best["time_per_output_token_s"],
            "generate_s": best["generate_s"],
            "prefill_s": best["prefill_s"],
            "setup_s": setup_s,
            "pim_s": best["pim_s"],
            "host_s": best["host_s"],
            "scatter_bytes": sum(s.args.get("bytes", 0) for s in spans
                                 if s.name == "scatter"),
            "cached_bytes": sum(s.args.get("bytes", 0) for s in spans
                                if s.name == "scatter_cached"),
        }

    cold = leg(resident=False)
    warm = leg(resident=True)
    return {
        "workload": "decode",
        "config": {"layers": layers, "d_model": cfg.d_model,
                   "streams": streams, "prompt_len": prompt_len,
                   "max_new": max_new},
        "reps": reps,
        "parity": True,                  # both legs asserted against ref
        "cold": cold,
        "warm": warm,
        "warm_speedup": (cold["time_per_output_token_s"]
                         / warm["time_per_output_token_s"])
        if warm["time_per_output_token_s"] else 0.0,
    }


def collect(grid=None, workloads=None, *, n_requests: int = 6,
            scale: int = 2, smoke: bool = False,
            pr_tag: str | None = None) -> dict:
    """Run the suites and assemble the artifact document.  Grid, plans, and
    calibration all come from one `repro.pim` session; ``grid=`` wraps a
    caller's existing grid in the session instead of allocating one."""
    from benchmarks import microbench as mb
    from benchmarks import roofline as rl
    from benchmarks.throughput import throughput
    from repro import pim

    session = pim.PimSession(grid=grid)   # grid=None -> allocate one
    registry = pim.registry()
    names = list(workloads or registry)
    entries = [registry[n] for n in names]

    # the instruction-level cost model (DESIGN.md §15) is calibrated once
    # and threaded through autotune so every plan carries model predictions
    # (model_candidate_s prunes the tuned probe sweep; predicted_stage_s is
    # stamped onto every request record)
    from repro.core.costmodel import CostModel
    cm = CostModel.calibrate(session.grid, reps=2 if smoke else 3)
    tuning = session.autotune([e for e in entries if e.pipelineable],
                              scale=scale, reps=2 if smoke else 3,
                              probe=False, cost_model=cm)
    rows = throughput(workloads=names, n_requests=n_requests, scale=scale,
                      n_chunks=DEFAULT_N_CHUNKS, tuning=tuning,
                      grid=session.grid)

    doc = {
        "schema": SCHEMA,
        "env": env_info(),
        "settings": {"pr_tag": pr_tag, "smoke": smoke,
                     "banks": session.n_banks, "ranks": session.n_ranks,
                     "n_requests": n_requests,
                     "scale": scale, "default_n_chunks": DEFAULT_N_CHUNKS},
        "model": tuning.as_dict(),
        "workloads": {row["workload"]: _workload_doc(row, registry[
            row["workload"]]) for row in rows},
        "micro": mb.smoke(session.grid) if smoke else [
            r for fig in mb.ALL for r in
            (fig(fast=True) if fig is mb.fig4_arith_throughput else fig())],
        "scaling": _scaling_section(session, names, smoke),
        "observability": _observability_section(session.grid, names, smoke),
        "residency": _residency_section(session.grid, names, smoke),
        "serving": _serving_section(session.grid, smoke),
        "decode": _decode_section(session.grid, smoke),
        "cost_model": _cost_model_section(session.grid, tuning, cm, names,
                                          smoke),
        # the fourth benchmark: rows ride along when dry-run records exist
        # ([] otherwise — the LM roofline needs repro.launch.dryrun output)
        "roofline": rl.rows(rl.load_records()),
    }
    session.close()
    return doc


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if argv[:1] == ["roofline"]:
        # the fourth subcommand: render the roofline table / re-run a cell
        from benchmarks import roofline as rl
        return rl.main(argv[1:]) or 0

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--banks", type=int, default=0,
                    help="re-exec with N forced host devices")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run: small scale, few requests, "
                         "characterization slice only")
    ap.add_argument("--out", default="BENCH.json",
                    help="artifact path (e.g. BENCH_PR10.json)")
    ap.add_argument("--pr-tag", default=None,
                    help="free-form tag recorded in settings.pr_tag")
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--scale", type=int, default=None)
    ap.add_argument("--workloads", nargs="*", default=None,
                    help="subset of registry names (default: full registry)")
    args = ap.parse_args(argv)

    if args.banks:
        env = cpu_rehearsal_env(args.banks)
        cmd = [sys.executable, str(_HERE / "bench.py"), "--out", args.out]
        if args.smoke:
            cmd.append("--smoke")
        if args.pr_tag:
            cmd += ["--pr-tag", args.pr_tag]
        if args.requests is not None:
            cmd += ["--requests", str(args.requests)]
        if args.scale is not None:
            cmd += ["--scale", str(args.scale)]
        if args.workloads:
            cmd += ["--workloads", *args.workloads]
        return subprocess.call(cmd, env=env)

    n_requests = args.requests if args.requests is not None \
        else (3 if args.smoke else 6)
    scale = args.scale if args.scale is not None else (1 if args.smoke else 2)
    doc = collect(workloads=args.workloads, n_requests=n_requests,
                  scale=scale, smoke=args.smoke, pr_tag=args.pr_tag)

    errors = validate(doc)
    if errors:
        print("bench: refusing to write a schema-invalid artifact:")
        for e in errors:
            print(f"  - {e}")
        return 1
    out = pathlib.Path(args.out)
    out.write_text(json.dumps(doc, indent=1, sort_keys=True) + "\n")
    n_tuned = sum(1 for w in doc["workloads"].values()
                  if w.get("tuned", {}).get("adopted") == "tuned")
    print(f"bench: wrote {out} — {len(doc['workloads'])} workloads, "
          f"{n_tuned} with an adopted tuned plan, schema {SCHEMA}")
    return 0


if __name__ == "__main__":
    enable_compile_cache()
    sys.exit(main())
