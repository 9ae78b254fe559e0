#!/usr/bin/env python
"""Render a runtime trace (``session.trace_export()`` JSON) in the terminal.

The Perfetto UI is the deep-dive tool; this is the glanceable one — what CI
publishes into the job summary and what a quick local look needs:

* **top-N slowest spans** — where single-span time went (a cold compile, a
  serialized fallback, one straggling chunk);
* **per-stage summary** — busy seconds / span count / mean per category
  (cpu_dpu, dpu, dpu_cpu, inter_dpu, ...), per track;
* **critical path & overlap efficiency** — achieved wall span vs the
  bottleneck stage's busy time.  A perfectly overlapped pipeline keeps its
  bottleneck stage busy end-to-end, so ``bottleneck_busy / wall`` is 1.0;
  the gap below 1.0 is pipeline bubble — the quantity the paper's stacked
  bars can only show in aggregate (DESIGN.md §11);
* **cached-scatter savings** — warm chunks served from the resident-operand
  cache emit ``scatter_cached`` spans (DESIGN.md §12) instead of pushing
  bytes; the summary counts them, sums the bytes the elided pushes would
  have moved, and estimates the seconds saved from the mean duration of the
  cold ``scatter`` spans in the same trace.

    PYTHONPATH=src python tools/trace_view.py trace.json [--top 10]
    python tools/trace_view.py trace.json --summary >> "$GITHUB_STEP_SUMMARY"
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

#: categories that represent pipeline work (the overlap-efficiency
#: denominator); queue/sched/session spans describe bookkeeping around it
WORK_CATS = ("cpu_dpu", "dpu", "dpu_cpu", "inter_dpu", "transfer")


def load_events(path) -> list[dict]:
    doc = json.loads(pathlib.Path(path).read_text())
    events = doc.get("traceEvents", doc if isinstance(doc, list) else [])
    if not isinstance(events, list):
        raise ValueError(f"{path}: not a trace_event JSON document")
    return events


def split_events(events):
    """(spans, tid->track-name): complete events + thread-name metadata."""
    tracks = {e["tid"]: e["args"]["name"] for e in events
              if e.get("ph") == "M" and e.get("name") == "thread_name"}
    spans = [e for e in events if e.get("ph") == "X"]
    return spans, tracks


def top_slowest(spans, tracks, n: int = 10) -> list[dict]:
    rows = sorted(spans, key=lambda e: e.get("dur", 0.0), reverse=True)[:n]
    return [{"name": e["name"], "cat": e.get("cat", ""),
             "track": tracks.get(e["tid"], str(e["tid"])),
             "ms": e.get("dur", 0.0) / 1e3,
             "args": e.get("args", {})} for e in rows]


def stage_summary(spans) -> dict:
    """Per-category busy seconds/count/mean + wall span + overlap
    efficiency.  The efficiency denominator is the achieved wall span over
    all work spans; the numerator is the busiest single (stage, track) —
    rank pipelines run concurrently, so summing a stage across tracks
    would overcount (busy > wall)."""
    stages: dict[str, dict] = {}
    per_track: dict[tuple, float] = {}
    t_lo, t_hi = float("inf"), 0.0
    for e in spans:
        cat = e.get("cat", "span")
        s = stages.setdefault(cat, {"seconds": 0.0, "count": 0})
        dur = e.get("dur", 0.0) / 1e6
        s["seconds"] += dur
        s["count"] += 1
        if cat in WORK_CATS:
            key = (cat, e["tid"])
            per_track[key] = per_track.get(key, 0.0) + dur
            t_lo = min(t_lo, e["ts"])
            t_hi = max(t_hi, e["ts"] + e.get("dur", 0.0))
    for s in stages.values():
        s["mean_ms"] = s["seconds"] / s["count"] * 1e3
    wall = max(0.0, (t_hi - t_lo) / 1e6) if t_hi else 0.0
    bottleneck, busy = None, 0.0
    if per_track:
        (bottleneck, _), busy = max(per_track.items(),
                                    key=lambda kv: kv[1])
    return {"stages": stages, "wall_s": wall, "bottleneck": bottleneck,
            "bottleneck_busy_s": busy,
            "overlap_efficiency": min(1.0, busy / wall) if wall else 0.0}


def residency_summary(spans) -> dict:
    """Cached-scatter savings (DESIGN.md §12): how many chunk pushes the
    resident-operand cache elided, the bytes those pushes would have moved,
    and an estimate of the seconds saved — cached count × the mean duration
    of the *cold* ``scatter`` spans in the same trace (the work a warm hit
    replaces)."""
    cached = [e for e in spans if e["name"] == "scatter_cached"]
    cold = [e for e in spans if e["name"] == "scatter"]
    cold_mean_s = (sum(e.get("dur", 0.0) for e in cold) / len(cold) / 1e6
                   if cold else 0.0)
    return {
        "cached_spans": len(cached),
        "cached_bytes": sum(e.get("args", {}).get("bytes", 0)
                            for e in cached),
        "cold_scatter_spans": len(cold),
        "cold_scatter_mean_ms": cold_mean_s * 1e3,
        "est_saved_s": len(cached) * cold_mean_s,
    }


def render(path, top: int = 10, markdown: bool = False) -> str:
    spans, tracks = split_events(load_events(path))
    summ = stage_summary(spans)
    res = residency_summary(spans)
    lines: list[str] = []
    if markdown:
        lines += [f"### Runtime trace `{pathlib.Path(path).name}`", ""]
    lines.append(
        f"{len(spans)} spans on {len(tracks)} tracks · wall "
        f"{summ['wall_s'] * 1e3:.1f} ms · bottleneck stage "
        f"{summ['bottleneck'] or '—'} "
        f"({summ['bottleneck_busy_s'] * 1e3:.1f} ms busy) · overlap "
        f"efficiency {summ['overlap_efficiency']:.0%}")
    if res["cached_spans"]:
        lines.append(
            f"resident cache: {res['cached_spans']} scatter(s) elided · "
            f"{res['cached_bytes'] / 1e6:.2f} MB not pushed · "
            f"~{res['est_saved_s'] * 1e3:.1f} ms saved "
            f"(mean cold scatter {res['cold_scatter_mean_ms']:.3f} ms)")
    lines.append("")
    if markdown:
        lines += ["| stage | spans | busy ms | mean ms |",
                  "|---|---|---|---|"]
        fmt = "| {c} | {n} | {s:.1f} | {m:.3f} |".format
    else:
        lines.append(f"{'stage':<12}{'spans':>7}{'busy ms':>10}"
                     f"{'mean ms':>10}")
        fmt = "{c:<12}{n:>7}{s:>10.1f}{m:>10.3f}".format
    for cat, s in sorted(summ["stages"].items(),
                         key=lambda kv: -kv[1]["seconds"]):
        lines.append(fmt(c=cat, n=s["count"], s=s["seconds"] * 1e3,
                         m=s["mean_ms"]))
    lines.append("")
    title = f"top {top} slowest spans"
    if markdown:
        lines += [f"#### {title}", "",
                  "| span | cat | track | ms |", "|---|---|---|---|"]
        row = "| {name} | {cat} | {track} | {ms:.3f} |".format
    else:
        lines.append(title)
        row = "  {name:<18}{cat:<12}{track:<12}{ms:>10.3f} ms".format
    for r in top_slowest(spans, tracks, top):
        lines.append(row(**{k: r[k] for k in
                            ("name", "cat", "track", "ms")}))
    return "\n".join(lines) + "\n"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", help="trace JSON from session.trace_export()")
    ap.add_argument("--top", type=int, default=10,
                    help="how many slowest spans to list (default 10)")
    ap.add_argument("--summary", action="store_true",
                    help="markdown output (for $GITHUB_STEP_SUMMARY)")
    args = ap.parse_args(argv)
    print(render(args.trace, top=args.top, markdown=args.summary), end="")
    return 0


if __name__ == "__main__":
    sys.exit(main())
