"""Name the device's idle time by the runtime's own spans.

The program writes a ``pim.<name>`` profiler annotation around each stage
of a request (``src/repro/runtime/trace.py``), tagged with the request's
id, on the thread that does the work.  This reads them from the traced
window's ``.xplane.pb`` and splits every idle gap of the device (the same
gaps as ``trace_reduce``: the window of the ``bench.window`` span less the
union of the chips' operation intervals) exactly by the serving thread's
span that covers each instant:

* **pipeline**: ``pim.split``, ``pim.scatter``, ``pim.scatter_cached``,
  ``pim.launch``, ``pim.device_wait``, ``pim.copy_out``, ``pim.merge``;
* **scheduler**: ``pim.wait``, ``pim.pop``, ``pim.fulfill``, and the rest
  of ``pim.batch`` outside its pipeline spans;
* **rest**: the serving thread in none of them.

A serving thread is one that carries any of these spans; a client thread
carries only ``pim.submit`` (and the harness's ``client.*``), and never
names a gap.  Where serving threads overlap (rank pipelines), an instant
in a pipeline span of any of them counts as pipeline.  A trace with no
serving span at all (a program without these spans), or with no chip,
gives no attribution.

    python3 bench/span_reduce.py <file.xplane.pb>   # idle seconds per span
"""
from __future__ import annotations

import dataclasses
import functools
import json
import os
import sys

import trace_reduce

PIPELINE = ("pim.split", "pim.scatter", "pim.scatter_cached", "pim.launch",
            "pim.device_wait", "pim.copy_out", "pim.merge")
SCHEDULER = ("pim.wait", "pim.pop", "pim.batch", "pim.fulfill")
#: span name -> the stage it names; pipeline outranks scheduler
STAGE = {**{n: 1 for n in SCHEDULER}, **{n: 2 for n in PIPELINE}}
PREFIX = "pim."
REST = "(no span)"


@dataclasses.dataclass
class Attribution:
    """Idle seconds of the window, mean over the chips."""

    window_s: float
    idle_s: float
    pipeline_s: float
    scheduler_s: float
    chips: int
    spans: dict               # innermost pim.* span (or REST) -> seconds

    @property
    def rest_s(self) -> float:
        return self.idle_s - self.pipeline_s - self.scheduler_s

    def share(self, seconds: float) -> float:
        """``seconds`` in per cent of the window."""
        return 100.0 * seconds / self.window_s


def _segments(spans: list) -> list:
    """A thread's nested ``(start, end, name)`` spans as disjoint
    ``(start, end, name, stage)`` pieces: each instant under its innermost
    span, and the stage of the innermost span that names one (0: none)."""
    out: list = []
    stack: list = []                      # (end, name, stage)
    t = 0

    def upto(x):
        nonlocal t
        if stack and x > t:
            _, name, stage = stack[-1]
            out.append((t, x, name, stage))
        t = max(t, x)

    for s, e, name in sorted(spans, key=lambda sp: (sp[0], -sp[1])):
        while stack and stack[-1][0] <= s:
            upto(stack[-1][0])
            stack.pop()
        upto(s)
        if stack:
            e = min(e, stack[-1][0])
        stage = STAGE.get(name, stack[-1][2] if stack else 0)
        stack.append((e, name, stage))
    while stack:
        upto(stack[-1][0])
        stack.pop()
    return out


def _overlay(threads: list) -> list:
    """Disjoint pieces of several threads' segments: where they overlap,
    the piece of the highest stage wins."""
    if len(threads) == 1:
        return threads[0]
    points = sorted({x for segs in threads for s, e, *_ in segs
                     for x in (s, e)})
    at = [0] * len(threads)
    out: list = []
    for a, b in zip(points, points[1:]):
        best = None
        for k, segs in enumerate(threads):
            while at[k] < len(segs) and segs[at[k]][1] <= a:
                at[k] += 1
            if at[k] < len(segs) and segs[at[k]][0] <= a:
                seg = segs[at[k]]
                if best is None or seg[3] > best[3]:
                    best = seg
        if best is not None:
            out.append((a, b, best[2], best[3]))
    return out


def _attribute(gaps: list, pieces: list, totals: dict, spans: dict):
    """Add the overlap of the sorted disjoint ``gaps`` with the sorted
    disjoint ``pieces`` into ``totals`` (by stage) and ``spans`` (by
    name), in nanoseconds."""
    j = 0
    for gs, ge in gaps:
        while j < len(pieces) and pieces[j][1] <= gs:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < ge:
            s, e, name, stage = pieces[k]
            d = min(e, ge) - max(s, gs)
            if d > 0:
                totals[stage] = totals.get(stage, 0) + d
                spans[name] = spans.get(name, 0) + d
            k += 1


def reduce(path: str, window: str = trace_reduce.WINDOW
           ) -> Attribution | None:
    """The window's idle time by stage, or None when no thread carries a
    serving span or the trace has no chip."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    win, serving, devices = None, [], []
    for plane in data.planes:
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            devices.append(plane)
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            ours, serves = [], False
            for e in line.events:
                if e.name == window and e.duration_ns > 0:
                    win = (e.start_ns, e.start_ns + e.duration_ns)
                elif e.name.startswith(PREFIX) and e.duration_ns > 0:
                    ours.append((e.start_ns, e.start_ns + e.duration_ns,
                                 e.name))
                    serves = serves or e.name in STAGE
            if serves:
                serving.append(_segments(ours))
    if win is None:
        raise ValueError(f"{path}: no host span named {window!r}")
    if not serving or not devices:
        return None
    lo, hi = win
    pieces = _overlay(serving)
    totals: dict = {}
    spans: dict = {}
    idle = 0
    for plane in devices:                 # as trace_reduce.reduce does
        lines = {ln.name: ln for ln in plane.lines}
        name = (trace_reduce.OPS_LINE if trace_reduce.OPS_LINE in lines
                else trace_reduce.MODULES_LINE)
        busy = ([(s, e) for _, s, e in
                 trace_reduce._events(lines[name], lo, hi)]
                if name in lines else [])
        merged = trace_reduce._union(busy)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        idle += sum(e - s for s, e in gaps)
        _attribute(gaps, pieces, totals, spans)
    chips = len(devices)
    per = 1e-9 / chips
    spans[REST] = idle - sum(totals.values())
    return Attribution(
        window_s=(hi - lo) * 1e-9, idle_s=idle * per,
        pipeline_s=totals.get(2, 0) * per, scheduler_s=totals.get(1, 0) * per,
        chips=chips,
        spans={n: s * per for n, s in sorted(spans.items(),
                                               key=lambda kv: -kv[1])})


@functools.lru_cache(maxsize=4)
def _reduce_cached(path: str, mtime_ns: int) -> Attribution | None:
    return reduce(path)


def of_run(run) -> Attribution | None:
    """The attribution of a traced run's window: the newest ``.xplane.pb``
    where ``harness.Window`` writes the cell's trace, read once for every
    metric that asks.  None when untraced, when the program wrote no
    serving span, or when that file holds another window than the run's."""
    import harness
    if run.trace is None or run.cell is None:
        return None
    files = sorted((harness.ROOT / "bench_out" / "trace" / run.cell.name)
                   .rglob("*.xplane.pb"))
    if not files:
        return None
    path = str(files[-1])
    got = _reduce_cached(path, os.stat(path).st_mtime_ns)
    if got is None or abs(got.window_s - run.trace.window_s) > 1e-9:
        return None
    return got


if __name__ == "__main__":
    a = reduce(sys.argv[1])
    if a is None:
        print("no serving span in the trace")
        sys.exit(1)
    print(json.dumps(dataclasses.asdict(a), indent=1))
    print(f"window {a.window_s:.6f} s, idle {a.share(a.idle_s):.3f} %: "
          f"pipeline {a.share(a.pipeline_s):.3f} %, scheduler "
          f"{a.share(a.scheduler_s):.3f} %, rest {a.share(a.rest_s):.3f} %")
    print(f"{'span':<24}{'idle s':>12}{'% of idle':>11}")
    for name, s in a.spans.items():
        print(f"{name:<24}{s:>12.6f}{100 * s / a.idle_s:>11.2f}")
