"""Reduce a ``jax.profiler`` trace (``.xplane.pb``) to the numbers the
benchmark reports: device busy time over the window, device time per
kernel, the operations that took most time, and the idle gaps named by
what the host was doing in them.

The window is the host span that the harness opens around its measured
window (``harness.Window``: a ``TraceAnnotation`` named ``bench.window``).
Each TPU is a plane named ``/device:TPU:<n>``; its ``XLA Ops`` line holds
one event per operation run, and its ``XLA Modules`` line one per program
run (a jitted phase: the module's name is the phase's name).  Busy time is
the union of the operation intervals inside the window, and idle time the
rest; both are averaged over the chips.  An idle gap is named by what the
host was doing at its midpoint: the innermost of the harness's own spans
(``client.<workload>``: the benchmark's calls into the program) covering it, then, after a ``/``, the innermost runtime span on
that span's thread (a device put, an ``np.asarray`` of a result, an
executable launch), where there is one.

    python3 bench/trace_reduce.py <file.xplane.pb>    # prints the summary
"""
from __future__ import annotations

import dataclasses
import heapq
import json
import re
import sys

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "bench.window"
#: name prefixes of the harness's own host spans
HARNESS = ("bench.", "client.")
TOP = 10
#: the chips' planes (not the host's or a custom plane such as Megascale)
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                         # mean over the chips
    chips: int
    kernels: dict                         # module name -> [seconds, count]
    device_ops: list                      # [[op name, seconds]] top TOP
    idle_gaps: list                       # [[host span, seconds]] top TOP

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s if self.window_s else 0.0

    def kernel_time(self, pattern: str) -> tuple[float, int]:
        """Seconds and runs, summed over chips, of the modules whose name
        matches the regular expression ``pattern``."""
        rx = re.compile(pattern)
        secs = runs = 0
        for name, (s, n) in self.kernels.items():
            if rx.search(name):
                secs += s
                runs += n
        return secs, runs

    def breakdown(self) -> dict:
        return {"device_ops": self.device_ops[:TOP],
                "idle_gaps": self.idle_gaps[:TOP]}


def _events(line, lo: float, hi: float):
    """(start, end) ns of the line's events, clipped to [lo, hi]."""
    for e in line.events:
        s = e.start_ns
        t = s + e.duration_ns
        if t > lo and s < hi:
            yield e.name, max(s, lo), min(t, hi)


def _union(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _innermost(spans: list, points: list) -> list:
    """For each of the sorted ``points``, the shortest of the
    ``(start, end, name)`` spans covering it (None where none does)."""
    spans = sorted(spans)
    active: list = []                     # heap of (duration, end, name)
    out, i = [], 0
    for p in points:
        while i < len(spans) and spans[i][0] <= p:
            s, e, name = spans[i]
            heapq.heappush(active, (e - s, e, name))
            i += 1
        while active and active[0][1] <= p:
            heapq.heappop(active)
        out.append(active[0] if active else None)
    return out


def _name_gaps(gaps: list, lines: list) -> dict:
    """Idle seconds per name (see the module's docstring); ``lines`` holds
    each host thread's ``(start, end, name)`` spans."""
    gaps = sorted(gaps)
    mids = [(s + e) / 2 for s, e in gaps]
    best = [None] * len(gaps)              # (duration, harness, runtime)
    for spans in lines:
        ours = [sp for sp in spans if sp[2].startswith(HARNESS)]
        if not ours:
            continue
        theirs = [sp for sp in spans if not sp[2].startswith(HARNESS)]
        for k, (h, r) in enumerate(zip(_innermost(ours, mids),
                                       _innermost(theirs, mids))):
            if h is not None and (best[k] is None or h[0] < best[k][0]):
                best[k] = (h[0], h[2], r[2] if r is not None else None)
    out: dict = {}
    for (s, e), b in zip(gaps, best):
        name = ("(no harness span)" if b is None
                else b[1] if b[2] is None else f"{b[1]}/{b[2]}")
        out[name] = out.get(name, 0.0) + (e - s) * 1e-9
    return out


def reduce(path: str, window: str = WINDOW) -> Summary:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host_planes = [], []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            devices.append(plane)
        elif plane.name.startswith("/host:"):
            host_planes.append(plane)
    win = None
    host = []
    for plane in host_planes:
        for line in plane.lines:
            spans = []
            for e in line.events:
                if e.duration_ns <= 0:
                    continue
                if e.name == window:
                    win = (e.start_ns, e.start_ns + e.duration_ns)
                else:
                    spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                  e.name))
            host.append(spans)
    if win is None:
        raise ValueError(f"{path}: no host span named {window!r}")
    lo, hi = win
    busy_total = 0.0
    kernels: dict = {}
    ops: dict = {}
    gaps: list = []
    for plane in devices:
        lines = {ln.name: ln for ln in plane.lines}
        busy = []
        if OPS_LINE in lines:
            for name, s, e in _events(lines[OPS_LINE], lo, hi):
                busy.append((s, e))
                ops[name] = ops.get(name, 0.0) + (e - s) * 1e-9
        if MODULES_LINE in lines:
            for name, s, e in _events(lines[MODULES_LINE], lo, hi):
                k = kernels.setdefault(re.sub(r"\(\d+\)$", "", name),
                                       [0.0, 0])
                k[0] += (e - s) * 1e-9
                k[1] += 1
                if OPS_LINE not in lines:
                    busy.append((s, e))
        merged = _union(busy)
        busy_total += sum(e - s for s, e in merged) * 1e-9
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    chips = max(1, len(devices))
    named = _name_gaps(gaps, host)
    per_chip = [[n, s / chips] for n, s in
                sorted(named.items(), key=lambda kv: -kv[1])]
    return Summary(
        window_s=(hi - lo) * 1e-9, busy_s=busy_total / chips,
        chips=len(devices), kernels=kernels,
        device_ops=[[n, s] for n, s in sorted(ops.items(),
                                               key=lambda kv: -kv[1])][:TOP],
        idle_gaps=per_chip[:TOP])


if __name__ == "__main__":
    s = reduce(sys.argv[1])
    print(json.dumps(dataclasses.asdict(s), indent=1))
    print(f"idle share {s.idle_share:.4f}")
