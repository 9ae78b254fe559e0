"""The benchmark's general machinery: finding a cell's files by name, the
device check, the compile cache, timing, the window, the traced run, and
the result line.

A cell is one entry of ``workloads`` in ``BENCHMARK.json``.  Everything
that belongs to one configuration, traffic mix or metric sits in a file of
its own, found by the name the entry gives:

* ``bench/configs/<config>.json``   the deployment's sizes, its source, the
  keys cut from it (``reduced``), the sizes set here (``assumed``), its
  plain reference (``reference`` names ``bench/references/<name>.py``) and
  the limits of its correctness checks (``checks``);
* ``bench/traffic/<traffic>.json``  the mix's parameters; ``driver`` names
  the general generator ``bench/drivers/<driver>.py`` that reads them;
* ``bench/metrics/<metric>.py``     ``read(run) -> float | None`` for each
  metric of ``end_to_end`` and ``per_layer``.

A driver's ``run(ctx)`` sets the deployment up from the seed, warms it,
drives the window, checks what the window produced against the reference
and returns a :class:`Run`.  Adding a cell, a mix, a configuration or a
metric is adding files and entries; nothing here names one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import pathlib
import sys
import time
from typing import Any, Callable

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


class Refused(Exception):
    """The run cannot measure: no accelerator, too few chips, or a device
    the peaks table does not know.  No result is printed."""


# -- files by name -------------------------------------------------------------

def load_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def _load_json(kind: str, name: str) -> dict:
    path = BENCH_DIR / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file {path}")
    return json.loads(path.read_text())


def _load_module(kind: str, name: str):
    path = BENCH_DIR / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file {path}")
    modname = f"_bench_{kind}_" + "".join(
        c if c.isalnum() else "_" for c in name)
    if modname in sys.modules:
        return sys.modules[modname]
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    spec.loader.exec_module(mod)
    return mod


def load_config(name: str) -> dict:
    return _load_json("configs", name)


def load_traffic(name: str) -> dict:
    return _load_json("traffic", name)


def load_driver(name: str):
    return _load_module("drivers", name)


def load_reference(name: str):
    return _load_module("references", name)


def load_metric(name: str):
    return _load_module("metrics", name)


@dataclasses.dataclass
class Cell:
    """One workload entry with its files resolved."""

    name: str
    entry: dict
    config: dict
    traffic: dict
    end_to_end: list          # metric entries this cell reports untraced
    per_layer: list           # ... and traced

    @property
    def chips(self) -> int:
        return int(self.entry["chips"])


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(bench: dict, name: str) -> Cell:
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r}; have {sorted(entries)}")
    entry = entries[name]
    return Cell(name=name, entry=entry,
                config=load_config(entry["config"]),
                traffic=load_traffic(entry["traffic"]),
                end_to_end=[m for m in bench["end_to_end"]
                            if _reports(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _reports(m, name)])


# -- device, peaks, compile cache ---------------------------------------------

def load_peaks(kind: str) -> dict:
    table = json.loads((BENCH_DIR / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise Refused(f"device kind {kind!r} is not in bench/peaks.json "
                      f"(known: {sorted(table)})")
    return table[kind]


def require_devices(chips: int, devices=None):
    """The first ``chips`` TPU devices; :class:`Refused` on any other
    platform or too few chips."""
    if devices is None:
        import jax
        devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise Refused(f"needs a TPU, found platform {platform!r}")
    if len(devices) < chips:
        raise Refused(f"the cell needs {chips} chips, found {len(devices)}")
    return devices[:chips]


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    where set, else ``.jax_cache/`` in the checkout (a fixed path, part of
    the cache key).  Every program is cached, however short its compile, so
    that a second run compiles nothing."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_info(devices) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak_bytes(devices) -> int | None:
    """``peak_bytes_in_use`` of the fullest chip."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    return max(peaks) if all(p is not None for p in peaks) else None


# -- compiles ------------------------------------------------------------------

class CompileWatch:
    """Backend compiles as wall-clock spans, from JAX's monitoring events
    (the program's ``chip_smoke.CompileWatch``, copied)."""

    def __init__(self):
        import jax
        self.spans: list[tuple[float, float]] = []
        self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            end = time.perf_counter()
            self.spans.append((end - duration, end))

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def count(self, t0: float, t1: float) -> int:
        """Compiles that ended inside ``[t0, t1]``."""
        return sum(1 for _, e in self.spans if t0 <= e <= t1)


# -- the run -------------------------------------------------------------------

@dataclasses.dataclass
class Check:
    """One number compared with its limit: correct iff value <= limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit


@dataclasses.dataclass
class Context:
    """What a driver gets: the cell, the run's arguments, and the hooks."""

    cell: Cell
    seed: int
    seconds: float
    trace: bool
    t_process: float                     # perf_counter at process start
    devices: list
    peaks: dict
    watch: Any = None
    out_dir: pathlib.Path = ROOT / "bench_out"
    log: Callable[[str], None] = lambda msg: print(msg, file=sys.stderr,
                                                   flush=True)

    def reference(self):
        return load_reference(self.cell.config["reference"])

    def limit(self, name: str) -> float:
        return float(self.cell.config["checks"][name])


@dataclasses.dataclass
class Run:
    """What a driver returns: the measurements every metric reader may
    read, and the checks that decide ``correct``."""

    setup_s: float = 0.0
    window_s: float = 0.0
    attempted: int = 0
    failed: int = 0
    latencies_s: list = dataclasses.field(default_factory=list)
    completed_in_window: int = 0
    records: list = dataclasses.field(default_factory=list)
    compiles_in_window: int = 0
    memory_peak_bytes: int | None = None
    checks: list = dataclasses.field(default_factory=list)
    trace: Any = None                    # trace_reduce.Summary, traced runs
    facts: dict = dataclasses.field(default_factory=dict)
    peaks: dict = dataclasses.field(default_factory=dict)
    cell: Any = None


class Window:
    """The measured window: the compile count across it and, in a traced
    run, the profiler around it with a ``bench.window`` annotation."""

    def __init__(self, ctx: Context):
        self.ctx = ctx
        self.trace_dir = ctx.out_dir / "trace" / ctx.cell.name

    def __enter__(self):
        if self.ctx.trace:
            import shutil

            import jax
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            self.trace_dir.mkdir(parents=True, exist_ok=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # host spans, no Python calls
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(self.trace_dir),
                                     profiler_options=opts)
            self._ann = annotate("bench.window")
            self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.t1 = time.perf_counter()
        if self.ctx.trace:
            import jax
            self._ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
        self.compiles = (self.ctx.watch.count(self.t0, self.t1)
                         if self.ctx.watch is not None else 0)

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def reduce(self):
        """The trace of a traced window, reduced (None when untraced)."""
        if not self.ctx.trace:
            return None
        import trace_reduce
        files = sorted(self.trace_dir.rglob("*.xplane.pb"))
        if not files:
            return None
        return trace_reduce.reduce(str(files[-1]), window="bench.window")


def annotate(name: str):
    """A host span in the profiler's trace (next to free when no trace
    runs)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


def exact_percentile(values, q: float) -> float:
    """The ``q``-th percentile of every value, linearly interpolated
    between order statistics (numpy's default)."""
    import numpy as np
    return float(np.percentile(np.asarray(values, float), q))


def metrics_of(run: Run, entries: list) -> dict:
    """``{name: {"value", "unit"}}`` for every entry whose reader finds
    something to read."""
    out = {}
    for m in entries:
        value = load_metric(m["name"]).read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(ctx: Context) -> tuple[Run, dict]:
    """Drive the cell's traffic once; returns the run and its result line."""
    run = load_driver(ctx.cell.traffic["driver"]).run(ctx)
    run.cell, run.peaks = ctx.cell, ctx.peaks
    return run, result_line(run, ctx.cell, ctx.devices, ctx.trace)


def result_line(run: Run, cell: Cell, devices, trace: bool) -> dict:
    # a check that found no answer reads inf; JSON has no infinity
    checks = {c.name: {"value": c.value if math.isfinite(c.value)
                       else 1e300, "limit": c.limit} for c in run.checks}
    correct = (bool(run.checks) and all(c.ok for c in run.checks)
               and run.failed == 0)
    device = dict(device_info(devices),
                  memory_peak_bytes=run.memory_peak_bytes)
    line = {"correct": correct, "attempted": run.attempted,
            "failed": run.failed,
            "metrics": metrics_of(run, cell.per_layer if trace
                                  else cell.end_to_end),
            "device": device}
    if trace and run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        line["breakdown"] = run.trace.breakdown()
    line["checks"] = checks
    return line
