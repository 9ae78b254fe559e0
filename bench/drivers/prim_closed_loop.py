"""Closed-loop driver for PrIM workloads served by one ``pim.session``.

Traffic parameters (``bench/traffic/<mix>.json``):

* ``clients``   closed-loop clients; each sends one request and waits for
  its result before sending the next (no think time);
* ``mix``       workload names, served round-robin: client ``c``'s ``i``-th
  request is ``mix[(c + i) % len(mix)]``;
* ``pool``      operand sets per column workload, made from the seed in
  set-up; request ``i`` of client ``c`` takes set ``(c + i // len(mix)) %
  pool``, sent from the host every time (nothing of it stays resident);
* ``sample``    answers per workload kept for the check (a reservoir over
  every answer of the window, drawn from the seed);
* ``warm_rounds`` requests per client before the window, in set-up.

Configuration (``bench/configs/<config>.json``): ``session`` is passed to
``pim.session`` (serving mode, untuned defaults, no autotuning); the
dataset sizes (``gemv_rows``, ``gemv_cols``, ``va_elements``, ...) are read
by the configuration's reference, which draws the operands.  GEMV's matrix
is made on the device from the seed, pinned once through a
``ResidentHandle`` and only its vector changes per request: a fresh seeded
``x`` each time.

The window opens once set-up is done and closes ``--seconds`` later: clients
send nothing after the close and wait for what they sent.  Latency is client
submit to result in hand, over every request sent in the window; the rate
counts the requests completed inside it.
"""
from __future__ import annotations

import threading
import time

import numpy as np

import harness
from harness import annotate


def _seed_ints(seed: int, *more: int) -> list:
    return [seed % (1 << 63), *more]


class _Reservoir:
    """Uniform sample of ``k`` answers per workload, drawn from the seed."""

    def __init__(self, k: int, seed: int):
        self.k = k
        self.rng = np.random.default_rng(_seed_ints(seed, 7))
        self.lock = threading.Lock()
        self.seen: dict = {}
        self.kept: dict = {}

    def offer(self, workload: str, item) -> None:
        with self.lock:
            n = self.seen.get(workload, 0) + 1
            self.seen[workload] = n
            kept = self.kept.setdefault(workload, [])
            if len(kept) < self.k:
                kept.append(item)
            else:
                j = int(self.rng.integers(n))
                if j < self.k:
                    kept[j] = item


class _Client(threading.Thread):
    def __init__(self, c: int, loop: "_Loop", rounds: float):
        super().__init__(name=f"bench-client-{c}", daemon=True)
        self.c, self.loop, self.rounds = c, loop, rounds
        self.rng = np.random.default_rng(_seed_ints(loop.seed, 11, c))
        self.done: list = []               # (workload, t_submit, t_done, ok)
        self.i = 0

    def request(self):
        lp = self.loop
        w = lp.mix[(self.c + self.i) % len(lp.mix)]
        if w == "GEMV":
            x = self.rng.standard_normal(lp.gemv_cols, dtype=np.float32)
            return w, (lp.gemv_handle, x), x
        k = (self.c + self.i // len(lp.mix)) % lp.pool
        return w, lp.operands[w][k], k

    def run(self):
        lp = self.loop
        while self.i < self.rounds:
            w, args, key = self.request()
            t_sub = time.perf_counter()
            if t_sub >= lp.deadline:
                return
            ok = True
            with annotate(f"client.{w}"):
                try:
                    out = lp.session.submit(w, *args).result()
                except Exception as e:      # counted as failed
                    lp.errors.append(repr(e))
                    ok, out = False, None
            t_done = time.perf_counter()
            self.done.append((w, t_sub, t_done, ok))
            if ok and lp.sample is not None:
                lp.sample.offer(w, (key, out))
            self.i += 1


class _Loop:
    """The session, the operands and the clients of one run."""

    def __init__(self, ctx: harness.Context):
        from repro import pim
        cfg, tr = ctx.cell.config, ctx.cell.traffic
        self.seed = ctx.seed
        self.mix = list(tr["mix"])
        self.pool = int(tr.get("pool", 1))
        self.cfg = cfg
        ref = ctx.reference()
        self.ref = ref
        self.session = pim.session(**cfg["session"], autotune=False).start()
        self.operands: dict = {}
        self.gemv_handle = self.gemv_host = None
        rng = np.random.default_rng(_seed_ints(ctx.seed, 3))
        for w in dict.fromkeys(self.mix):
            if w == "GEMV":
                self._make_gemv(ctx, ref)
            else:
                self.operands[w] = [
                    ref.make_column_args(w, rng, cfg)
                    for _ in range(self.pool)]
        self.deadline = 0.0
        self.errors: list = []
        self.sample = None

    def _make_gemv(self, ctx, ref):
        import jax
        from repro.runtime.resident import ResidentHandle
        rows, cols = ref.gemv_shape(self.cfg)
        key = int(np.random.default_rng(_seed_ints(ctx.seed, 5))
                  .integers(1 << 31))
        make = jax.jit(lambda k: jax.random.normal(k, (rows, cols),
                                                   np.float32))
        a = make(jax.random.PRNGKey(key))
        self.gemv_host = np.asarray(a)
        del a
        self.gemv_cols = cols
        self.gemv_handle = ResidentHandle(self.gemv_host)
        self.session.pin("GEMV", self.gemv_handle,
                         np.zeros(cols, np.float32))

    def drive(self, clients: int, until: float,
              rounds: float = float("inf")) -> list:
        """Run ``clients`` closed-loop clients until ``until`` or until each
        has sent ``rounds`` requests."""
        self.deadline = until
        cs = [_Client(c, self, rounds) for c in range(clients)]
        for c in cs:
            c.start()
        for c in cs:
            c.join()
        return cs


def run(ctx: harness.Context) -> harness.Run:
    tr = ctx.cell.traffic
    clients = int(tr["clients"])
    with annotate("bench.setup"):
        loop = _Loop(ctx)
        # the window's shapes and batch mixes, untimed
        loop.drive(clients, float("inf"),
                   rounds=int(tr.get("warm_rounds", len(loop.mix))))
        if loop.errors:
            raise RuntimeError(f"warm-up failed: {loop.errors[:3]}")
    loop.sample = _Reservoir(int(tr.get("sample", 4)), ctx.seed)
    n_before = len(loop.session.telemetry.records)
    res = harness.Run()
    res.setup_s = time.perf_counter() - ctx.t_process
    ctx.log(f"setup {res.setup_s:.3f} s")

    win = harness.Window(ctx)
    with win:
        cs = loop.drive(clients, time.perf_counter() + ctx.seconds)
    t0, t1 = win.t0, win.t1
    done = [d for c in cs for d in c.done]
    res.window_s = ctx.seconds
    res.attempted = len(done)
    res.failed = sum(1 for d in done if not d[3])
    res.latencies_s = [d[2] - d[1] for d in done if d[3]]
    res.completed_in_window = sum(1 for d in done
                                  if d[3] and d[2] <= t0 + ctx.seconds)
    res.compiles_in_window = win.compiles
    recs = list(loop.session.telemetry.records)[n_before:]
    res.records = [r for r in recs if t0 <= r.t_submit <= t1]
    columns = [w for w in dict.fromkeys(loop.mix) if w != "GEMV"]
    res.facts = {"n_chunks": loop.session.scheduler.n_chunks,
                 "elements": {w: loop.ref.column_elements(w, loop.cfg)
                              for w in columns},
                 "gemv_shape": (list(loop.ref.gemv_shape(loop.cfg))
                                if "GEMV" in loop.mix else None),
                 "mix": loop.mix, "n_banks": loop.session.n_banks,
                 "served": {w: sum(1 for d in done if d[0] == w and d[3])
                            for w in loop.mix}}
    res.trace = win.reduce()
    res.memory_peak_bytes = harness.memory_peak_bytes(ctx.devices)
    lat = sorted(res.latencies_s) or [0.0]
    ctx.log(f"window: {res.attempted} requests, {res.failed} failed, "
            f"{res.completed_in_window} completed in {ctx.seconds} s; "
            f"compiles in window {res.compiles_in_window}; latency ms "
            f"p50 {1e3 * lat[len(lat) // 2]:.3f} max {1e3 * lat[-1]:.3f}, "
            f"over 4x p50: {sum(x > 4 * lat[len(lat) // 2] for x in lat)}; "
            f"errors {loop.errors[:3]}")
    loop.session.close()
    res.checks = check(ctx, loop)
    return res


def check(ctx: harness.Context, loop: _Loop) -> list:
    """Compare the sampled answers with the plain reference."""
    ref, kept = loop.ref, loop.sample.kept
    checks = []
    if "GEMV" in loop.mix:
        got = kept.get("GEMV", [])
        ys = [np.asarray(y).reshape(-1) for _, y in got]
        if not got or any(y.shape != ys[0].shape for y in ys) \
                or ys[0].shape[0] != loop.gemv_host.shape[0]:
            err = float("inf")
        else:
            err = ref.gemv_error(loop.gemv_host,
                                 np.stack([k for k, _ in got]), np.stack(ys))
        checks.append(harness.Check("gemv_err", err, ctx.limit("gemv_err")))
    columns = [w for w in dict.fromkeys(loop.mix) if w != "GEMV"]
    if columns:
        wrong = 0
        for w in columns:
            refs = {}
            wrong += not kept.get(w)          # a workload never answered
            for k, out in kept.get(w, []):
                if k not in refs:
                    refs[k] = ref.column_ref(w, loop.operands[w][k])
                wrong += not ref.same_answer(out, refs[k])
        checks.append(harness.Check("wrong_answers", float(wrong),
                                    ctx.limit("wrong_answers")))
    return checks
