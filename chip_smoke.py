"""Smoke run of the PIM session on TPU chips, through its user entry points.

One process drives every phase.  It refuses any device but a TPU and fails
on the first phase whose result disagrees with its reference:

* **registry** — ``pim.session()`` with untuned defaults serves every PrIM
  registry workload through ``submit()``; each result is checked with the
  entry's ``compare`` against ``ref()``.  Linear workloads run at
  ``scale=256`` (operands of tens to hundreds of MB); the serialized-only
  NW and BFS at the scales in ``SERIAL_SCALES``;
* **decode** — ``DecodeEngine`` serves TinyLlama 1.1B at its published
  widths (22 layers, d_model 2048, 32 heads / 4 KV heads, d_ff 5632, vocab
  32000) in float32 with seeded random weights, and its tokens are compared
  with ``greedy_generate``.

    python3 chip_smoke.py              # one chip: registry + decode
    python3 chip_smoke.py --chips 4    # four chips: the registry on a 4-bank
                                       # grid and a 2x2 ranked grid, and a
                                       # ranks=2 decode engine

Earlier lines report sizes, resident bytes, compile seconds apart from run
seconds and the device's peak bytes in use; the last line is one JSON
object naming the device.  The persistent compilation cache goes where
``JAX_COMPILATION_CACHE_DIR`` says, else to ``.jax_cache`` in the checkout,
so a second run reports cache hits.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.launch.cli import enable_compile_cache  # noqa: E402

#: scale of every pipelineable registry workload (operand sizes grow
#: linearly: GEMV is a 131072 x 256 float32 matrix, VA two 16.8M-int32
#: vectors)
SCALE = 256
#: serialized-only workloads: host-driven loops, sized to finish inside a
#: minute each (NW: two 2048-long sequences; BFS: 2097152 vertices, whose
#: host-side graph generation and reference take most of that minute)
SERIAL_SCALES = {"NW": 32, "BFS": 4096}
SEED = 0
STREAMS, PROMPT_LEN, MAX_NEW = 4, 8, 8


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileWatch:
    """Backend compiles, as wall-clock spans, and persistent-cache
    hits/misses, from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.spans: list[tuple[float, float]] = []   # perf_counter seconds
        self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            end = time.perf_counter()
            self.spans.append((end - duration, end))

    def compile_seconds(self, t0: float, t1: float) -> float:
        """Wall seconds within ``[t0, t1]`` in which a backend compile ran.
        The spans are merged, not summed: the rank threads of a ranked
        session compile at the same time."""
        total, cur = 0.0, None
        for s, e in sorted((max(s, t0), min(e, t1)) for s, e in self.spans):
            if e <= s:
                continue
            if cur is not None and s <= cur[1]:
                cur = (cur[0], max(cur[1], e))
                continue
            if cur is not None:
                total += cur[1] - cur[0]
            cur = (s, e)
        return total + (cur[1] - cur[0] if cur is not None else 0.0)

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


class Timed:
    """Wall seconds of a block, split into backend compile and the rest."""

    def __init__(self, watch: CompileWatch):
        self.watch = watch

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self.wall_s = t1 - self.t0
        self.compile_s = self.watch.compile_seconds(self.t0, t1)
        self.run_s = self.wall_s - self.compile_s

    def __str__(self) -> str:
        return (f"compile {self.compile_s:.3f}s run {self.run_s:.3f}s "
                f"(wall {self.wall_s:.3f}s)")


def peak_bytes() -> int | None:
    """Largest ``peak_bytes_in_use`` over the devices (None on a backend
    that reports no memory stats)."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    return max(peaks) if all(p is not None for p in peaks) else None


def _shapes(args) -> str:
    import jax
    leaves = jax.tree.leaves(args)
    return " ".join(f"{tuple(a.shape)}:{a.dtype}" if hasattr(a, "shape")
                    else repr(a) for a in leaves)


def registry_phase(watch: CompileWatch, *, scale: int = SCALE,
                   serial_scales: dict = SERIAL_SCALES, seed: int = SEED,
                   **session_kw) -> list[dict]:
    """Serve every registry workload once through ``submit()`` on a session
    opened with ``session_kw`` and check it against ``ref()``; raises on the
    first mismatch.  Returns one row per workload."""
    from repro import pim
    rows = []
    with pim.session(**session_kw) as s:
        log(f"registry: session {s!r}, residency budget "
            f"{s.cache.budget_bytes} bytes")
        for name, entry in pim.registry().items():
            sc = serial_scales.get(name, scale)
            args = entry.make_args(np.random.default_rng(seed), sc)
            with Timed(watch) as t:
                out = s.submit(name, *args).result()
            entry.compare(out, entry.ref(*args))
            row = {"workload": name, "scale": sc,
                   "bytes_in": entry.arg_nbytes(args),
                   "compile_s": t.compile_s, "run_s": t.run_s}
            rows.append(row)
            log(f"  {name:6s} scale {sc:4d} {row['bytes_in']:>11d} B in "
                f"[{_shapes(args)}] {t} — matches ref()")
        cache = s.cache.stats()
    log(f"registry: {len(rows)} workloads match ref(); resident "
        f"{cache['resident_bytes']} bytes in {cache['entries']} entries; "
        f"peak_bytes_in_use {peak_bytes()}")
    return rows


def tinyllama_f32():
    """TinyLlama 1.1B at its published widths, float32 (the decode
    engine's numerics contract)."""
    import jax.numpy as jnp

    from repro.configs.tinyllama_1_1b import FULL
    return dataclasses.replace(FULL, dtype=jnp.float32)


def decode_phase(watch: CompileWatch, cfg, *, streams: int = STREAMS,
                 prompt_len: int = PROMPT_LEN, max_new: int = MAX_NEW,
                 banks: int = 1, ranks: int | None = None,
                 seed: int = SEED) -> dict:
    """Greedy-decode ``streams`` seeded prompts with a :class:`DecodeEngine`
    on ``banks`` banks (in ``ranks`` ranks) and with ``greedy_generate`` on
    as many devices; raises unless the tokens are identical."""
    import jax

    from repro.launch.serve import greedy_generate
    from repro.models import transformer
    from repro.pim.decode import DecodeEngine
    from repro.runtime.elastic import carve_mesh

    log(f"decode: {cfg.name} {cfg.n_layers} layers d_model {cfg.d_model} "
        f"heads {cfg.n_heads}/{cfg.n_kv_heads} d_ff {cfg.d_ff} vocab "
        f"{cfg.vocab} {jax.numpy.dtype(cfg.dtype).name}; {streams} streams, "
        f"prompt {prompt_len}, {max_new} new tokens")
    with Timed(watch) as t:
        params, specs = transformer.init(jax.random.PRNGKey(seed), cfg)
        prompt = jax.random.randint(jax.random.PRNGKey(seed + 1),
                                    (streams, prompt_len), 0, cfg.vocab)
        jax.block_until_ready(params)
    n_params = sum(a.size for a in jax.tree.leaves(params))
    log(f"  params {n_params} ({n_params * 4} B) init {t}")

    mesh = carve_mesh(jax.devices()[:banks], model_parallel=1)
    with Timed(watch) as t:
        ref = np.asarray(greedy_generate(params, cfg, mesh, specs, prompt,
                                         max_new=max_new))
    log(f"  greedy_generate reference on {banks} device(s) {t}")

    with Timed(watch) as t_setup:
        eng = DecodeEngine(params, cfg, banks=banks, ranks=ranks)
    with eng:
        cache = eng.session.cache.stats()
        log(f"  engine: {eng.session.n_banks} bank(s), {eng.session.n_ranks} "
            f"rank(s), {len(eng.pins)} pinned projections, resident "
            f"{cache['resident_bytes']} bytes of budget "
            f"{cache['budget_bytes']}; setup {t_setup}")
        with Timed(watch) as t:
            out = eng.generate(np.asarray(prompt), max_new)
        rep = eng.report()
        hits = eng.session.cache.stats()["hits"]
    for b in range(streams):
        log(f"  stream-{b}: {out[b].tolist()}")
    if not (out == ref).all():
        raise AssertionError(f"decode diverged from greedy_generate:\n"
                             f"engine {out.tolist()}\nref    {ref.tolist()}")
    log(f"decode: token-identical to greedy_generate across {streams} "
        f"streams; generate {t}; {rep['new_tokens']} new tokens, "
        f"{rep['steps']} steps, resident hits {hits}; peak_bytes_in_use "
        f"{peak_bytes()}")
    return {"setup_s": t_setup.wall_s, "generate_compile_s": t.compile_s,
            "generate_run_s": t.run_s, "resident_bytes":
            cache["resident_bytes"]}


def one_chip(watch: CompileWatch, *, cfg=None, scale: int = SCALE,
             serial_scales: dict = SERIAL_SCALES) -> None:
    """The default run: both phases on one bank (one chip)."""
    registry_phase(watch, scale=scale, serial_scales=serial_scales, banks=1)
    decode_phase(watch, cfg if cfg is not None else tinyllama_f32())


def four_chips(watch: CompileWatch, *, cfg=None, scale: int = SCALE,
               serial_scales: dict = SERIAL_SCALES) -> None:
    """``--chips 4``: a bank is a chip — the registry on a flat 4-bank grid
    and on a 2x2 rank x bank grid, and a ranks=2 decode engine."""
    registry_phase(watch, scale=scale, serial_scales=serial_scales, banks=4)
    registry_phase(watch, scale=scale, serial_scales=serial_scales, ranks=2,
                   banks_per_rank=2)
    decode_phase(watch, cfg if cfg is not None else tinyllama_f32(),
                 ranks=2, banks=4)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1 (default): registry + decode on one chip; 4: "
                         "the multi-bank and ranked paths only")
    args = ap.parse_args(argv)
    cache_dir = enable_compile_cache()

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {devs[0].platform!r}",
              file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devs)}", file=sys.stderr)
        return 2
    log(f"device: {devs[0].platform} {devs[0].device_kind} x{len(devs)}; "
        f"jax {jax.__version__}; compile cache {cache_dir}")
    watch = CompileWatch()
    with Timed(watch) as t:
        (one_chip if args.chips == 1 else four_chips)(watch)
    log(f"total {t}; compile cache hits {watch.hits} misses {watch.misses}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
