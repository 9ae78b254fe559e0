"""Closed-loop, lockstep greedy decode of DeepSeek-V2 streams through the
program's ``DecodeEngine`` on one ``pim.session``.

Traffic parameters (``bench/traffic/<mix>.json``):

* ``streams``        decode streams, one tenant each, advancing in lockstep:
  a step feeds every stream's last token and yields its next one;
* ``context_min``, ``context_max``  each stream's context length, drawn
  log-uniform between them from the seed, its token ids uniform over the
  vocabulary; block-prefilled in set-up, padded to ``context_max``
  positions so that one compile serves every length;
* ``max_new``        positions of cache beyond the context (the window ends
  early should a stream reach them);
* ``warm_steps``     decode steps in set-up, so that nothing compiles in
  the window;
* ``checked_streams``, ``checked_steps``  streams drawn from the seed whose
  logits at the last steps are checked against the reference.

Configuration (``bench/configs/<config>.json``): the model's published
``config.json`` keys, as run (``from_hf`` of the program's config module
reads them), ``session`` for ``pim.session``.  Weights are drawn on the host
from the seed, each matrix row-major in the layout the engine pins, so that
the program's parameter tree is a set of views and every weight lives once
in host memory and once on the device.

Each stream's next token is one request: its latency runs from the step's
start to the step's end, and the rate counts the tokens of the steps that
ended inside the window.  After the window the session is closed and the
reference runs on the device, one layer at a time from the host copies,
over each checked stream's tokens; ``logit_err`` is the largest
|engine - reference| logit over the largest |reference| logit at the last
``checked_steps`` steps.
"""
from __future__ import annotations

import collections
import concurrent.futures
import gc
import math
import os
import time
import zlib

import numpy as np

import harness
from harness import annotate

#: pieces each weight is drawn in, whatever the machine's core count, so
#: the same seed gives the same weights everywhere
PIECES = 16


def _seed_ints(seed: int, *more: int) -> list:
    return [seed % (1 << 63), *more]


class _Weights:
    """Seeded float32 weights, uniform with variance 1 / fan_in, drawn in
    :data:`PIECES` pieces on a thread pool."""

    def __init__(self, seed: int):
        self.seed = seed
        self.pool = concurrent.futures.ThreadPoolExecutor(
            min(PIECES, os.cpu_count() or 1))

    def draw(self, name: str, shape: tuple, fan_in: int) -> np.ndarray:
        out = np.empty(shape, np.float32)
        flat = out.reshape(-1)
        per = -(-flat.size // PIECES)
        half = math.sqrt(3.0 / fan_in)
        tag = zlib.crc32(name.encode())

        def fill(i):
            part = flat[i * per:(i + 1) * per]
            if part.size:
                rng = np.random.default_rng(_seed_ints(self.seed, tag, i))
                rng.random(out=part, dtype=np.float32)
                part *= np.float32(2 * half)
                part -= np.float32(half)
        list(self.pool.map(fill, range(PIECES)))
        return out

    def rows(self, name: str, d_out: int, d_in: int, lead=()) -> np.ndarray:
        """A (lead, d_in, d_out) activations-on-the-left weight that is the
        transposed view of a row-major (lead, d_out, d_in) matrix."""
        return np.swapaxes(self.draw(name, (*lead, d_out, d_in), d_in),
                           -1, -2)


def make_params(cfg, seed: int) -> dict:
    """The program's parameter tree (``transformer.init``'s layout) of a
    DeepSeek-V2 config with a leading dense layer, as numpy views."""
    w = _Weights(seed)
    d, H, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    qd = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    R = cfg.n_layers - 1
    one = np.float32(1.0)

    def mixer(tag, lead):
        return {"wq": w.rows(tag + "wq", H * qd, d, lead),
                "wkv_a": w.rows(tag + "wkv_a", r + cfg.qk_rope_head_dim, d,
                                lead),
                "kv_norm": np.full((*lead, r), one),
                "wkv_b": w.draw(tag + "wkv_b",
                                (*lead, r, H * (cfg.qk_nope_head_dim
                                                + cfg.v_head_dim)), r),
                "wo": w.rows(tag + "wo", d, H * cfg.v_head_dim, lead)}

    def swiglu(tag, f, lead):
        return {"wi": w.rows(tag + "wi", 2 * f, d, lead),
                "wo": w.rows(tag + "wo", d, f, lead)}

    dense = {"norm1": np.full(d, one), "norm2": np.full(d, one),
             "mixer": mixer("l0.", ()), "ffn": swiglu("l0.", cfg.dense_ff,
                                                      ())}
    ffn = swiglu("moe.", cfg.d_ff, (R, cfg.moe_experts))
    ffn["router"] = w.draw("moe.router", (R, d, cfg.moe_experts), d)
    ffn["shared"] = swiglu("moe.shared.", cfg.d_ff * cfg.moe_shared_experts,
                           (R,))
    moe = {"norm1": np.full((R, d), one), "norm2": np.full((R, d), one),
           "mixer": mixer("moe.", (R,)), "ffn": ffn}
    params = {"embed": w.draw("embed", (cfg.vocab, d), cfg.vocab),
              "final_norm": np.full(d, one),
              "lm_head": w.draw("lm_head", (d, cfg.vocab), d),
              "prologue": [dense], "group": [moe]}
    w.pool.shutdown()
    return params


def contexts(ctx: harness.Context, vocab: int) -> list:
    """Each stream's context: a length log-uniform over [context_min,
    context_max] and token ids uniform over the vocabulary."""
    tr = ctx.cell.traffic
    lo, hi = int(tr["context_min"]), int(tr["context_max"])
    rng = np.random.default_rng(_seed_ints(ctx.seed, 21))
    out = []
    for _ in range(int(tr["streams"])):
        n = int(round(math.exp(rng.uniform(math.log(lo), math.log(hi)))))
        out.append(rng.integers(0, vocab, min(max(n, lo), hi),
                                dtype=np.int32))
    return out


def run(ctx: harness.Context) -> harness.Run:
    # the program's model code first: a program without it fails here, at
    # once, before any weight is drawn
    from repro import pim
    from repro.configs.deepseek_v2_lite import from_hf, reference_params
    from repro.pim.decode import DecodeEngine

    conf, tr = ctx.cell.config, ctx.cell.traffic
    cfg = from_hf(conf)
    n_check = int(tr["checked_streams"])
    n_steps = int(tr["checked_steps"])
    max_len = int(tr["context_max"]) + int(tr["max_new"])
    with annotate("bench.setup"):
        session = pim.session(**conf["session"], autotune=False).start()
        params = make_params(cfg, ctx.seed)
        t = time.perf_counter()
        eng = DecodeEngine(params, cfg, session=session)
        ctx.log(f"weights hashed and pinned in "
                f"{time.perf_counter() - t:.1f} s ({len(eng.pins)} handles)")
        prompts = contexts(ctx, cfg.vocab)
        checked = sorted(np.random.default_rng(_seed_ints(ctx.seed, 23))
                         .choice(len(prompts), n_check, replace=False))
        t = time.perf_counter()
        eng.prefill(prompts, max_len=max_len,
                    block=int(tr["context_max"]))
        ctx.log(f"prefill of {[len(p) for p in prompts]} tokens in "
                f"{time.perf_counter() - t:.1f} s")
        kept = collections.deque(maxlen=n_steps)
        for _ in range(int(tr["warm_steps"])):
            eng.step()
            kept.append(np.asarray(eng.last_logits[np.asarray(checked)]))
    n_warm = len(eng.steps)
    n_before = len(session.telemetry.records)
    res = harness.Run()
    res.setup_s = time.perf_counter() - ctx.t_process
    ctx.log(f"setup {res.setup_s:.3f} s")

    spans = []
    win = harness.Window(ctx)
    with win:
        end = win.t0 + ctx.seconds
        while True:
            ts = time.perf_counter()
            if ts >= end or max(len(s.tokens) for s in eng.streams) \
                    >= max_len:
                break
            with annotate("client.decode"):
                eng.step()
            spans.append((ts, time.perf_counter()))
            kept.append(np.asarray(eng.last_logits[np.asarray(checked)]))
    B = len(eng.streams)
    res.window_s = ctx.seconds
    res.attempted = B * len(spans)
    res.latencies_s = [te - ts for ts, te in spans for _ in range(B)]
    res.completed_in_window = B * sum(1 for _, te in spans if te <= end)
    res.compiles_in_window = win.compiles
    recs = list(session.telemetry.records)[n_before:]
    res.records = [r for r in recs if win.t0 <= r.t_submit <= win.t1]
    res.facts = {
        "n_chunks": session.scheduler.n_chunks, "n_banks": session.n_banks,
        "steps": [dict(vars(s)) for s in eng.steps[n_warm:]],
        "matvec_shapes": {p: list((h.value["w"] if "w" in h.value
                                   else h.value["wg"]).shape)
                          for (_, p), h in eng.handles.items()},
        "contexts": [len(p) for p in prompts]}
    res.trace = win.reduce()
    res.memory_peak_bytes = harness.memory_peak_bytes(ctx.devices)
    steps = res.facts["steps"]
    ctx.log(f"window: {len(spans)} steps, {res.completed_in_window} tokens "
            f"in {ctx.seconds} s; compiles in window "
            f"{res.compiles_in_window}; step s "
            f"{[round(te - ts, 3) for ts, te in spans]}; experts s/step "
            f"{np.mean([s['experts_s'] for s in steps]) if steps else 0:.3f}"
            f", expert requests/step "
            f"{np.mean([s['expert_requests'] for s in steps]) if steps else 0}")
    seqs = [eng.streams[b].tokens[:-1] for b in checked]
    session.close()
    del eng
    gc.collect()
    res.checks = check(ctx, reference_params(params, cfg), seqs, list(kept),
                       max_len)
    return res


def check(ctx: harness.Context, params, seqs, got, max_len) -> list:
    """The reference over each checked stream's tokens, against the
    engine's logits at its last steps.  Each sequence is padded to
    ``max_len`` tokens (causal attention: the padding changes no earlier
    logit), so the reference's shapes, and its compiles, are the same in
    every run."""
    if not got:
        return [harness.Check("logit_err", float("inf"),
                              ctx.limit("logit_err"))]
    ref = ctx.reference()
    t = time.perf_counter()
    k = len(got)
    want = ref.forward(ctx.cell.config, params,
                       [np.pad(s, (0, max_len - len(s))) for s in seqs],
                       [np.arange(len(s) - k, len(s)) for s in seqs])
    errs = []
    for b, w in enumerate(want):
        e = np.stack([g[b] for g in got])
        errs.append(float(np.abs(e - w).max() / np.abs(w).max()))
        ctx.log(f"checked stream {b}: {len(seqs[b])} tokens, logit_err "
                f"{errs[-1]:.3e}, greedy tokens agree "
                f"{bool((e.argmax(-1) == w.argmax(-1)).all())}")
    ctx.log(f"reference in {time.perf_counter() - t:.1f} s")
    return [harness.Check("logit_err", max(errs), ctx.limit("logit_err"))]
