"""PIM-offloaded LLM decode serving (DESIGN.md §14).

The paper's central claim is that PIM wins exactly where decode lives:
memory-bound operators with low arithmetic intensity and operands that can
*stay* in the banks.  Autoregressive decode is one long stream of matvecs
against weights that never change — so each weight matrix should cross the
CPU↔DPU boundary once, at session setup, and every subsequent token should
move only its activation vector.

:class:`DecodeEngine` is that serving path, assembled from the existing
subsystems rather than beside them:

* **weight residency** — every (layer, projection) operand pytree from
  :mod:`repro.models.pim_bridge` is wrapped in one
  :class:`~repro.runtime.resident.ResidentHandle` and pinned via
  :meth:`~repro.pim.session.PimSession.pin`, so the first token is already
  warm and no step ever rehashes the weights (DESIGN.md §12);
* **rank-sharded matvecs** — the pinned GEMV-B / GEMV-G chunks are output
  *rows*; on a ranked session (``ranks=R``) the contiguous chunk blocks
  shard attention heads and FFN columns across ranks (DESIGN.md §10);
* **multi-stream serving** — streams are the unit of traffic, each with
  its own cache, tokens and latency, advancing in lockstep; a step sends
  one request a weight matrix, its vector operand a column per stream
  that needs the matrix (every stream for an attention or dense FFN
  matrix, the streams that chose it for a routed expert), so the matrix
  is read once for all of them.  The requests go to the engine's one
  tenant, where consecutive ones coalesce into a chunk-pipeline batch;
  weighted-fair dispatch orders the engine against other tenants, not
  the streams inside a step (DESIGN.md §13).  ``step_deadline_s`` stamps
  each request with a deadline for QoS experiments;
* **phase accounting** — every request is tagged ``layer=i,
  proj=q|k|v|o|up|down`` and ``streams=n`` (telemetry rows grow ``tag_*``
  columns, trace ``serve`` spans carry the labels), and each step keeps
  an independent engine-side :class:`StepRecord` of where its wall time
  went.

Host/PIM split per layer (the host math is the model's own jnp functions,
so tokens match :func:`repro.launch.serve.greedy_generate` exactly):

    host: rms_norm ─ PIM: q,k,v ─ host: rope + KV append + attention
    ─ PIM: o ─ host: residual + rms_norm ─ PIM: gate|up ─ PIM: down
    ─ host: residual    (per layer; then final norm + lm_head + argmax)

DeepSeek-V2's block (latent attention + MoE, DESIGN.md §14) splits the
same way, with the routing on the host:

    host: rms_norm ─ PIM: q, kv_a ─ host: MLA over the latent cache
    (``wkv_b`` absorbed) ─ PIM: o ─ host: residual + rms_norm + router
    (softmax, top-k, no renormalisation unless the model says so, no
    capacity) ─ PIM: gate|up of the shared and of every chosen expert ─
    PIM: their downs ─ host: residual + shared + Σ gate·expert

:meth:`DecodeEngine.prefill` builds the streams' latent caches from whole
prompts in one block forward on the device, against the same pinned
weights, so that a long context costs no per-token requests.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops as kops
from repro.models import attention, mla
from repro.models.layers import ModelConfig, rms_norm, rope
from repro.models.pim_bridge import (LayerWeights, extract_decode_weights,
                                     workload_of)
from repro.runtime.qos import RequestOptions
from repro.runtime.resident import ResidentHandle
from repro.runtime.trace import get_tracer, span

from .session import PimSession, session as open_session

#: projection label -> PrIM workload that serves it (the attention block's
#: names; every other matvec follows ``pim_bridge.workload_of``)
PROJ_WORKLOADS = {"q": "GEMV-B", "k": "GEMV-B", "v": "GEMV-B",
                  "o": "GEMV-B", "up": "GEMV-G", "down": "GEMV-B"}

#: the session tenant of every engine request
TENANT = "decode"

#: engine-measured step phases: the PIM groups + everything else ("qkv"
#: is MLA's q + kv_a; a model with MoE layers adds "experts", their shared
#: and routed experts)
PIM_GROUPS = ("qkv", "o", "up", "down")


@dataclasses.dataclass
class StepRecord:
    """Where one engine step's wall time went — measured by the engine
    around each submit→drain group and each host segment, independently of
    the telemetry rows the same step produces (the test battery checks the
    two views agree).  ``route_s`` (routing), ``attend_s`` (the attention
    host half) are parts of ``host_s``; ``experts_s`` is
    ``pim_s["experts"]``, over ``expert_requests`` requests."""

    step: int
    tokens: int              # newly *generated* tokens (0 while prefilling)
    wall_s: float
    pim_s: dict              # group (DecodeEngine.groups) -> seconds
    host_s: float
    route_s: float = 0.0
    attend_s: float = 0.0
    experts_s: float = 0.0
    requests: int = 0
    matvecs: int = 0
    expert_batches: int = 0
    expert_requests: int = 0


class _Stream:
    """One decode stream: its name, its tokens (the last one is fed
    next) and per-layer caches on the host device — ``attention.init_cache``'s
    layout, or for MLA the latent ``c`` (T, r) and rope key ``pe``
    (T, rope) with ``pos`` positions filled."""

    __slots__ = ("name", "tokens", "caches", "pos")

    def __init__(self, name: str, cfg: ModelConfig, max_len: int,
                 tokens: Sequence[int]):
        self.name = name
        self.tokens = [int(t) for t in tokens]
        self.pos = 0
        if cfg.kv_lora_rank:
            self.caches = [
                {"c": jnp.zeros((max_len, cfg.kv_lora_rank), jnp.float32),
                 "pe": jnp.zeros((max_len, cfg.qk_rope_head_dim),
                                 jnp.float32)}
                for _ in range(cfg.n_layers)]
        else:
            self.caches = [attention.init_cache(cfg, 1, max_len, jnp.float32)
                           for _ in range(cfg.n_layers)]


# -- host halves of the latent-attention / MoE block (jitted, fixed shapes) ----

@functools.partial(jax.jit, static_argnums=(0,))
def _mla_decode(cfg, q, kva, kv_norm, wkv_b, c, pe, pos):
    """One position of one stream: latent + rope key into the cache at
    ``pos``, absorbed attention over positions ``<= pos``."""
    p = jnp.reshape(pos, (1, 1))
    q_nope, q_pe, c_kv, k_pe = mla.latent(
        cfg, q.reshape(1, 1, -1), kva.reshape(1, 1, -1), kv_norm, p)
    c = jax.lax.dynamic_update_slice(c, c_kv[0], (pos, 0))
    pe = jax.lax.dynamic_update_slice(pe, k_pe[0], (pos, 0))
    o = mla.attend_latent(cfg, q_nope[:, :, 0], q_pe[:, :, 0], c[None],
                          pe[None], p[0] + 1, wkv_b)
    return o[0], c, pe


@functools.partial(jax.jit, static_argnums=(0,))
def _route(k, h, router):
    """Softmax over the experts, top-k: (gates (B, k), experts (B, k))."""
    probs = jax.nn.softmax(h.astype(jnp.float32) @ router, axis=-1)
    return jax.lax.top_k(probs, k)


@jax.jit
def _combine(x, shared, ys, gates):
    """x + shared + Σ_e gate_e · y_e for one stream."""
    return x + (shared + gates @ ys).reshape(x.shape)


# -- block prefill on the device, against the pinned row chunks ----------------

def _mv_rows(x, chunks, m):
    """x (S, d_in) against a GEMV-B operand's row chunks [(w (1, per, d_in),
    b (1, per, 1))]: (S, m)."""
    y = jnp.concatenate([x @ w[0].T + b[0].T for w, b in chunks], -1)
    return y[:, :m]


def _swiglu_rows(x, up, down, m_up, m_down):
    g = jnp.concatenate([x @ wg[0].T for wg, _ in up], -1)[:, :m_up]
    u = jnp.concatenate([x @ wu[0].T for _, wu in up], -1)[:, :m_up]
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    return _mv_rows(h, down, m_down)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _prefill_attn(cfg, ms, x, w, cache):
    """x (P, d) + MLA over the block; the block's latents into the cache's
    first P rows."""
    P = x.shape[0]
    h = rms_norm(x, w["norm1"], cfg.norm_eps)
    q = _mv_rows(h, w["q"], ms[0])
    kva = _mv_rows(h, w["kv_a"], ms[1])
    q_nope, q_pe, c_kv, k_pe = mla.latent(
        cfg, q[None], kva[None], w["kv_norm"], jnp.arange(P)[None])
    o = mla.attend_block(cfg, q_nope, q_pe, c_kv, k_pe, w["wkv_b"])[0]
    cache = {"c": jax.lax.dynamic_update_slice(cache["c"], c_kv[0], (0, 0)),
             "pe": jax.lax.dynamic_update_slice(cache["pe"], k_pe[0],
                                                (0, 0))}
    return x + _mv_rows(o, w["o"], ms[2]), cache


@functools.partial(jax.jit, static_argnums=(0, 1))
def _prefill_dense(cfg, ms, x, w):
    h = rms_norm(x, w["norm2"], cfg.norm_eps)
    return x + _swiglu_rows(h, w["up"], w["down"], *ms)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _prefill_moe(cfg, ms, x, w):
    """Shared experts added, and the routing of every position."""
    h = rms_norm(x, w["norm2"], cfg.norm_eps)
    gates, idx = _route(cfg.moe_top_k, h, w["router"])
    return x + _swiglu_rows(h, w["up"], w["down"], *ms), h, gates, idx


@functools.partial(jax.jit, static_argnums=(0,))
def _prefill_expert(ms, x, h, rows, gates, up, down):
    """One routed expert over the positions ``rows`` (padded with gate 0)."""
    y = _swiglu_rows(h[rows], up, down, *ms)
    return x.at[rows].add(gates[:, None] * y)


def _bucket(n: int, least: int = 16) -> int:
    """The power of two >= n (and >= least): one compile per bucket."""
    return max(least, 1 << max(0, n - 1).bit_length())


class DecodeEngine:
    """Continuous multi-stream greedy decode with session-resident weights.

    ``session=`` reuses an open :class:`PimSession` (it must allow
    residency for pinning); otherwise the engine opens its own from
    ``banks=``/``ranks=``/``n_chunks=`` and closes it with :meth:`close`.
    ``pin=False`` skips the setup-time placement — the cold baseline the
    decode bench leg measures (with ``resident=False`` on the session,
    every step re-scatters every weight).
    """

    def __init__(self, params, cfg: ModelConfig, *,
                 session: PimSession | None = None,
                 banks: int | None = None, ranks: int | None = None,
                 n_chunks: int = 2, resident: bool = True, pin: bool = True,
                 step_deadline_s: float | None = None):
        self.cfg = cfg
        self.layers: list[LayerWeights] = extract_decode_weights(params, cfg)
        self.groups = PIM_GROUPS + (
            ("experts",) if any(lw.n_experts for lw in self.layers) else ())
        self._own = session is None
        if session is None:
            session = open_session(banks=banks, ranks=ranks,
                                   n_chunks=n_chunks, resident=resident)
        self.session = session
        self.step_deadline_s = step_deadline_s
        self.steps: list[StepRecord] = []
        self.streams: list[_Stream] = []
        #: logits (B, V) of the last step, on the host device
        self.last_logits = None
        # one handle per (layer, proj): the digest is computed once here;
        # every submit and the pin below reuse it (no per-step rehash)
        self.handles: dict[tuple[int, str], ResidentHandle] = {
            (li, proj): ResidentHandle(op)
            for li, lw in enumerate(self.layers)
            for proj, op in lw.mats.items()}
        self.pins: list[str] = []
        self.pinned: dict[tuple[int, str], str] = {}
        self.setup_s = 0.0
        if pin and session.cache is not None:
            t0 = time.perf_counter()
            for key, handle in self.handles.items():
                x = np.zeros(self._in_dim(key), np.float32)
                fp = session.pin(workload_of(key[1]), handle, x)
                self.pins.append(fp)
                self.pinned[key] = fp
            self.setup_s = time.perf_counter() - t0
        # the host half's weights, on the host device
        self.embed = jnp.asarray(params["embed"])
        self.final_norm = jnp.asarray(params["final_norm"])
        self.lm_head = jnp.asarray(params["lm_head"])
        self.host = [{k: jnp.asarray(v) for k, v in lw.host.items()}
                     for lw in self.layers]

    def _in_dim(self, key) -> int:
        op = self.handles[key].value
        return (op["w"] if "w" in op else op["wg"]).shape[1]

    def _rows(self, li: int, proj: str) -> int:
        op = self.handles[(li, proj)].value
        return (op["w"] if "w" in op else op["wg"]).shape[0]

    # -- one group of matvecs, one request a matrix ---------------------------

    def _group(self, li: int, jobs: Sequence[tuple[str, Sequence]],
               width: int, rec: StepRecord) -> tuple[list, float, list]:
        """Submit one request per job ``(proj, vectors)`` of layer ``li``,
        the vectors stacked as columns and padded with zero columns to
        ``width`` (the step's stream count, so that each matrix compiles
        at one shape), run the group to completion, and return (per job
        the result vectors in the order of its vectors, group wall
        seconds, the requests).
        Consecutive requests of one workload coalesce into one
        chunk-pipeline batch (one tenant: the engine's)."""
        t0 = time.perf_counter()
        reqs = []
        for proj, vecs in jobs:
            x = np.zeros((len(vecs[0]), width), np.float32)
            x[:, :len(vecs)] = np.stack(vecs, 1)
            opts = RequestOptions(tenant=TENANT,
                                  deadline_s=self.step_deadline_s,
                                  tags={"layer": li, "proj": proj,
                                        "streams": len(vecs)})
            reqs.append(self.session.submit(
                workload_of(proj), self.handles[(li, proj)], x,
                options=opts))
        if not self.session.serving:
            self.session.drain()
        out = []
        for r, (_, vecs) in zip(reqs, jobs):
            y = r.result()
            out.append([y[:, j] for j in range(len(vecs))])
        rec.requests += len(reqs)
        rec.matvecs += sum(len(v) for _, v in jobs)
        return out, time.perf_counter() - t0, reqs

    # -- attention host halves --------------------------------------------------

    def _attend(self, stream: _Stream, li: int, qv, kv, vv) -> np.ndarray:
        """Host half of the attention block for one stream: rope, KV append
        at the cache cursor, softmax attention — byte-for-byte the math of
        ``attention.decode``, with the three projections supplied."""
        cfg = self.cfg
        H, KVH, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
        cache = stream.caches[li]
        q = jnp.asarray(qv).reshape(1, 1, H, hd).transpose(0, 2, 1, 3)
        k = jnp.asarray(kv).reshape(1, 1, KVH, hd).transpose(0, 2, 1, 3)
        v = jnp.asarray(vv).reshape(1, 1, KVH, hd).transpose(0, 2, 1, 3)
        positions = cache["len"][:, None]
        q = rope(q, positions[:, None, :], cfg.rope_theta)
        k = rope(k, positions[:, None, :], cfg.rope_theta)
        idx = cache["len"][0]
        kc = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, 0, idx, 0))
        vc = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, 0, idx, 0))
        lengths = cache["len"] + 1
        o = kops.decode_attention(
            q, kc, vc, lengths, window=cfg.window,
            impl="grouped" if cfg.fast_decode else "ref")
        stream.caches[li] = {"k": kc, "v": vc, "len": lengths}
        return np.asarray(o.transpose(0, 2, 1, 3).reshape(-1), np.float32)

    def _attend_mla(self, stream: _Stream, li: int, qv, kvav) -> np.ndarray:
        """Host half of latent attention for one stream at its position."""
        w, cache = self.host[li], stream.caches[li]
        o, cache["c"], cache["pe"] = _mla_decode(
            self.cfg, jnp.asarray(qv), jnp.asarray(kvav), w["kv_norm"],
            w["wkv_b"], cache["c"], cache["pe"], stream.pos)
        return np.asarray(o, np.float32)

    # -- one step: every stream advances one token -----------------------------

    def _step(self, streams: Sequence[_Stream], toks: np.ndarray,
              step: int, generated: bool) -> np.ndarray:
        """Advance every stream one position on input tokens ``toks``
        ((B,) int32); returns next tokens (B,) int32 by greedy argmax and
        appends this step's :class:`StepRecord`."""
        cfg = self.cfg
        d = cfg.d_model
        eps = cfg.norm_eps
        t0 = time.perf_counter()
        rec = StepRecord(step=step, tokens=len(streams) if generated else 0,
                         wall_s=0.0, pim_s=dict.fromkeys(self.groups, 0.0),
                         host_s=0.0)
        pim_s = rec.pim_s
        B = len(streams)

        th = time.perf_counter()
        xs = [self.embed[jnp.asarray(t).reshape(1, 1)]
              for t in toks]                                # (1, 1, d) each
        rec.host_s += time.perf_counter() - th

        for li, lw in enumerate(self.layers):
            w = self.host[li]
            th = time.perf_counter()
            hv = [np.asarray(rms_norm(x, w["norm1"], eps)).reshape(-1)
                  for x in xs]
            rec.host_s += time.perf_counter() - th

            if cfg.kv_lora_rank:
                qkv, dt, _ = self._group(li, [("q", hv), ("kv_a", hv)], B,
                                         rec)
                pim_s["qkv"] += dt
                th = time.perf_counter()
                with span("mla", "session", layer=li):
                    ov = [self._attend_mla(s, li, *r)
                          for s, *r in zip(streams, *qkv)]
                dt = time.perf_counter() - th
                rec.attend_s += dt
            else:
                qkv, dt, _ = self._group(li, [("q", hv), ("k", hv),
                                              ("v", hv)], B, rec)
                pim_s["qkv"] += dt
                th = time.perf_counter()
                ov = [self._attend(s, li, *r)
                      for s, *r in zip(streams, *qkv)]
                dt = time.perf_counter() - th
            rec.host_s += dt

            (mo,), dt, _ = self._group(li, [("o", ov)], B, rec)
            pim_s["o"] += dt

            th = time.perf_counter()
            xs = [x + jnp.asarray(m).reshape(1, 1, d)
                  for x, m in zip(xs, mo)]
            h2 = [np.asarray(rms_norm(x, w["norm2"], eps)).reshape(-1)
                  for x in xs]
            rec.host_s += time.perf_counter() - th

            if lw.n_experts:
                xs = self._moe(li, xs, h2, rec)
                continue
            (hidden,), dt, _ = self._group(li, [("up", h2)], B, rec)
            pim_s["up"] += dt
            (down,), dt, _ = self._group(li, [("down", hidden)], B, rec)
            pim_s["down"] += dt

            th = time.perf_counter()
            xs = [x + jnp.asarray(dn).reshape(1, 1, d)
                  for x, dn in zip(xs, down)]
            rec.host_s += time.perf_counter() - th

        th = time.perf_counter()
        h = rms_norm(jnp.concatenate(xs, 0), self.final_norm, eps)
        logits = (h @ self.lm_head)[:, -1, :]               # (B, V)
        nxt = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
        self.last_logits = logits
        rec.host_s += time.perf_counter() - th

        rec.wall_s = time.perf_counter() - t0
        rec.experts_s = pim_s.get("experts", 0.0)
        self.steps.append(rec)
        tr = get_tracer()
        if tr.enabled:
            tr.emit("decode_step", "session", t0, t0 + rec.wall_s,
                    track="decode", step=step, streams=len(streams),
                    generated=int(generated))
        return nxt

    def _moe(self, li: int, xs, h2, rec: StepRecord) -> list:
        """Route on the host, then one gate|up request for the shared
        experts over every stream and one for each chosen expert over the
        streams that chose it, then their downs; each stream sums its
        experts in its own top-k order."""
        cfg, w = self.cfg, self.host[li]
        B = len(h2)
        th = time.perf_counter()
        with span("route", "session", layer=li):
            gates, idx = _route(cfg.moe_top_k, jnp.asarray(np.stack(h2)),
                                w["router"])
            gates, idx = np.asarray(gates), np.asarray(idx)
        dt = time.perf_counter() - th
        rec.route_s += dt
        rec.host_s += dt

        th = time.perf_counter()
        experts = np.unique(idx)
        # the streams of each chosen expert, in stream order
        users = [np.flatnonzero((idx == e).any(1)) for e in experts]
        matvecs = 2 * (B + sum(len(u) for u in users))
        with span("experts", "session", layer=li, experts=len(experts),
                  matvecs=matvecs) as sp:
            ups, _, r1 = self._group(
                li, [("shared.up", h2)] + [
                    (f"e{e}.up", [h2[b] for b in u])
                    for e, u in zip(experts, users)], B, rec)
            downs, _, r2 = self._group(
                li, [("shared.down", ups[0])] + [
                    (f"e{e}.down", y) for e, y in zip(experts, ups[1:])],
                B, rec)
            first = getattr(r1[0], "record", None)
            sp.tag(req=first and first.request_id,
                   requests=len(r1) + len(r2))
        dt = time.perf_counter() - th
        rec.pim_s["experts"] += dt
        rec.expert_batches += len(r1) + len(r2)
        rec.expert_requests += matvecs

        th = time.perf_counter()
        # stream b's output of expert e: its place among e's streams
        out = {(e, b): y for e, u, ys in zip(experts, users, downs[1:])
               for b, y in zip(u, ys)}
        xs = [_combine(x, jnp.asarray(downs[0][b]),
                       jnp.asarray(np.stack([out[e, b] for e in idx[b]])),
                       jnp.asarray(gates[b]))
              for b, x in enumerate(xs)]
        rec.host_s += time.perf_counter() - th
        return xs

    # -- public API ------------------------------------------------------------

    def generate(self, prompts, max_new: int) -> np.ndarray:
        """Greedy-decode ``max_new`` tokens per stream after teacher-forced
        token-by-token prefill — the exact schedule of
        :func:`repro.launch.serve.greedy_generate`, so outputs are
        token-identical on the same params/prompt.  ``prompts`` is (B, S)
        int32; returns (B, S + max_new) int32."""
        prompts = np.asarray(prompts, np.int32)
        B, S = prompts.shape
        streams = [_Stream(f"stream-{b}", self.cfg, S + max_new,
                           prompts[b, :1]) for b in range(B)]
        toks = prompts[:, 0]
        # host math at the GEMV phases' full float32 (prim.common.PRECISION)
        # and the reference's: a TPU's default runs bfloat16 passes
        with jax.default_matmul_precision("highest"):
            for i in range(S + max_new - 1):
                nxt = self._step(streams, toks, step=i,
                                 generated=i + 1 >= S)
                toks = prompts[:, i + 1] if i + 1 < S else nxt
                for s, t in zip(streams, toks):
                    s.tokens.append(int(t))
                    s.pos += 1
        return np.asarray([s.tokens for s in streams], np.int32)

    def prefill(self, prompts: Sequence[Sequence[int]], max_len: int,
                block: int | None = None) -> None:
        """Open one stream per prompt and build its cache from every token
        of the prompt but the last in one block forward on the device —
        the engine's layer math against the pinned weights, each token
        through all of its top-k experts — so that the next :meth:`step`
        feeds each prompt's last token.  Prompts are padded to ``block``
        positions (default: the power of two over the longest), so that
        one compile serves prompts of every length; ``max_len`` bounds
        prompt plus generated tokens.  Latent-attention models only."""
        cfg = self.cfg
        if not cfg.kv_lora_rank:
            raise ValueError("prefill() builds latent caches: "
                             f"{cfg.name} has no latent attention")
        prompts = [np.asarray(p, np.int32) for p in prompts]
        n_pre = [len(p) - 1 for p in prompts]
        P = block or _bucket(max(n_pre))
        if max(n_pre) > P or P > max_len or min(n_pre) < 1:
            raise ValueError(f"prompts of {min(n_pre) + 1}..{max(n_pre) + 1}"
                             f" tokens do not fit block {P} / max_len "
                             f"{max_len}")
        base = len(self.streams)
        streams = [_Stream(f"stream-{base + b}", cfg, max_len, p)
                   for b, p in enumerate(prompts)]
        with span("prefill", "session", requests=len(streams)), \
                jax.default_matmul_precision("highest"):
            xs = [self.embed[jnp.asarray(np.pad(p[:n], (0, P - n)))]
                  for p, n in zip(prompts, n_pre)]
            for li, lw in enumerate(self.layers):
                w = self._prefill_weights(li, lw)
                ms = tuple(self._rows(li, k) for k in ("q", "kv_a", "o"))
                for b, s in enumerate(streams):
                    xs[b], s.caches[li] = _prefill_attn(
                        cfg, ms, xs[b], w, s.caches[li])
                if not lw.n_experts:
                    ms = (self._rows(li, "up"), self._rows(li, "down"))
                    xs = [_prefill_dense(cfg, ms, x, w) for x in xs]
                    continue
                ms = (self._rows(li, "shared.up"),
                      self._rows(li, "shared.down"))
                xs = [self._prefill_experts(li, lw, w, ms, x, n)
                      for x, n in zip(xs, n_pre)]
            jax.block_until_ready(xs)
        for s, n in zip(streams, n_pre):
            s.pos = n
        self.streams += streams

    def _prefill_weights(self, li: int, lw: LayerWeights) -> dict:
        """Layer ``li``'s host arrays and the row chunks of its attention,
        dense-FFN and shared-expert matvecs, as the device holds them."""
        w = dict(self.host[li])
        for proj in lw.mats:
            if not proj.startswith("e"):
                w[proj.removeprefix("shared.")] = self._device_rows(li, proj)
        return w

    def _device_rows(self, li: int, proj: str) -> list:
        """Matvec ``proj``'s row chunks on the device: the pinned entry's
        buffers where one bank holds them all, else the host operand put
        on the device as one chunk."""
        fp = self.pinned.get((li, proj))
        ent = (self.session.cache.lookup(fp)
               if fp is not None and self.session.n_banks == 1 else None)
        if ent is not None and ent.ready:
            return [ent.get(g) for g in range(ent.expected_chunks)]
        op = self.handles[(li, proj)].value
        a, b = (op["w"], op["b"]) if "w" in op else (op["wg"], op["wu"])
        return [(jnp.asarray(a)[None], jnp.asarray(b)[None])]

    def _prefill_experts(self, li, lw, w, ms, x, n):
        """An MoE layer's FFN over one stream's block: the shared experts,
        then each routed expert over the real positions routed to it."""
        x, h, gates, idx = _prefill_moe(self.cfg, ms, x, w)
        idx, gates = np.asarray(idx)[:n], np.asarray(gates)[:n]
        k = idx.shape[1]
        tok = np.repeat(np.arange(n, dtype=np.int32), k)
        ex, gw = idx.reshape(-1), gates.reshape(-1)
        order = np.argsort(ex, kind="stable")
        ends = np.cumsum(np.bincount(ex, minlength=lw.n_experts))
        ems = (self._rows(li, "e0.up"), self._rows(li, "e0.down"))
        for e in range(lw.n_experts):
            sel = order[ends[e - 1] if e else 0:ends[e]]
            if not len(sel):
                continue
            m = _bucket(len(sel))
            rows = np.zeros(m, np.int32)
            g = np.zeros(m, np.float32)
            rows[:len(sel)], g[:len(sel)] = tok[sel], gw[sel]
            x = _prefill_expert(
                ems, x, h, jnp.asarray(rows), jnp.asarray(g),
                self._device_rows(li, f"e{e}.up"),
                self._device_rows(li, f"e{e}.down"))
        return x

    def step(self) -> np.ndarray:
        """Every open stream (:meth:`prefill`) advances one token: each
        feeds its last token and appends its greedy next one, returned as
        (B,) int32; the step's logits stay in :attr:`last_logits`."""
        streams = self.streams
        toks = np.asarray([s.tokens[-1] for s in streams], np.int32)
        with jax.default_matmul_precision("highest"):
            nxt = self._step(streams, toks, step=len(self.steps),
                             generated=True)
        for s, t in zip(streams, nxt):
            s.tokens.append(int(t))
            s.pos += 1
        return nxt

    def report(self) -> dict:
        """Serving metrics over every step so far: tokens/sec and
        time-per-output-token over the *generation* steps (prefill and
        setup reported separately), plus the engine-side phase breakdown
        (summed :class:`StepRecord` buckets)."""
        gen = [s for s in self.steps if s.tokens]
        pre = [s for s in self.steps if not s.tokens]
        gen_wall = sum(s.wall_s for s in gen)
        new_tokens = sum(s.tokens for s in gen)
        pim_s = dict.fromkeys(self.groups, 0.0)
        for s in self.steps:
            for k, v in s.pim_s.items():
                pim_s[k] += v
        return {
            "steps": len(self.steps),
            "new_tokens": new_tokens,
            "tokens_per_s": (new_tokens / gen_wall) if gen_wall else 0.0,
            "time_per_output_token_s": (gen_wall / new_tokens)
            if new_tokens else 0.0,
            "prefill_s": sum(s.wall_s for s in pre),
            "generate_s": gen_wall,
            "setup_s": self.setup_s,
            "host_s": sum(s.host_s for s in self.steps),
            "pim_s": pim_s,
        }

    def proj_seconds(self) -> dict[tuple[int, str], float]:
        """(layer, proj) -> summed telemetry service seconds, grouped from
        the tagged request rows — the telemetry-side view the test battery
        reconciles against the engine-side :class:`StepRecord` buckets."""
        out: dict[tuple[int, str], float] = {}
        for rec in list(self.session.telemetry.records):
            proj = rec.tags.get("proj")
            if proj is None:
                continue
            key = (rec.tags.get("layer"), proj)
            out[key] = out.get(key, 0.0) + max(0.0, rec.t_finish
                                               - rec.t_start)
        return out

    def close(self) -> None:
        """Release the engine's session if it owns one (unpins and frees
        the resident weights); a shared session is left untouched."""
        if self._own and not self.session.closed:
            self.session.close()

    def __enter__(self) -> "DecodeEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
