"""Mixture-of-Experts layer: top-k routing, sort-based capacity dispatch,
shared experts (DeepSeek/Kimi style), expert-parallel sharding.

Dispatch is sort-based (no T×E one-hot): tokens' (token, expert) pairs are
ranked within their expert via a segment-count, bucketed into an (E, C, d)
capacity layout (over-capacity pairs drop — standard GShard semantics),
expert-matmul'ed (einsum or the moe_gmm Pallas kernel), and combined with the
router weights.  Experts are sharded over "model" (EP); the (tokens→experts)
re-layout is the framework's canonical all-to-all exchange phase.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import ops
from .layers import ModelConfig, dense_init, emb_axis


def init(key, cfg: ModelConfig):
    d, f, E = cfg.d_model, cfg.d_ff, cfg.moe_experts
    ks = jax.random.split(key, 4)
    e = emb_axis(cfg.fsdp)
    params = {
        "router": dense_init(ks[0], (d, E), jnp.float32),
        "wi": dense_init(ks[1], (E, d, 2 * f), cfg.dtype, in_axis=1),
        "wo": dense_init(ks[2], (E, f, d), cfg.dtype, in_axis=1),
    }
    specs = {"router": P(e, None),
             "wi": P("model", e, None), "wo": P("model", None, e)}
    if cfg.moe_shared_experts:
        fs = f * cfg.moe_shared_experts
        k1, k2 = jax.random.split(ks[3])
        params["shared"] = {"wi": dense_init(k1, (d, 2 * fs), cfg.dtype),
                            "wo": dense_init(k2, (fs, d), cfg.dtype)}
        specs["shared"] = {"wi": P(e, "model"), "wo": P("model", e)}
    return params, specs


def _capacity(cfg: ModelConfig, n_tokens: int) -> int:
    if cfg.moe_capacity_factor <= 0:        # dropless: one expert may
        return max(8, -(-n_tokens // 8) * 8)  # take every token
    c = int(cfg.moe_capacity_factor * n_tokens * cfg.moe_top_k
            / cfg.moe_experts)
    return max(8, -(-c // 8) * 8)


def _norm_gates(cfg: ModelConfig, gate):
    """The top-k softmax gates, renormalised to sum to 1 where the model
    says so (``moe_norm_topk``; DeepSeek-V2 and DeepSeekMoE do not)."""
    if not cfg.moe_norm_topk:
        return gate
    return gate / jnp.clip(gate.sum(-1, keepdims=True), 1e-9)


def apply(p, cfg: ModelConfig, x, *, use_kernel: bool = False):
    """x: (B, S, d) → (B, S, d).  Aux losses returned separately."""
    B, S, d = x.shape
    E, K = cfg.moe_experts, cfg.moe_top_k
    T = B * S
    xt = x.reshape(T, d)
    C = _capacity(cfg, T)

    logits = (xt.astype(jnp.float32) @ p["router"])          # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, topk = jax.lax.top_k(probs, K)                     # (T, K)
    gate = _norm_gates(cfg, gate).astype(x.dtype)

    # sort-based rank-in-expert
    ef = topk.reshape(-1)                                    # (T*K,)
    order = jnp.argsort(ef)
    sorted_e = ef[order]
    counts = jax.ops.segment_sum(jnp.ones_like(ef), ef, num_segments=E)
    starts = jnp.cumsum(counts) - counts                     # (E,)
    rank_sorted = jnp.arange(T * K) - starts[sorted_e]
    rank = jnp.zeros_like(rank_sorted).at[order].set(rank_sorted)  # (T*K,)

    slot = jnp.where(rank < C, ef * C + rank, E * C)         # drop over-cap
    tok = jnp.repeat(jnp.arange(T), K)
    xg = jnp.zeros((E * C, d), x.dtype).at[slot].set(xt[tok], mode="drop")
    if cfg.moe_dispatch_sharded:
        # §Perf ``moe_shard``: the flattened slot buffer is expert-major, so
        # it can carry the expert-parallel sharding through the scatter —
        # GSPMD partitions the dispatch instead of replicating it
        xg = jax.lax.with_sharding_constraint(xg, P("model", None))
    xg = xg.reshape(E, C, d)
    if cfg.moe_dispatch_sharded:
        xg = jax.lax.with_sharding_constraint(xg, P("model", None, None))

    if use_kernel:
        cnt = jnp.minimum(counts, C).astype(jnp.int32)
        h = ops.moe_gmm(xg, p["wi"], cnt)
        g, u = jnp.split(h, 2, axis=-1)
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
        yg = ops.moe_gmm(h, p["wo"], cnt)
    else:
        h = jnp.einsum("ecd,edf->ecf", xg, p["wi"])
        g, u = jnp.split(h, 2, axis=-1)
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
        yg = jnp.einsum("ecf,efd->ecd", h, p["wo"])

    # combine: gather each pair's expert output, weight, sum over K
    if cfg.moe_dispatch_sharded:
        yg = jax.lax.with_sharding_constraint(yg, P("model", None, None))
    flat = yg.reshape(E * C, d)
    if cfg.moe_dispatch_sharded:
        flat = jax.lax.with_sharding_constraint(flat, P("model", None))
    pair_out = jnp.where((rank < C)[:, None],
                         flat[jnp.clip(slot, 0, E * C - 1)], 0)
    if cfg.moe_dispatch_sharded:
        # token-major pair rows: redistribute expert→data here (the combine
        # exchange), not by all-gathering the whole expert buffer
        pair_out = jax.lax.with_sharding_constraint(pair_out, P("data", None))
    y = jax.ops.segment_sum(pair_out * gate.reshape(-1)[:, None], tok,
                            num_segments=T)

    if cfg.moe_shared_experts:
        sh = p["shared"]
        hs = xt @ sh["wi"]
        g2, u2 = jnp.split(hs, 2, axis=-1)
        y = y + (jax.nn.silu(g2.astype(jnp.float32)).astype(x.dtype) * u2) \
            @ sh["wo"]

    # load-balance aux loss (Switch): E * mean(frac_tokens * frac_probs)
    frac_tok = counts.astype(jnp.float32) / jnp.maximum(T * K, 1)
    frac_prob = probs.mean(axis=0)
    aux = E * jnp.sum(frac_tok * frac_prob)
    return y.reshape(B, S, d).astype(x.dtype), aux


# ---------------------------------------------------------------------------
# expert-parallel shard_map variant (§Perf ``moe_ep``)
# ---------------------------------------------------------------------------

def apply_ep(p, cfg: ModelConfig, x, *, model_axis: str = "model"):
    """Expert-parallel MoE via shard_map over the model axis.

    Layout inside the step: activations are replicated across "model" (data
    sharded only), experts are sharded over "model".  Each device therefore
    already *holds* every token it could need — it dispatches its local
    tokens to its OWN expert slice and contributes a per-token partial
    output; the combine is a single psum over "model" (T_loc·d bytes)
    instead of GSPMD's all-gather of the whole (E, C, d) expert buffer.
    Routing is replicated (identical on every model rank) so no token ever
    crosses the wire — the paper's "minimize inter-bank traffic" applied to
    expert parallelism.  Shared experts stay outside (plain TP path).
    """
    B, S, d = x.shape
    E, K = cfg.moe_experts, cfg.moe_top_k
    mesh = jax.sharding.get_abstract_mesh()
    dp = tuple(a for a in mesh.axis_names if a != model_axis)

    def local(xt, router, wi, wo):
        # xt: (T_loc, d) local data shard [replicated over model];
        # wi: (E_loc, d, 2f) local expert slice
        T_loc = xt.shape[0]
        C = _capacity(cfg, T_loc)     # per-data-shard per-expert capacity
        E_loc = wi.shape[0]
        if cfg.fsdp:                  # ZeRO-3: gather this layer's experts
            router = jax.lax.all_gather(router, "data", axis=0, tiled=True)
            wi = jax.lax.all_gather(wi, "data", axis=1, tiled=True)
            wo = jax.lax.all_gather(wo, "data", axis=2, tiled=True)
        j = jax.lax.axis_index(model_axis)
        lo = j * E_loc
        logits = (xt.astype(jnp.float32) @ router)       # (T_loc, E)
        probs = jax.nn.softmax(logits, axis=-1)
        gate, topk = jax.lax.top_k(probs, K)
        gate = _norm_gates(cfg, gate).astype(xt.dtype)
        ef = topk.reshape(-1)
        order = jnp.argsort(ef)
        sorted_e = ef[order]
        counts = jax.ops.segment_sum(jnp.ones_like(ef), ef, num_segments=E)
        starts = jnp.cumsum(counts) - counts
        rank_sorted = jnp.arange(T_loc * K) - starts[sorted_e]
        rank = jnp.zeros_like(rank_sorted).at[order].set(rank_sorted)

        mine = (ef >= lo) & (ef < lo + E_loc) & (rank < C)
        slot = jnp.where(mine, (ef - lo) * C + rank, E_loc * C)
        tok = jnp.repeat(jnp.arange(T_loc), K)
        xg = jnp.zeros((E_loc * C, d), xt.dtype).at[slot].set(
            xt[tok], mode="drop").reshape(E_loc, C, d)

        h = jnp.einsum("ecd,edf->ecf", xg, wi)
        g, u = jnp.split(h, 2, axis=-1)
        h = jax.nn.silu(g.astype(jnp.float32)).astype(xt.dtype) * u
        yg = jnp.einsum("ecf,efd->ecd", h, wo).reshape(E_loc * C, d)

        pair_out = jnp.where(mine[:, None],
                             yg[jnp.clip(slot, 0, E_loc * C - 1)], 0)
        y_part = jax.ops.segment_sum(pair_out * gate.reshape(-1)[:, None],
                                     tok, num_segments=T_loc)
        y = jax.lax.psum(y_part, model_axis)             # the combine
        frac_tok = counts.astype(jnp.float32) / jnp.maximum(T_loc * K, 1)
        aux = E * jnp.sum(frac_tok * probs.mean(axis=0))
        aux = jax.lax.pmean(aux, dp) if dp else aux
        return y.astype(xt.dtype), aux
    fs = "data" if cfg.fsdp else None
    mapped = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(dp, None), P(fs, None),
                  P(model_axis, fs, None), P(model_axis, None, fs)),
        out_specs=(P(dp, None), P()), check_vma=False)
    xt = x.reshape(B * S, d)
    y, aux = mapped(xt, p["router"], p["wi"], p["wo"])

    if cfg.moe_shared_experts:
        sh = p["shared"]
        hs = xt @ sh["wi"]
        g2, u2 = jnp.split(hs, 2, axis=-1)
        y = y + (jax.nn.silu(g2.astype(jnp.float32)).astype(x.dtype) * u2) \
            @ sh["wo"]
    return y.reshape(B, S, d).astype(x.dtype), aux
