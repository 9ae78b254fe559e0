"""Composable decoder assembler for all 10 assigned architectures.

A model is: embed → [prologue blocks] → scan(repeating layer group) →
final norm → lm head.  The repeating group is derived from the config's
cadences (attn_every / moe_every / cross_attn_every / slstm_every), so
homogeneous stacks compile as a single ``lax.scan`` step (small HLO, fast
multi-cell dry-runs) with optional per-group remat.

Block kinds: attn | mla | mamba | mlstm | slstm | cross;  FFN: dense | moe | none.
"""
from __future__ import annotations

import math
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import attention, mamba, mla, moe, xlstm
from .layers import ModelConfig, dense_init, emb_axis, mlp_init, rms_norm, swiglu


# ---------------------------------------------------------------------------
# layer plan
# ---------------------------------------------------------------------------

def _desc(cfg: ModelConfig, li: int) -> dict:
    if cfg.family == "ssm":
        mixer = "slstm" if (cfg.slstm_every and
                            li % cfg.slstm_every == cfg.slstm_every - 1) \
            else "mlstm"
        return {"mixer": mixer, "ffn": "none", "ff": 0}
    if cfg.attn_every and li % cfg.attn_every != 0:
        mixer = "mamba"
    elif cfg.cross_attn_every and \
            li % cfg.cross_attn_every == cfg.cross_attn_every - 1:
        mixer = "cross"
    else:
        mixer = "mla" if cfg.kv_lora_rank else "attn"
    is_moe = (cfg.moe_experts > 0 and li % cfg.moe_every == 0
              and not (cfg.moe_first_dense and li == 0))
    if is_moe:
        return {"mixer": mixer, "ffn": "moe", "ff": cfg.d_ff}
    ff = cfg.dense_ff or cfg.d_ff
    return {"mixer": mixer, "ffn": "dense" if ff else "none", "ff": ff}


def layer_plan(cfg: ModelConfig):
    """Returns (prologue_descs, period_descs, repeats)."""
    descs = [_desc(cfg, li) for li in range(cfg.n_layers)]
    cad = [c for c in (cfg.attn_every, cfg.moe_every, cfg.cross_attn_every,
                       cfg.slstm_every) if c]
    p = math.lcm(*cad) if cad else 1
    for q in range(cfg.n_layers + 1):
        rest = descs[q:]
        if len(rest) % p:
            continue
        groups = [rest[i:i + p] for i in range(0, len(rest), p)]
        if all(g == groups[0] for g in groups):
            return descs[:q], groups[0] if groups else [], len(groups)
    raise ValueError(f"no periodic plan for {cfg.name}")


# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------

def _block_init(key, cfg: ModelConfig, desc: dict):
    km, kf = jax.random.split(key)
    d = cfg.d_model
    params: dict = {"norm1": jnp.ones((d,), cfg.dtype)}
    specs: dict = {"norm1": P(None)}
    mixer = desc["mixer"]
    if mixer in ("attn", "cross"):
        params["mixer"], specs["mixer"] = attention.init(km, cfg)
    elif mixer == "mla":
        params["mixer"], specs["mixer"] = mla.init(km, cfg)
    elif mixer == "mamba":
        params["mixer"], specs["mixer"] = mamba.init(km, cfg)
    elif mixer == "mlstm":
        params["mixer"], specs["mixer"] = xlstm.init_mlstm(km, cfg)
    elif mixer == "slstm":
        params["mixer"], specs["mixer"] = xlstm.init_slstm(km, cfg)
    if desc["ffn"] != "none":
        params["norm2"] = jnp.ones((d,), cfg.dtype)
        specs["norm2"] = P(None)
        if desc["ffn"] == "moe":
            params["ffn"], specs["ffn"] = moe.init(kf, cfg)
        else:
            params["ffn"], specs["ffn"] = mlp_init(kf, d, desc["ff"],
                                                   cfg.dtype, cfg.fsdp)
    return params, specs


def _block_apply(p, cfg: ModelConfig, desc: dict, x, frontend, use_kernel):
    aux = jnp.zeros((), jnp.float32)
    h = rms_norm(x, p["norm1"], cfg.norm_eps)
    mixer = desc["mixer"]
    if mixer == "attn":
        mo = attention.apply(p["mixer"], cfg, h, use_kernel=use_kernel)
    elif mixer == "mla":
        mo = mla.apply(p["mixer"], cfg, h)
    elif mixer == "cross":
        mo = attention.apply_cross(p["mixer"], cfg, h, frontend)
    elif mixer == "mamba":
        mo = mamba.apply(p["mixer"], cfg, h, use_kernel=use_kernel)
    elif mixer == "mlstm":
        mo = xlstm.apply_mlstm_chunked(p["mixer"], cfg, h,
                                       chunk=cfg.mlstm_chunk) \
            if cfg.mlstm_chunk else xlstm.apply_mlstm(p["mixer"], cfg, h)
    else:
        mo = xlstm.apply_slstm(p["mixer"], cfg, h)
    if desc["ffn"] == "none":
        return x + mo, aux
    if cfg.parallel_block:          # stablelm: attn ∥ ffn off one norm
        fo = swiglu(h, p["ffn"]["wi"], p["ffn"]["wo"])
        return x + mo + fo, aux
    x = x + mo
    h2 = rms_norm(x, p["norm2"], cfg.norm_eps)
    if desc["ffn"] == "moe":
        if cfg.moe_ep:
            fo, aux = moe.apply_ep(p["ffn"], cfg, h2)
        else:
            fo, aux = moe.apply(p["ffn"], cfg, h2, use_kernel=use_kernel)
    else:
        fo = swiglu(h2, p["ffn"]["wi"], p["ffn"]["wo"])
    return x + fo, aux


# ---------------------------------------------------------------------------
# model
# ---------------------------------------------------------------------------

def init(key, cfg: ModelConfig):
    pro, period, repeats = layer_plan(cfg)
    keys = jax.random.split(key, 4 + len(pro))
    e = emb_axis(cfg.fsdp)
    params: dict = {
        "embed": dense_init(keys[0], (cfg.vocab, cfg.d_model), cfg.dtype),
        "final_norm": jnp.ones((cfg.d_model,), cfg.dtype),
        "lm_head": dense_init(keys[1], (cfg.d_model, cfg.vocab), cfg.dtype),
    }
    specs: dict = {
        "embed": P("model", e),
        "final_norm": P(None),
        "lm_head": P(e, "model"),
    }
    if pro:
        pp, ss = zip(*[_block_init(keys[4 + i], cfg, d)
                       for i, d in enumerate(pro)])
        params["prologue"], specs["prologue"] = list(pp), list(ss)
    if repeats:
        def one(k):
            ks = jax.random.split(k, len(period))
            return [_block_init(ks[i], cfg, d)[0]
                    for i, d in enumerate(period)]
        stacked = jax.vmap(one)(jax.random.split(keys[2], repeats))
        params["group"] = stacked
        gspecs = [_block_init(keys[3], cfg, d)[1] for d in period]
        # prepend scan axis (None) to every spec
        specs["group"] = jax.tree.map(
            lambda s: P(*((None,) + tuple(s))), gspecs,
            is_leaf=lambda s: isinstance(s, P))
    return params, specs


def trunk(params, cfg: ModelConfig, tokens=None, embeds=None,
          frontend=None, use_kernel: bool = False):
    """Embed + all blocks + final norm (pre-lm_head hidden). → (x, aux)."""
    pro, period, repeats = layer_plan(cfg)
    x = params["embed"][tokens] if embeds is None else embeds.astype(cfg.dtype)
    aux = jnp.zeros((), jnp.float32)
    for p_, d_ in zip(params.get("prologue", []), pro):
        x, a = _block_apply(p_, cfg, d_, x, frontend, use_kernel)
        aux += a

    if repeats:
        def body(carry, layer_params):
            x, aux = carry
            for i, d_ in enumerate(period):
                x, a = _block_apply(layer_params[i], cfg, d_, x, frontend,
                                    use_kernel)
                aux += a
            return (x, aux), None

        if cfg.remat:
            policy = None if cfg.remat_policy == "full" else \
                jax.checkpoint_policies.dots_with_no_batch_dims_saveable
            body = jax.checkpoint(body, policy=policy)
        if cfg.scan_layers:
            (x, aux), _ = jax.lax.scan(body, (x, aux), params["group"])
        else:       # unrolled: exact XLA cost_analysis (dry-run cost path)
            for r in range(repeats):
                lp = jax.tree.map(lambda a: a[r], params["group"])
                (x, aux), _ = body((x, aux), lp)

    return rms_norm(x, params["final_norm"], cfg.norm_eps), aux


def forward(params, cfg: ModelConfig, tokens=None, embeds=None,
            frontend=None, use_kernel: bool = False):
    """tokens: (B, S) int32 or embeds: (B, S, d). Returns (logits, aux)."""
    x, aux = trunk(params, cfg, tokens=tokens, embeds=embeds,
                   frontend=frontend, use_kernel=use_kernel)
    return x @ params["lm_head"], aux


def _chunked_ce(x, lm_head, labels, n_chunks: int, unroll: bool = False):
    """Streaming CE over vocab chunks: the (B,S,V) logits tensor is never
    materialized (one (B,S,V/k) bf16 chunk live at a time, f32 running
    max/sum/gold) — the beyond-paper memory optimization of §Perf."""
    d, V = lm_head.shape
    vc = -(-V // n_chunks)
    pad = n_chunks * vc - V
    w = jnp.pad(lm_head, ((0, 0), (0, pad)))
    w = jnp.moveaxis(w.reshape(d, n_chunks, vc), 1, 0)       # (k, d, vc)
    starts = jnp.arange(n_chunks) * vc
    B, S = labels.shape
    init = (jnp.full((B, S), -1e30, jnp.float32),
            jnp.zeros((B, S), jnp.float32),
            jnp.zeros((B, S), jnp.float32))

    def body(carry, wi):
        m, s, gold = carry
        wch, start = wi
        lg = (x @ wch).astype(jnp.float32)                   # (B, S, vc)
        valid = (start + jnp.arange(vc)) < V
        lg = jnp.where(valid, lg, -1e30)
        m_new = jnp.maximum(m, lg.max(-1))
        s = s * jnp.exp(m - m_new) + jnp.exp(
            lg - m_new[..., None]).sum(-1)
        inb = (labels >= start) & (labels < start + vc)
        idx = jnp.clip(labels - start, 0, vc - 1)
        gold = gold + jnp.where(
            inb, jnp.take_along_axis(lg, idx[..., None], -1)[..., 0], 0.0)
        return (m_new, s, gold), None

    (m, s, gold), _ = jax.lax.scan(body, init, (w, starts),
                                   unroll=n_chunks if unroll else 1)
    return jnp.mean(m + jnp.log(s) - gold)


def loss_fn(params, cfg: ModelConfig, batch, use_kernel: bool = False,
            loss_chunks: int = 0):
    """batch: {"tokens" or "embeds", "labels" (B,S) int32, optional
    "frontend"}.  Mean next-token CE + MoE aux."""
    labels = batch["labels"]
    if loss_chunks:
        x, aux = trunk(params, cfg, tokens=batch.get("tokens"),
                       embeds=batch.get("embeds"),
                       frontend=batch.get("frontend"), use_kernel=use_kernel)
        ce = _chunked_ce(x, params["lm_head"], labels, loss_chunks,
                         unroll=not cfg.scan_layers)
    else:
        logits, aux = forward(params, cfg, tokens=batch.get("tokens"),
                              embeds=batch.get("embeds"),
                              frontend=batch.get("frontend"),
                              use_kernel=use_kernel)
        lf = logits.astype(jnp.float32)
        logz = jax.scipy.special.logsumexp(lf, axis=-1)
        gold = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
        ce = jnp.mean(logz - gold)
    return ce + 0.01 * aux, {"ce": ce, "aux": aux}


# ---------------------------------------------------------------------------
# decode (serve path)
# ---------------------------------------------------------------------------

def _block_cache(cfg: ModelConfig, desc: dict, batch: int, max_len: int,
                 frontend=None, p=None):
    mixer = desc["mixer"]
    if mixer == "attn":
        return attention.init_cache(cfg, batch, max_len)
    if mixer == "mla":
        return mla.init_cache(cfg, batch, max_len)
    if mixer == "cross":
        # precomputed cross K/V from the frontend tokens
        B, T, _ = frontend.shape
        H, KVH, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
        k = (frontend @ p["mixer"]["wk"]).reshape(B, T, KVH, hd)
        v = (frontend @ p["mixer"]["wv"]).reshape(B, T, KVH, hd)
        return {"ck": k.transpose(0, 2, 1, 3), "cv": v.transpose(0, 2, 1, 3)}
    if mixer == "mamba":
        return mamba.init_cache(cfg, batch)
    if mixer == "mlstm":
        return xlstm.init_mlstm_cache(cfg, batch)
    return xlstm.init_slstm_cache(cfg, batch)


def init_cache(params, cfg: ModelConfig, batch: int, max_len: int,
               frontend=None):
    pro, period, repeats = layer_plan(cfg)
    cache: dict = {}
    if pro:
        cache["prologue"] = [
            _block_cache(cfg, d, batch, max_len, frontend,
                         params["prologue"][i]) for i, d in enumerate(pro)]
    if repeats:
        def one(layer_params):
            return [_block_cache(cfg, d, batch, max_len, frontend,
                                 layer_params[i]) for i, d in enumerate(period)]
        cache["group"] = jax.vmap(one)(params["group"]) if any(
            d["mixer"] == "cross" for d in period) else \
            _stack_caches(cfg, period, batch, max_len, repeats, frontend)
    return cache


def _stack_caches(cfg, period, batch, max_len, repeats, frontend):
    protos = [_block_cache(cfg, d, batch, max_len, frontend, None)
              for d in period]
    return jax.tree.map(
        lambda a: jnp.broadcast_to(a, (repeats,) + a.shape).copy(), protos)


def _block_decode(p, cfg, desc, x, cache, frontend):
    mixer = desc["mixer"]
    h = rms_norm(x, p["norm1"], cfg.norm_eps)
    if mixer == "attn":
        mo, cache = attention.decode(p["mixer"], cfg, h, cache)
    elif mixer == "mla":
        mo, cache = mla.decode(p["mixer"], cfg, h, cache)
    elif mixer == "cross":
        q = (h @ p["mixer"]["wq"]).reshape(
            x.shape[0], 1, cfg.n_heads, cfg.hd).transpose(0, 2, 1, 3)
        from repro.kernels import ops as kops
        T = cache["ck"].shape[2]
        lens = jnp.full((x.shape[0],), T, jnp.int32)
        o = kops.decode_attention(q, cache["ck"], cache["cv"], lens)
        mo = o.transpose(0, 2, 1, 3).reshape(x.shape[0], 1,
                                             cfg.n_heads * cfg.hd) \
            @ p["mixer"]["wo"]
    elif mixer == "mamba":
        mo, cache = mamba.decode(p["mixer"], cfg, h, cache)
    elif mixer == "mlstm":
        mo, cache = xlstm.decode_mlstm(p["mixer"], cfg, h, cache)
    else:
        mo, cache = xlstm.decode_slstm(p["mixer"], cfg, h, cache)
    if desc["ffn"] == "none":
        return x + mo, cache
    if cfg.parallel_block:
        fo = swiglu(h, p["ffn"]["wi"], p["ffn"]["wo"])
        return x + mo + fo, cache
    x = x + mo
    h2 = rms_norm(x, p["norm2"], cfg.norm_eps)
    if desc["ffn"] == "moe":
        fo, _ = moe.apply(p["ffn"], cfg, h2)
    else:
        fo = swiglu(h2, p["ffn"]["wi"], p["ffn"]["wo"])
    return x + fo, cache


def decode_step(params, cfg: ModelConfig, tokens, cache, embeds=None,
                frontend=None):
    """One decode step. tokens: (B, 1) int32 (or embeds (B,1,d)).
    Returns (logits (B, 1, V), new_cache)."""
    pro, period, repeats = layer_plan(cfg)
    x = params["embed"][tokens] if embeds is None else embeds.astype(cfg.dtype)
    new_cache: dict = {}
    if pro:
        ncs = []
        for i, d_ in enumerate(pro):
            x, nc = _block_decode(params["prologue"][i], cfg, d_, x,
                                  cache["prologue"][i], frontend)
            ncs.append(nc)
        new_cache["prologue"] = ncs

    if repeats:
        def body(x, xs):
            layer_params, layer_cache = xs
            ncs = []
            for i, d_ in enumerate(period):
                x, nc = _block_decode(layer_params[i], cfg, d_, x,
                                      layer_cache[i], frontend)
                ncs.append(nc)
            return x, ncs

        if cfg.scan_layers:
            x, group_cache = jax.lax.scan(body, x,
                                          (params["group"], cache["group"]))
        else:
            outs = []
            for r in range(repeats):
                lp = jax.tree.map(lambda a: a[r], params["group"])
                lc = jax.tree.map(lambda a: a[r], cache["group"])
                x, nc = body(x, (lp, lc))
                outs.append(nc)
            group_cache = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
        new_cache["group"] = group_cache

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = x @ params["lm_head"]
    return logits, new_cache
