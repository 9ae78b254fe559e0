"""Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434 §2.1).

Per token, the hidden state ``x`` (d) gives

* the queries ``q = x @ wq`` (no q-LoRA), split per head into ``q_nope``
  (``qk_nope_head_dim``) and ``q_pe`` (``qk_rope_head_dim``);
* the compressed KV ``x @ wkv_a`` = ``c_kv`` (``kv_lora_rank``) and one
  rope key ``k_pe`` shared by every head; ``c_kv`` is rms-normalised
  (``kv_norm``);
* per head, ``k_nope`` and ``v`` from ``c_kv @ wkv_b``.

``q_pe`` and ``k_pe`` are roped with YaRN frequencies.  Attention scores
are ``(q_nope·k_nope + q_pe·k_pe) * softmax_scale``, and the heads' values
go through ``wo``.  The cache holds only what a position contributes to
every later step: the normalised ``c_kv`` and the roped ``k_pe``.

Two forms of the same attention:

* :func:`attend_block` decompresses ``k_nope``/``v`` for every position —
  the cheaper form over a whole prompt (prefill, training);
* :func:`attend_latent` absorbs ``wkv_b`` into the query and the output
  (``q_nope @ W_UK`` scores against ``c_kv`` directly, the weighted
  ``c_kv`` goes through ``W_UV``) — the cheaper form for one new token
  against a long cache (decode).

Like the published ``modeling_deepseek.py``, the rope halves are
interleaved in the projections' output and permuted to half-split before
``rotate_half`` (:func:`rope_pe`).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .layers import ModelConfig, dense_init, emb_axis, rms_norm


def init(key, cfg: ModelConfig):
    d, H, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    nope, rope_d, v = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, \
        cfg.v_head_dim
    ks = jax.random.split(key, 4)
    e = emb_axis(cfg.fsdp)
    params = {
        "wq": dense_init(ks[0], (d, H * (nope + rope_d)), cfg.dtype),
        "wkv_a": dense_init(ks[1], (d, r + rope_d), cfg.dtype),
        "kv_norm": jnp.ones((r,), cfg.dtype),
        "wkv_b": dense_init(ks[2], (r, H * (nope + v)), cfg.dtype),
        "wo": dense_init(ks[3], (H * v, d), cfg.dtype),
    }
    specs = {"wq": P(e, "model"), "wkv_a": P(e, None), "kv_norm": P(None),
             "wkv_b": P(None, "model"), "wo": P("model", e)}
    return params, specs


# -- YaRN ----------------------------------------------------------------------

def yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def inv_freq(cfg: ModelConfig) -> np.ndarray:
    """The rope frequencies of the ``qk_rope_head_dim`` channels: plain
    rope, or YaRN's blend of extrapolated and interpolated frequencies
    with a linear ramp between the correction dims of ``beta_fast`` and
    ``beta_slow`` rotations."""
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    if not cfg.yarn_factor:
        return extra
    inter = extra / cfg.yarn_factor

    def corr_dim(rot):
        return dim * math.log(cfg.yarn_original_max_pos
                              / (rot * 2 * math.pi)) / (2 * math.log(base))
    low = max(math.floor(corr_dim(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(corr_dim(cfg.yarn_beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    mask = 1.0 - ramp
    return (inter * (1 - mask) + extra * mask).astype(np.float32)


def rope_mscale(cfg: ModelConfig) -> float:
    """YaRN's scale on cos and sin (1.0 when mscale == mscale_all_dim)."""
    if not cfg.yarn_factor:
        return 1.0
    return (yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale)
            / yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim))


def softmax_scale(cfg: ModelConfig) -> float:
    """``q_head_dim ** -0.5``, times ``mscale(factor, mscale_all_dim)**2``
    under YaRN."""
    s = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if cfg.yarn_factor and cfg.yarn_mscale_all_dim:
        m = yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim)
        s *= m * m
    return s


def rope_pe(x, positions, cfg: ModelConfig):
    """x: (..., S, rope) in the projection's interleaved pair order;
    positions broadcastable to (..., S).  Permutes each head's pairs to
    half-split, then ``x * cos + rotate_half(x) * sin``."""
    dim = x.shape[-1]
    x = x.reshape(*x.shape[:-1], dim // 2, 2)
    x = jnp.swapaxes(x, -1, -2).reshape(*x.shape[:-2], dim)
    freqs = positions[..., None].astype(jnp.float32) * jnp.asarray(
        inv_freq(cfg))
    emb = jnp.concatenate([freqs, freqs], -1)
    m = rope_mscale(cfg)
    cos, sin = jnp.cos(emb) * m, jnp.sin(emb) * m
    rot = jnp.concatenate([-x[..., dim // 2:], x[..., :dim // 2]], -1)
    return (x * cos + rot * sin).astype(x.dtype)


# -- the host half: projections in, latent and attention out -------------------

def latent(cfg: ModelConfig, q, kva, kv_norm, positions):
    """From the two projections of S tokens — ``q`` (B, S, H*(nope+rope))
    and ``kva`` (B, S, r+rope) — give the roped queries ``(q_nope, q_pe)``
    (B, H, S, ·) and the cache entries: normalised ``c_kv`` (B, S, r) and
    roped ``k_pe`` (B, S, rope); positions (B, S)."""
    B, S, _ = q.shape
    H, nope = cfg.n_heads, cfg.qk_nope_head_dim
    r = cfg.kv_lora_rank
    q = q.reshape(B, S, H, -1).transpose(0, 2, 1, 3)
    q_nope, q_pe = q[..., :nope], q[..., nope:]
    q_pe = rope_pe(q_pe, positions[:, None, :], cfg)
    c_kv = rms_norm(kva[..., :r], kv_norm, cfg.norm_eps)
    k_pe = rope_pe(kva[..., r:], positions, cfg)
    return q_nope, q_pe, c_kv, k_pe


def _split_kv_b(cfg: ModelConfig, wkv_b):
    """(r, H*(nope+v)) → W_UK (r, H, nope), W_UV (r, H, v)."""
    w = wkv_b.reshape(cfg.kv_lora_rank, cfg.n_heads, -1)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


def attend_block(cfg: ModelConfig, q_nope, q_pe, c_kv, k_pe, wkv_b,
                 q_block: int = 1024):
    """Causal attention of S tokens over themselves, ``k_nope``/``v``
    decompressed from the latent; queries in blocks of ``q_block`` so that
    one block's scores (B, H, q_block, S) are live at a time.  Returns
    (B, S, H*v)."""
    B, H, S, _ = q_nope.shape
    w_uk, w_uv = _split_kv_b(cfg, wkv_b)
    k_nope = jnp.einsum("bsr,rhn->bhsn", c_kv, w_uk)
    v = jnp.einsum("bsr,rhv->bhsv", c_kv, w_uv)
    scale = softmax_scale(cfg)
    outs = []
    for lo in range(0, S, q_block):
        hi = min(S, lo + q_block)
        s = (jnp.einsum("bhqn,bhkn->bhqk", q_nope[:, :, lo:hi], k_nope)
             + jnp.einsum("bhqp,bkp->bhqk", q_pe[:, :, lo:hi], k_pe))
        s = s.astype(jnp.float32) * scale
        causal = jnp.arange(lo, hi)[:, None] >= jnp.arange(S)[None, :]
        s = jnp.where(causal, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        outs.append(jnp.einsum("bhqk,bhkv->bqhv", p, v))
    o = jnp.concatenate(outs, axis=1)
    return o.reshape(B, S, -1)


def attend_latent(cfg: ModelConfig, q_nope, q_pe, c_cache, pe_cache,
                  lengths, wkv_b):
    """One new query per stream against its latent cache, ``wkv_b``
    absorbed: q_nope, q_pe (B, H, ·) ; caches (B, T, r) and (B, T, rope),
    positions < ``lengths`` (B,) valid.  Returns (B, H*v)."""
    w_uk, w_uv = _split_kv_b(cfg, wkv_b)
    q_lat = jnp.einsum("bhn,rhn->bhr", q_nope, w_uk)
    s = (jnp.einsum("bhr,btr->bht", q_lat, c_cache)
         + jnp.einsum("bhp,btp->bht", q_pe, pe_cache))
    s = s.astype(jnp.float32) * softmax_scale(cfg)
    valid = jnp.arange(c_cache.shape[1])[None, :] < lengths[:, None]
    s = jnp.where(valid[:, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(c_cache.dtype)
    o_lat = jnp.einsum("bht,btr->bhr", p, c_cache)
    o = jnp.einsum("bhr,rhv->bhv", o_lat, w_uv)
    return o.reshape(o.shape[0], -1)


# -- the model path --------------------------------------------------------------

def apply(p, cfg: ModelConfig, x, *, positions=None):
    """Training / prefill self-attention. x: (B, S, d)."""
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    q_nope, q_pe, c_kv, k_pe = latent(cfg, x @ p["wq"], x @ p["wkv_a"],
                                      p["kv_norm"], positions)
    return attend_block(cfg, q_nope, q_pe, c_kv, k_pe, p["wkv_b"]) @ p["wo"]


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=None):
    dtype = dtype or cfg.dtype
    return {"c": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dtype),
            "pe": jnp.zeros((batch, max_len, cfg.qk_rope_head_dim), dtype),
            "len": jnp.zeros((batch,), jnp.int32)}


def write(cache, c_kv, k_pe):
    """Append one position per stream (c_kv (B, r), k_pe (B, rope)) at
    each stream's own length."""
    rows = jnp.arange(c_kv.shape[0])
    idx = cache["len"]
    return {"c": cache["c"].at[rows, idx].set(c_kv.astype(cache["c"].dtype)),
            "pe": cache["pe"].at[rows, idx].set(
                k_pe.astype(cache["pe"].dtype)),
            "len": idx + 1}


def decode(p, cfg: ModelConfig, x, cache):
    """Single-token decode. x: (B, 1, d); returns (y, new_cache)."""
    positions = cache["len"][:, None]
    q_nope, q_pe, c_kv, k_pe = latent(cfg, x @ p["wq"], x @ p["wkv_a"],
                                      p["kv_norm"], positions)
    cache = write(cache, c_kv[:, 0], k_pe[:, 0])
    o = attend_latent(cfg, q_nope[:, :, 0], q_pe[:, :, 0], cache["c"],
                      cache["pe"], cache["len"], p["wkv_b"])
    return (o @ p["wo"])[:, None, :], cache
