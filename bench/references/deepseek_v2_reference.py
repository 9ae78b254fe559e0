"""Plain reference of DeepSeek-V2 (arXiv:2405.04434; the published
``modeling_deepseek.py``): the full forward pass over a token sequence in
straightforward float32 ``jax.numpy`` at the highest matmul precision —
no cache, no kernels, no batching across sequences, every attention head
and every expert computed on its own.

``cfg`` is the model's ``config.json`` as a dict (``hidden_size``,
``num_attention_heads``, ``kv_lora_rank``, ``qk_nope_head_dim``,
``qk_rope_head_dim``, ``v_head_dim``, ``rope_theta``, ``rope_scaling``,
``rms_norm_eps``, ``n_routed_experts``, ``num_experts_per_tok``,
``norm_topk_prob``, ``routed_scaling_factor``).  ``params`` holds
``embed`` (V, d), ``final_norm`` (d), ``lm_head`` (d, V) and ``layers``,
one dict per layer with activations-on-the-left weights:

* ``norm1``, ``norm2`` (d); ``wq`` (d, H*(nope+rope)), ``wkv_a``
  (d, r+rope), ``kv_norm`` (r), ``wkv_b`` (r, H*(nope+v)), ``wo`` (H*v, d);
* a dense layer: ``ffn = {"wi": (d, 2f) gate|up, "wo": (f, d)}``;
* an MoE layer: ``ffn = {"router": (d, E), "wi": (E, d, 2f), "wo":
  (E, f, d), "shared": {"wi", "wo"}}`` (the shared experts as one SwiGLU).

Each layer's weights go to the device when that layer runs, so a model
larger than the device's memory runs one layer at a time.

Departures from the published code: none in the mathematics.  The MoE
layer computes every expert on every token and weights it by its gate,
zero for experts outside a token's top-k; the published code gathers each
expert's tokens.  The rope halves are permuted from interleaved to
half-split before ``rotate_half``, as published.

A copy of the program's ``repro/models/reference_deepseek_v2.py`` that
imports nothing of the program, with the control below.

``column_control`` is the control the harness puts in the program's place
(``tests/bench/controls.py``): each matvec the decode engine submits
(GEMV-B ``W @ x + b``, GEMV-G ``silu(Wg @ x) * (Wu @ x)``) computed at the
TPU's default precision, one bfloat16 pass with float32 accumulation.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def _rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return w * (x * jax.lax.rsqrt(var + eps))


def _swiglu(x, wi, wo):
    f = wi.shape[1] // 2
    h = x @ wi
    return (jax.nn.silu(h[:, :f]) * h[:, f:]) @ wo


def _yarn_get_mscale(scale, mscale):
    if scale <= 1:
        return 1.0
    return 0.1 * mscale * math.log(scale) + 1.0


def rope_tables(cfg: dict, positions: np.ndarray):
    """cos and sin (S, rope) of the rope channels at ``positions``."""
    dim = cfg["qk_rope_head_dim"]
    base = cfg["rope_theta"]
    freq_extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    rs = cfg.get("rope_scaling")
    mscale = 1.0
    if rs:
        factor = rs["factor"]
        orig = rs["original_max_position_embeddings"]
        freq_inter = freq_extra / factor

        def corr(rot):
            return (dim * math.log(orig / (rot * 2 * math.pi))
                    / (2 * math.log(base)))
        low = max(math.floor(corr(rs["beta_fast"])), 0)
        high = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
        if low == high:
            high += 0.001
        ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
        extra_mask = 1.0 - ramp
        inv_freq = freq_inter * (1 - extra_mask) + freq_extra * extra_mask
        mscale = (_yarn_get_mscale(factor, rs["mscale"])
                  / _yarn_get_mscale(factor, rs["mscale_all_dim"]))
    else:
        inv_freq = freq_extra
    inv_freq = inv_freq.astype(np.float32)
    freqs = np.outer(positions.astype(np.float32), inv_freq)
    emb = np.concatenate([freqs, freqs], axis=-1)
    return (jnp.asarray(np.cos(emb) * mscale, jnp.float32),
            jnp.asarray(np.sin(emb) * mscale, jnp.float32))


def _apply_rope(x, cos, sin):
    """x (..., S, dim) interleaved → half-split, then rotate_half."""
    d = x.shape[-1]
    x = x.reshape(*x.shape[:-1], d // 2, 2)
    x = jnp.swapaxes(x, -1, -2).reshape(*x.shape[:-2], d)
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * cos + rot * sin


def softmax_scale(cfg: dict) -> float:
    scale = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    rs = cfg.get("rope_scaling")
    if rs and rs.get("mscale_all_dim"):
        m = _yarn_get_mscale(rs["factor"], rs["mscale_all_dim"])
        scale = scale * m * m
    return scale


def _attention(cfg, p, h, cos, sin):
    S = h.shape[0]
    H = cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    r, vd = cfg["kv_lora_rank"], cfg["v_head_dim"]
    q = (h @ p["wq"]).reshape(S, H, nope + rope)
    kva = h @ p["wkv_a"]
    c_kv = _rms_norm(kva[:, :r], p["kv_norm"], cfg["rms_norm_eps"])
    k_pe = _apply_rope(kva[:, r:], cos, sin)                 # (S, rope)
    kv = (c_kv @ p["wkv_b"]).reshape(S, H, nope + vd)
    causal = np.tril(np.ones((S, S), bool))
    scale = softmax_scale(cfg)
    heads = []
    for i in range(H):
        qi = jnp.concatenate([q[:, i, :nope],
                              _apply_rope(q[:, i, nope:], cos, sin)], -1)
        ki = jnp.concatenate([kv[:, i, :nope], k_pe], -1)
        s = (qi @ ki.T) * scale
        s = jnp.where(causal, s, -jnp.inf)
        heads.append(jax.nn.softmax(s, axis=-1) @ kv[:, i, nope:])
    return jnp.concatenate(heads, -1) @ p["wo"]


def _moe(cfg, ffn, h):
    E, k = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    probs = jax.nn.softmax(h @ ffn["router"], axis=-1)       # (S, E)
    _, top = jax.lax.top_k(probs, k)
    chosen = jnp.zeros_like(probs).at[
        jnp.arange(h.shape[0])[:, None], top].set(1.0)
    gates = probs * chosen
    if cfg["norm_topk_prob"]:
        gates = gates / gates.sum(-1, keepdims=True)
    gates = gates * cfg["routed_scaling_factor"]
    y = _swiglu(h, ffn["shared"]["wi"], ffn["shared"]["wo"])
    for e in range(E):
        y = y + gates[:, e:e + 1] * _swiglu(h, ffn["wi"][e], ffn["wo"][e])
    return y


def _layer(cfg, p, x, cos, sin):
    eps = cfg["rms_norm_eps"]
    x = x + _attention(cfg, p, _rms_norm(x, p["norm1"], eps), cos, sin)
    h = _rms_norm(x, p["norm2"], eps)
    if "router" in p["ffn"]:
        return x + _moe(cfg, p["ffn"], h)
    return x + _swiglu(h, p["ffn"]["wi"], p["ffn"]["wo"])


def forward(cfg: dict, params: dict, sequences, positions=None):
    """Logits (len(p), V) at the positions ``p`` of each token sequence in
    ``sequences`` (``positions``, one index array per sequence; default
    every position), as float32 numpy.  Layers run in turn over all the
    sequences, each layer's weights on the device once.  Attention is
    causal, so tokens after a position do not change its logits."""
    dev = lambda t: jax.tree.map(jnp.asarray, t)   # noqa: E731
    if positions is None:
        positions = [np.arange(len(s)) for s in sequences]
    with jax.default_matmul_precision("highest"):
        embed = params["embed"]
        xs = [jnp.asarray(np.asarray(embed)[np.asarray(s)], jnp.float32)
              for s in sequences]
        tables = [rope_tables(cfg, np.arange(len(s))) for s in sequences]
        for p in params["layers"]:
            pd = dev(p)
            xs = [_layer(cfg, pd, x, cos, sin)
                  for x, (cos, sin) in zip(xs, tables)]
            del pd
        head = dev({"n": params["final_norm"], "w": params["lm_head"]})
        out = []
        for x, pos in zip(xs, positions):
            x = _rms_norm(x[np.asarray(pos)], head["n"], cfg["rms_norm_eps"])
            out.append(np.asarray(x @ head["w"], np.float32))
    return out


# -- the control: each matvec one precision lower ---------------------------------

def _bf16_mv(w, x):
    return jnp.dot(jnp.asarray(w).astype(jnp.bfloat16),
                   jnp.asarray(x).astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)


def column_control(workload: str, args):
    """The answer to one GEMV-B / GEMV-G request at one bfloat16 pass."""
    op, x = args
    op = getattr(op, "value", op)              # a resident handle's operand
    if workload == "GEMV-B":
        return np.asarray(_bf16_mv(op["w"], x) + jnp.asarray(op["b"]))
    if workload == "GEMV-G":
        return np.asarray(jax.nn.silu(_bf16_mv(op["wg"], x))
                          * _bf16_mv(op["wu"], x))
    raise KeyError(f"no control for {workload!r}")
