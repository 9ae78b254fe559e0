"""Model-stack primitives: param trees + sharding specs + pure functions.

Design: no module framework — every layer is (init(key, cfg) → (params,
specs), apply(params, x, ...) → y) where ``specs`` is a pytree of
``PartitionSpec`` congruent to ``params``.  Mesh axis names used in specs:

  "model" — tensor-parallel axis (heads / d_ff / experts / vocab)
  "data"  — optional FSDP shard of the embed dim (ZeRO-3), enabled per arch

Batch/sequence sharding lives at the train/serve-step level (launch/train.py),
not in param specs.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

Params = Any          # nested dict of arrays
Specs = Any           # congruent nested dict of PartitionSpec


# ---------------------------------------------------------------------------
# config
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str = "model"
    family: str = "dense"       # dense | moe | hybrid | ssm | vlm | audio
    n_layers: int = 2
    d_model: int = 128
    n_heads: int = 4
    n_kv_heads: int = 4
    d_ff: int = 256
    vocab: int = 256
    head_dim: int = 0           # 0 ⇒ d_model // n_heads
    window: int | None = None   # sliding-window attention
    qkv_bias: bool = False
    parallel_block: bool = False    # stablelm: attn ∥ ffn
    # MoE
    moe_experts: int = 0
    moe_top_k: int = 0
    moe_shared_experts: int = 0
    moe_every: int = 1          # MoE layer every k-th layer
    moe_first_dense: bool = False
    moe_capacity_factor: float = 1.25   # 0 ⇒ dropless (capacity = tokens)
    moe_norm_topk: bool = True  # renormalise the top-k gates to sum to 1
    dense_ff: int = 0           # d_ff of the non-MoE layers (jamba) / dense l0
    # hybrid (jamba)
    attn_every: int = 0         # 1 attention layer per this many (0 = all)
    # ssm
    ssm_state: int = 16
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_conv: int = 4
    # xlstm
    slstm_every: int = 0        # sLSTM block every k-th layer (0 = none)
    # vlm / audio frontends (stubs provide these token streams)
    cross_attn_every: int = 0   # cross-attn layer every k-th layer
    n_frontend_tokens: int = 0  # precomputed patch/frame embeddings
    # numerics / distribution
    dtype: Any = jnp.bfloat16
    fsdp: bool = False          # shard embed dim of params over "data"
    remat: bool = True
    remat_policy: str = "full"  # "full" | "dots" (save MXU outputs)
    fast_decode: bool = False   # grouped-GQA decode attention (§Perf)
    moe_dispatch_sharded: bool = False  # expert-shard the dispatch buffers
    mlstm_chunk: int = 0        # chunked mLSTM prefill (0 = full parallel)
    moe_ep: bool = False        # shard_map expert-parallel MoE (§Perf)
    scan_layers: bool = True    # lax.scan over the repeating group (False ⇒
    rope_theta: float = 1e4     # unrolled Python loop — exact cost_analysis)
    norm_eps: float = 1e-6      # rms_norm epsilon of every block norm
    # multi-head latent attention (DeepSeek-V2, models/mla.py); a
    # kv_lora_rank > 0 replaces GQA attention with MLA in every layer
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # YaRN rope scaling (factor 0 ⇒ plain rope)
    yarn_factor: float = 0.0
    yarn_original_max_pos: int = 4096
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 0.0

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    def active_params(self) -> float:
        """Active (per-token) parameter count — for 6·N·D roofline math."""
        return _param_count(self, active_only=True)

    def total_params(self) -> float:
        return _param_count(self, active_only=False)


def _param_count(cfg: ModelConfig, active_only: bool) -> float:
    d, hd = cfg.d_model, cfg.hd
    attn = d * hd * (cfg.n_heads + 2 * cfg.n_kv_heads) + cfg.n_heads * hd * d
    if cfg.kv_lora_rank:
        r, H = cfg.kv_lora_rank, cfg.n_heads
        attn = (d * H * (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
                + d * (r + cfg.qk_rope_head_dim)
                + r * H * (cfg.qk_nope_head_dim + cfg.v_head_dim)
                + H * cfg.v_head_dim * d)
    total = 2.0 * cfg.vocab * d          # embed + head
    for li in range(cfg.n_layers):
        is_attn = cfg.attn_every == 0 or li % cfg.attn_every == 0
        if cfg.family == "ssm":
            di = cfg.ssm_expand * d
            total += 2 * d * di + di * d + di * cfg.ssm_conv \
                + 2 * di * cfg.ssm_state
            continue
        if is_attn:
            total += attn
        else:                           # mamba layer (hybrid)
            di = cfg.ssm_expand * d
            total += 2 * d * di + di * d + di * cfg.ssm_conv \
                + 2 * di * cfg.ssm_state
        is_moe = (cfg.moe_experts > 0 and li % cfg.moe_every == 0
                  and not (cfg.moe_first_dense and li == 0))
        if is_moe:
            e = cfg.moe_top_k if active_only else cfg.moe_experts
            total += (e + cfg.moe_shared_experts) * 3 * d * cfg.d_ff \
                + d * cfg.moe_experts
        else:
            ff = cfg.dense_ff or cfg.d_ff
            if ff:
                total += 3 * d * ff
    return total


# ---------------------------------------------------------------------------
# primitives
# ---------------------------------------------------------------------------

def dense_init(key, shape, dtype, in_axis: int = 0):
    scale = 1.0 / math.sqrt(shape[in_axis])
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def rms_norm(x, scale, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale


def rope(x, positions, theta: float = 1e4):
    """x: (..., S, D) with D even; positions: broadcastable to (..., S)."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freqs   # (..., S, half)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


def swiglu(x, wi, wo):
    """wi: (d, 2f) fused gate|up; wo: (f, d)."""
    h = x @ wi
    gate, up = jnp.split(h, 2, axis=-1)
    return (jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up) @ wo


def emb_axis(fsdp: bool):
    """Mesh axis for the embed dim of params: FSDP shards it over 'data'."""
    return "data" if fsdp else None


def mlp_init(key, d, f, dtype, fsdp: bool = False):
    k1, k2 = jax.random.split(key)
    e = emb_axis(fsdp)
    params = {"wi": dense_init(k1, (d, 2 * f), dtype),
              "wo": dense_init(k2, (f, d), dtype)}
    specs = {"wi": P(e, "model"), "wo": P("model", e)}
    return params, specs
