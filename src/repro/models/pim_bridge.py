"""Model → PIM bridge: extract a decoder's per-layer matvec operands in the
banked layout the decode engine pins on the ranks (DESIGN.md §14).

The decode hot path is GEMV-dominant: per token, every layer runs its
attention projections (q/k/v/o, or MLA's q/kv_a/o) and the two halves of
each SwiGLU it uses (fused gate|up and down): the dense one, or the shared
experts and the routed experts the router chose.  ``repro.pim.decode`` routes exactly those six matvecs through the
PrIM workloads ``GEMV-B`` (``W @ x + b``) and ``GEMV-G`` (the SwiGLU gated
hidden) — everything else (norms, rope, KV append, attention softmax,
routing, lm_head) stays on the host, where the model's own jnp functions keep the
numerics identical to :func:`repro.launch.serve.greedy_generate`.

This module is the translation layer: it walks the transformer param tree
(``prologue`` blocks + the vmap-stacked repeating ``group``), checks the
architecture is within the engine's contract, and emits each projection as
the **row-major operand pytree** the GEMV decomposition wants:

* the model stores activations-on-the-left weights ``(d_in, d_out)``; the
  paper's GEMV decomposition shards *output rows* across DPUs (§4.2), so
  every matrix is transposed once here, at extraction, to ``(d_out, d_in)``;
* biases are materialized (zeros when the arch has none — exact ``+ 0.0``)
  so one resident pytree per projection covers both cases;
* the fused ``wi = gate|up`` matrix splits into the two ``(d_ff, d_model)``
  halves GEMV-G shards together, keeping each output element's gate and up
  rows on the same bank.

Everything is float32: the banked matvec computes in the operand dtype, and
token-exact parity with the pure-JAX reference is only claimed for float32
params (bfloat16 rounding differs between the two reduction orders).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from .layers import ModelConfig
from .transformer import layer_plan


@dataclasses.dataclass(frozen=True)
class LayerWeights:
    """One decoder layer's PIM-side operands + host-side arrays.

    ``mats`` maps each matvec of the layer to its operand pytree: GEMV-B
    ``{"w", "b"}`` (``b`` a column, ``(rows, 1)``) or, for a SwiGLU's
    gated half (a name ending in ``up``), GEMV-G ``{"wg", "wu"}``.  Each
    pytree is what the engine wraps in one
    :class:`~repro.runtime.resident.ResidentHandle` and pins as a unit.
    The names:

    * attention: ``q``, ``k``, ``v``, ``o``; latent attention (MLA):
      ``q``, ``kv_a`` (the compressed KV and the rope key), ``o``;
    * dense SwiGLU: ``up``, ``down``; MoE: the shared experts fused into
      one SwiGLU, ``shared.up`` / ``shared.down``, and each routed expert
      ``e<i>.up`` / ``e<i>.down``.

    ``host`` holds what stays on the host: the norm scales ``norm1``,
    ``norm2``; for MLA ``kv_norm`` and ``wkv_b`` (absorbed into the query
    and the output, ``models/mla.py``); for MoE the ``router`` (d, E).
    """

    mats: dict
    host: dict
    n_experts: int = 0


def workload_of(proj: str) -> str:
    """The PrIM workload that serves matvec ``proj``."""
    return "GEMV-G" if proj.endswith("up") else "GEMV-B"


def validate_decode_config(cfg: ModelConfig) -> None:
    """Reject configs outside the decode engine's contract.

    The engine replicates ``transformer.decode_step`` for two blocks: plain
    attention + dense SwiGLU, and DeepSeek-V2's latent attention (MLA) +
    dense SwiGLU or MoE (routed + shared experts).  Anything that changes
    the block dataflow (parallel residual, SSM/xLSTM mixers, cross
    attention, MoE under plain attention) or the numerics contract
    (non-float32 params) raises here, at construction, instead of silently
    diverging from the reference.
    """
    if cfg.dtype != jnp.float32:
        raise ValueError(
            f"decode engine requires float32 params for token-exact parity "
            f"with the jnp reference; {cfg.name} has dtype={cfg.dtype}")
    if cfg.parallel_block:
        raise ValueError(
            f"{cfg.name}: parallel_block (attn ∥ ffn off one norm) changes "
            "the residual dataflow — not supported by the decode engine")
    pro, period, _ = layer_plan(cfg)
    for li, desc in enumerate(pro + period):
        if desc["mixer"] not in ("attn", "mla"):
            raise ValueError(
                f"{cfg.name} layer {li}: mixer {desc['mixer']!r} is not "
                "offloadable — the decode engine handles attention and "
                "latent-attention blocks only (mamba/xlstm/cross layers "
                "have no GEMV hot path)")
        if desc["ffn"] == "none" or (desc["ffn"] == "moe"
                                     and desc["mixer"] != "mla"):
            raise ValueError(
                f"{cfg.name} layer {li}: ffn {desc['ffn']!r} — the dense "
                "SwiGLU maps onto GEMV-G/GEMV-B, and MoE is served only in "
                "DeepSeek-V2's latent-attention block ('none' has nothing "
                "to offload)")


def _f32(a) -> np.ndarray:
    return np.asarray(a, np.float32)


def _rows(a) -> np.ndarray:
    """Transpose to the row-sharded (d_out, d_in) GEMV layout, contiguous
    so the per-chunk device pushes are single copies (no copy at all when
    ``a`` is already the transpose of a row-major array)."""
    return np.ascontiguousarray(_f32(a).T)


def _bias(p: dict, key: str, n: int) -> np.ndarray:
    return _f32(p[key]) if key in p else np.zeros(n, np.float32)


def _gemv_b(w, b=None) -> dict:
    """GEMV-B's operand, the bias a column: the engine stacks its streams'
    vectors as columns."""
    w = _rows(w)
    b = np.zeros(w.shape[0], np.float32) if b is None else b
    return {"w": w, "b": b.reshape(-1, 1)}


def _swiglu(ffn: dict, prefix: str) -> dict:
    """``<prefix>up`` (GEMV-G over the fused gate|up) and ``<prefix>down``."""
    wi = _f32(ffn["wi"])                       # (d, 2f) fused gate|up
    f = wi.shape[1] // 2
    return {prefix + "up": {"wg": _rows(wi[:, :f]), "wu": _rows(wi[:, f:])},
            prefix + "down": _gemv_b(ffn["wo"])}


def _layer_params(params, n_prologue: int, period_len: int, li: int):
    """The li-th global layer's param dict: prologue blocks are plain list
    entries; repeated blocks index the vmap-stacked group leaves at
    (repeat, position) = divmod(li - n_prologue, period_len)."""
    if li < n_prologue:
        return params["prologue"][li]
    r, pos = divmod(li - n_prologue, period_len)
    return jax.tree.map(lambda a: a[r], params["group"][pos])


def per_layer_params(params, cfg: ModelConfig) -> list[dict]:
    """Every global layer's param dict, in layer order (views of the
    stacked group leaves where ``params`` holds numpy arrays)."""
    pro, period, _ = layer_plan(cfg)
    return [_layer_params(params, len(pro), max(len(period), 1), li)
            for li in range(cfg.n_layers)]


def extract_decode_weights(params, cfg: ModelConfig) -> list[LayerWeights]:
    """Per-global-layer PIM operands for every decoder layer, in layer
    order.  Validates the config first; the result is position-stable, so
    the engine's (layer, proj) handle map survives across steps."""
    validate_decode_config(cfg)
    pro, period, repeats = layer_plan(cfg)
    descs = pro + period * repeats
    H, KVH, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    layers = []
    for li, p in enumerate(per_layer_params(params, cfg)):
        m, ffn = p["mixer"], p["ffn"]
        host = {"norm1": p["norm1"], "norm2": p["norm2"]}
        if descs[li]["mixer"] == "mla":
            mats = {"q": _gemv_b(m["wq"]), "kv_a": _gemv_b(m["wkv_a"]),
                    "o": _gemv_b(m["wo"])}
            host |= {"kv_norm": m["kv_norm"], "wkv_b": m["wkv_b"]}
        else:
            mats = {"q": _gemv_b(m["wq"], _bias(m, "bq", H * hd)),
                    "k": _gemv_b(m["wk"], _bias(m, "bk", KVH * hd)),
                    "v": _gemv_b(m["wv"], _bias(m, "bv", KVH * hd)),
                    "o": _gemv_b(m["wo"])}
        n_experts = 0
        if descs[li]["ffn"] == "moe":
            n_experts = cfg.moe_experts
            mats |= _swiglu(ffn["shared"], "shared.")
            for e in range(n_experts):
                mats |= _swiglu({"wi": ffn["wi"][e], "wo": ffn["wo"][e]},
                                f"e{e}.")
            host["router"] = ffn["router"]
        else:
            mats |= _swiglu(ffn, "")
        layers.append(LayerWeights(mats=mats, host=host,
                                   n_experts=n_experts))
    return layers
