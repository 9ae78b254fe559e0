"""Architecture/shape registry: ``--arch <id>`` × assigned input shapes.

Each arch module defines FULL (the exact public-literature config) and SMOKE
(a reduced same-family config for CPU tests).  ``input_specs`` produces
ShapeDtypeStruct stand-ins for every model input — weak-type-correct,
shardable, no device allocation (dry-run style).
"""
from __future__ import annotations

import dataclasses
import importlib

import jax
import jax.numpy as jnp

from repro.models.layers import ModelConfig

ARCHS = [
    "jamba_1_5_large_398b", "h2o_danube_3_4b", "codeqwen1_5_7b",
    "stablelm_12b", "tinyllama_1_1b", "llama_3_2_vision_11b",
    "musicgen_medium", "xlstm_125m", "deepseek_moe_16b", "kimi_k2_1t_a32b",
]

#: served by the decode engine, outside the dry-run grid above
SERVED = ["deepseek_v2_lite"]

ARCH_IDS = {a.replace("_", "-"): a for a in ARCHS + SERVED}


@dataclasses.dataclass(frozen=True)
class Shape:
    name: str
    seq: int
    batch: int
    kind: str        # train | prefill | decode


SHAPES = {
    "train_4k": Shape("train_4k", 4096, 256, "train"),
    "prefill_32k": Shape("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": Shape("decode_32k", 32768, 128, "decode"),
    "long_500k": Shape("long_500k", 524288, 1, "decode"),
}


def get_config(arch: str, smoke: bool = False) -> ModelConfig:
    norm = arch.replace(".", "_").replace("-", "_")
    if norm not in ARCHS + SERVED:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(ARCH_IDS)}")
    mod = importlib.import_module(f"repro.configs.{norm}")
    return mod.SMOKE if smoke else mod.FULL


def is_subquadratic(cfg: ModelConfig) -> bool:
    """long_500k applicability: SSM / hybrid / sliding-window archs only."""
    return cfg.family in ("ssm", "hybrid") or cfg.window is not None


def skip_reason(cfg: ModelConfig, shape: Shape) -> str | None:
    if shape.name == "long_500k" and not is_subquadratic(cfg):
        return "SKIP(full-attention)"
    return None


def input_specs(cfg: ModelConfig, shape: Shape) -> dict:
    """ShapeDtypeStruct stand-ins for every input of the lowered step."""
    B, S = shape.batch, shape.seq
    i32 = jnp.int32
    if shape.kind == "train":
        if cfg.family == "audio":
            batch = {"embeds": jax.ShapeDtypeStruct((B, S, cfg.d_model),
                                                    cfg.dtype),
                     "labels": jax.ShapeDtypeStruct((B, S), i32)}
        else:
            batch = {"tokens": jax.ShapeDtypeStruct((B, S), i32),
                     "labels": jax.ShapeDtypeStruct((B, S), i32)}
        if cfg.family == "vlm":
            batch["frontend"] = jax.ShapeDtypeStruct(
                (B, cfg.n_frontend_tokens, cfg.d_model), cfg.dtype)
        return batch
    if shape.kind == "prefill":
        if cfg.family == "audio":
            batch = {"embeds": jax.ShapeDtypeStruct((B, S, cfg.d_model),
                                                    cfg.dtype)}
        else:
            batch = {"tokens": jax.ShapeDtypeStruct((B, S), i32)}
        if cfg.family == "vlm":
            batch["frontend"] = jax.ShapeDtypeStruct(
                (B, cfg.n_frontend_tokens, cfg.d_model), cfg.dtype)
        return batch
    # decode: one new token against a seq_len cache (cache specs built by
    # launch/serve.py via eval_shape of init_cache)
    if cfg.family == "audio":
        return {"embeds": jax.ShapeDtypeStruct((B, 1, cfg.d_model), cfg.dtype)}
    return {"tokens": jax.ShapeDtypeStruct((B, 1), i32)}
