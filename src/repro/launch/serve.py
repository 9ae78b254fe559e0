"""Serving: batched decode step with mesh-aware cache sharding.

Cache sharding rule (per leaf, greedy): give "data" (or ("pod","data")) the
largest divisible dim — the batch dim for batched decode, the *sequence* dim
for long-context batch-1 decode (ring-style KV sharding) — then give "model"
the next largest divisible dim (heads / head_dim / state).  This one rule
covers every (arch × decode shape) cell, including long_500k.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models import transformer
from repro.models.layers import ModelConfig
from repro.runtime.elastic import shardings_for
from .mesh import axis_size, data_axes


def cache_spec_for(shape: tuple[int, ...], ndata: int, nmodel: int,
                   dp, skip_dim0: bool = False) -> P:
    parts: list = [None] * len(shape)
    order = sorted(range(1 if skip_dim0 else 0, len(shape)),
                   key=lambda i: -shape[i])
    for ax_name, ax_size in ((dp, ndata), ("model", nmodel)):
        for i in order:
            if parts[i] is None and shape[i] >= ax_size and \
                    shape[i] % ax_size == 0 and ax_size > 1:
                parts[i] = ax_name
                break
    return P(*parts)


def cache_specs(cache_shapes, mesh):
    """Spec tree for an eval_shape'd cache pytree."""
    dp = data_axes(mesh)
    nd = axis_size(mesh, dp)
    nm = mesh.shape.get("model", 1)

    def leaf(path, a):
        skip = path and path[0] == "group"   # don't shard the scan axis
        if a.ndim == 0:
            return P()
        return cache_spec_for(a.shape, nd, nm, dp, skip_dim0=skip)

    return _map_with_path(leaf, cache_shapes)


def _map_with_path(fn, tree, path=()):
    if isinstance(tree, dict):
        return {k: _map_with_path(fn, v, path + (k,)) for k, v in tree.items()}
    if isinstance(tree, (list, tuple)):
        t = [_map_with_path(fn, v, path + (i,)) for i, v in enumerate(tree)]
        return type(tree)(t) if isinstance(tree, tuple) else t
    return fn(path, tree)


def make_cache(params, cfg: ModelConfig, mesh, batch: int, max_len: int,
               frontend=None):
    """Materialize a sharded decode cache."""
    shapes = jax.eval_shape(
        lambda p, f: transformer.init_cache(p, cfg, batch, max_len,
                                            frontend=f), params, frontend)
    specs = cache_specs(shapes, mesh)
    sh = shardings_for(mesh, specs)
    cache = jax.jit(
        lambda p, f: transformer.init_cache(p, cfg, batch, max_len,
                                            frontend=f),
        out_shardings=sh)(params, frontend)
    return cache, specs


def make_serve_step(cfg: ModelConfig, mesh, param_specs, cache_specs_tree,
                    *, batch: int = 0, donate: bool = True):
    dp = data_axes(mesh)
    # batch=1 long-context decode cannot batch-shard its inputs: replicate
    # them (the KV cache itself is sequence-sharded by cache_specs)
    bp = dp if batch and batch % axis_size(mesh, dp) == 0 else None

    def step(params, cache, tokens=None, embeds=None, frontend=None):
        logits, cache = transformer.decode_step(params, cfg, tokens, cache,
                                                embeds=embeds,
                                                frontend=frontend)
        return logits, cache

    psh = shardings_for(mesh, param_specs)
    csh = shardings_for(mesh, cache_specs_tree)
    tok_sh = NamedSharding(mesh, P(bp, None)) if cfg.family != "audio" else None
    emb_sh = NamedSharding(mesh, P(bp, None, None)) if cfg.family == "audio" \
        else None
    fr_sh = NamedSharding(mesh, P(bp, None, None)) if cfg.family == "vlm" \
        else None
    return jax.jit(
        step,
        in_shardings=(psh, csh, tok_sh, emb_sh, fr_sh),
        out_shardings=(None, csh),
        donate_argnums=(1,) if donate else (),
    )


def greedy_generate(params, cfg: ModelConfig, mesh, param_specs, prompt,
                    max_new: int, frontend=None):
    """Simple batched greedy decoding driver — the reference the PIM
    decode engine is checked against token for token.  Matmuls run at
    "highest" precision, as the engine's do: on a TPU the default computes
    a float32 matmul in bfloat16 passes."""
    B, S = prompt.shape
    cache, cspecs = make_cache(params, cfg, mesh, B, S + max_new,
                               frontend=frontend)
    step = make_serve_step(cfg, mesh, param_specs, cspecs, batch=B,
                           donate=False)
    # prefill token-by-token (simple; a fused prefill is the perf path)
    tok = prompt[:, :1]
    out = [tok]
    with jax.default_matmul_precision("highest"):
        for i in range(S + max_new - 1):
            logits, cache = step(params, cache, tok, None,
                                 frontend if cfg.family == "vlm" else None)
            if i + 1 < S:
                tok = prompt[:, i + 1:i + 2]
            else:
                tok = jnp.argmax(logits[:, -1:, :],
                                 axis=-1).astype(jnp.int32)
            out.append(tok)
    return jnp.concatenate(out, axis=1)
