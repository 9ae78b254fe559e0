"""Distributed train step + driver loop.

``make_train_step`` builds a jit'd (params, opt_state, batch) → (params,
opt_state, metrics) step with:
  * batch sharded over ("pod","data"), params/opt by the model's spec tree
    (tensor/expert parallel over "model"; FSDP over "data" when cfg.fsdp);
  * gradient-accumulation microbatching (``microbatches`` > 1): per-microbatch
    gradients are summed by a lax.scan, letting XLA overlap each microbatch's
    gradient collectives with the next microbatch's compute;
  * optional int8 gradient compression (``compress_grads``) via a shard_map
    data-parallel wrapper — pure-DP meshes only (model axis 1), 4× less
    gradient wire traffic (optim/adamw.psum_compressed).

The driver loop (``fit``) wires in the production substrate: checkpointing
(atomic + async), straggler monitoring, deterministic seekable data, and
elastic restart (restore onto whatever mesh is alive).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import optim
from repro.models import transformer
from repro.models.layers import ModelConfig
from repro.runtime.elastic import shardings_for
from .mesh import data_axes


def batch_specs(cfg: ModelConfig, mesh) -> dict:
    dp = data_axes(mesh)
    spec = {"labels": P(dp, None)}
    if cfg.family == "audio":
        spec["embeds"] = P(dp, None, None)
    else:
        spec["tokens"] = P(dp, None)
    if cfg.family == "vlm":
        spec["frontend"] = P(dp, None, None)
    return spec


def init_state(key, cfg: ModelConfig, mesh):
    """Materialize sharded params + optimizer state on the mesh."""
    box = {}

    def make(k):
        p, s = transformer.init(k, cfg)
        box["specs"] = s
        return p, optim.init(p)

    shapes = jax.eval_shape(make, key)
    specs = box["specs"]
    opt_specs = opt_state_specs(specs)
    sh = (shardings_for(mesh, specs), shardings_for(mesh, opt_specs))
    params, opt_state = jax.jit(make, out_shardings=sh)(key)
    return params, opt_state, specs


def opt_state_specs(param_specs) -> dict:
    return {"master": param_specs, "mu": param_specs, "nu": param_specs,
            "step": P()}


def make_train_step(cfg: ModelConfig, ocfg: optim.AdamWConfig, mesh,
                    param_specs, *, microbatches: int = 1,
                    use_kernel: bool = False, compress_grads: bool = False,
                    loss_chunks: int = 0, donate: bool = True):
    dp = data_axes(mesh)

    def loss(p, b):
        return transformer.loss_fn(p, cfg, b, use_kernel=use_kernel,
                                   loss_chunks=loss_chunks)

    def grads_of(params, batch):
        if microbatches == 1:
            return jax.value_and_grad(loss, has_aux=True)(params, batch)

        def mb(carry, b):
            (l, a), g = jax.value_and_grad(loss, has_aux=True)(params, b)
            gsum, lsum = carry
            return (jax.tree.map(jnp.add, gsum, g), lsum + l), a

        split = jax.tree.map(
            lambda x: x.reshape(microbatches, x.shape[0] // microbatches,
                                *x.shape[1:]), batch)
        zero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        # unrolled when layers are unrolled (the dry-run cost path): XLA's
        # cost_analysis counts a while body once, which would hide mb-1
        # microbatches of work
        (g, lsum), aux = jax.lax.scan(mb, (zero, jnp.zeros((), jnp.float32)),
                                      split,
                                      unroll=microbatches
                                      if not cfg.scan_layers else 1)
        g = jax.tree.map(lambda x: x / microbatches, g)
        return (lsum / microbatches, jax.tree.map(lambda a: a[-1], aux)), g

    def step(params, opt_state, batch):
        (l, aux), g = grads_of(params, batch)
        if compress_grads:
            g = _compressed_dp_grads(g, mesh)
        params, opt_state, om = optim.apply(ocfg, g, opt_state, params)
        metrics = {"loss": l, **om}
        return params, opt_state, metrics

    psh = shardings_for(mesh, param_specs)
    osh = shardings_for(mesh, opt_state_specs(param_specs))
    bsh = shardings_for(mesh, batch_specs(cfg, mesh))
    return jax.jit(
        step,
        in_shardings=(psh, osh, bsh),
        out_shardings=(psh, osh, None),
        donate_argnums=(0, 1) if donate else (),
    )


def _compressed_dp_grads(g, mesh):
    """int8-compress the data-axis gradient reduction (pure-DP meshes)."""
    if mesh.shape.get("model", 1) != 1:
        raise ValueError("compress_grads requires model axis of size 1")
    dp = data_axes(mesh)
    axis = dp if isinstance(dp, str) else dp[-1]
    f = jax.shard_map(
        lambda t: optim.psum_compressed(
            jax.tree.map(lambda x: x / mesh.shape[axis], t), axis),
        mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False)
    return f(g)


def shard_batch(batch: dict, cfg: ModelConfig, mesh):
    sh = shardings_for(mesh, batch_specs(cfg, mesh))
    return jax.tree.map(lambda x, s: jax.device_put(x, s), batch, sh)


def fit(cfg: ModelConfig, *, mesh, steps: int, data_loader,
        ocfg: optim.AdamWConfig | None = None, seed: int = 0,
        checkpointer=None, checkpoint_every: int = 0, monitor=None,
        microbatches: int = 1, use_kernel: bool = False, log_every: int = 10,
        log=print):
    """End-to-end training driver with restart support."""
    ocfg = ocfg or optim.AdamWConfig(total_steps=steps)
    key = jax.random.PRNGKey(seed)
    params, opt_state, specs = init_state(key, cfg, mesh)
    start = 0
    if checkpointer is not None and checkpointer.latest_step() is not None:
        tree, man = checkpointer.restore(shardings={
            "params": shardings_for(mesh, specs),
            "opt": shardings_for(mesh, opt_state_specs(specs))})
        params, opt_state = tree["params"], tree["opt"]
        start = man["step"]
        log(f"[train] resumed from step {start}")
    step_fn = make_train_step(cfg, ocfg, mesh, specs,
                              microbatches=microbatches,
                              use_kernel=use_kernel)
    data_loader.step = start
    history = []
    for i in range(start, steps):
        batch = shard_batch(next(data_loader), cfg, mesh)
        if monitor:
            monitor.start_step()
        params, opt_state, m = step_fn(params, opt_state, batch)
        jax.block_until_ready(m["loss"])
        if monitor:
            monitor.end_step(i)
        history.append(float(m["loss"]))
        if log_every and (i % log_every == 0 or i == steps - 1):
            log(f"[train] step {i} loss {float(m['loss']):.4f} "
                f"lr {float(m['lr']):.2e} gnorm {float(m['grad_norm']):.3f}")
        if checkpointer is not None and checkpoint_every and \
                (i + 1) % checkpoint_every == 0:
            checkpointer.save(i + 1, {"params": params, "opt": opt_state})
    if checkpointer is not None:
        checkpointer.wait()
    return params, opt_state, history
