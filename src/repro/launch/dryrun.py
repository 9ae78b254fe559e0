import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the device
# count at first init).  This module is the ONLY place that forces 512
# placeholder devices — tests and benchmarks see the real device count.

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this proves the distribution config is coherent without real
hardware:   jax.jit(step, in_shardings, out_shardings).lower(*specs)
            .compile()  → memory_analysis() (fits?) + cost_analysis()
            (FLOPs/bytes) + collective bytes parsed from the optimized HLO.

Results are written as JSON records under ``experiments/dryrun/`` and are the
single source for EXPERIMENTS.md §Dry-run.

Usage:
  python -m repro.launch.dryrun --arch tinyllama-1.1b --shape train_4k
  python -m repro.launch.dryrun --all [--mesh single|multi|both]
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax
import numpy as np

from repro import optim
from repro.configs import (ARCHS, SHAPES, get_config, input_specs,
                           skip_reason)
from repro.core import hlo as hlo_mod
from repro.core import perfmodel as perf_mod
from repro.core.perfmodel import (RooflineTerms, model_flops_decode,
                                  model_flops_train)
from repro.launch import serve as serve_mod
from repro.launch import train as train_mod
from repro.launch.mesh import make_production_mesh
from repro.models import transformer
from repro.runtime.elastic import shardings_for

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def _param_shapes_and_specs(cfg):
    box = {}

    def f(k):
        p, s = transformer.init(k, cfg)
        box["specs"] = s
        return p

    shapes = jax.eval_shape(f, jax.random.PRNGKey(0))
    return shapes, box["specs"]


def _strip_model_axis(specs):
    """opt flag tp1: drop tensor parallelism (pure DP) from a spec tree."""
    def fix(s):
        return type(s)(*[None if p == "model" else p for p in tuple(s)])
    import jax.sharding as shd
    return jax.tree.map(fix, specs,
                        is_leaf=lambda s: isinstance(s, shd.PartitionSpec))


def apply_opt_flags(cfg, pspecs, opt_flags):
    """§Perf hillclimb levers (see EXPERIMENTS.md §Perf for the log):
      microbatch    4-way gradient accumulation (comm/compute overlap)
      chunked_loss  streaming vocab-chunked CE (no (B,S,V) materialization)
      remat_dots    save MXU outputs in remat (less recompute)
      tp1           drop tensor parallelism (pure DP)
      nofsdp        disable FSDP param sharding
    """
    if "remat_dots" in opt_flags:
        cfg = dataclasses.replace(cfg, remat_policy="dots")
    if "nofsdp" in opt_flags:
        cfg = dataclasses.replace(cfg, fsdp=False)
    if "fast_decode" in opt_flags:
        cfg = dataclasses.replace(cfg, fast_decode=True)
    if "moe_shard" in opt_flags:
        cfg = dataclasses.replace(cfg, moe_dispatch_sharded=True)
    if "chunked_mlstm" in opt_flags:
        cfg = dataclasses.replace(cfg, mlstm_chunk=256)
    if "cap1" in opt_flags:
        cfg = dataclasses.replace(cfg, moe_capacity_factor=1.0)
    if "moe_ep" in opt_flags:
        cfg = dataclasses.replace(cfg, moe_ep=True)
    if "tp1" in opt_flags or "dp_all" in opt_flags:
        pspecs = _strip_model_axis(pspecs)
    return cfg, pspecs


def lower_cell(cfg, shape, mesh, *, opt_flags=()):
    """Lower + compile one cell; returns (lowered, compiled, meta)."""
    cfg, _ = apply_opt_flags(cfg, {}, opt_flags)
    pshapes, pspecs = _param_shapes_and_specs(cfg)
    _, pspecs = apply_opt_flags(cfg, pspecs, opt_flags)
    bspecs_tree = input_specs(cfg, shape)

    with jax.set_mesh(mesh):
        if shape.kind == "train":
            oshapes = jax.eval_shape(optim.init, pshapes)
            ocfg = optim.AdamWConfig()
            mb = 4 if "microbatch" in opt_flags else 1
            lc = 16 if "chunked_loss" in opt_flags else 0
            step = train_mod.make_train_step(
                cfg, ocfg, mesh, pspecs, microbatches=mb, loss_chunks=lc,
                donate=True)
            bsh = shardings_for(mesh, train_mod.batch_specs(cfg, mesh))
            binputs = jax.tree.map(
                lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                                   sharding=sh),
                bspecs_tree, bsh)
            lowered = step.lower(pshapes, oshapes, binputs)
        elif shape.kind == "prefill":
            def fwd(p, b):
                logits, _ = transformer.forward(
                    p, cfg, tokens=b.get("tokens"), embeds=b.get("embeds"),
                    frontend=b.get("frontend"))
                return logits
            psh = shardings_for(mesh, pspecs)
            bspec_tree = train_mod.batch_specs(cfg, mesh)
            if "dp_all" in opt_flags:   # fold batch over the idle model axis
                from jax.sharding import PartitionSpec as P
                from repro.launch.mesh import data_axes
                dp = data_axes(mesh)
                dpa = (dp, "model") if isinstance(dp, str) else dp + ("model",)
                bspec_tree = {k: P(dpa, *tuple(v)[1:])
                              for k, v in bspec_tree.items()}
            bsh = shardings_for(
                mesh, {k: v for k, v in bspec_tree.items()
                       if k in bspecs_tree})
            step = jax.jit(fwd, in_shardings=(psh, bsh))
            binputs = jax.tree.map(
                lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                                   sharding=sh),
                bspecs_tree, bsh)
            lowered = step.lower(pshapes, binputs)
        else:  # decode
            B = shape.batch
            fr = None
            if cfg.family == "vlm":
                fr = jax.ShapeDtypeStruct(
                    (B, cfg.n_frontend_tokens, cfg.d_model), cfg.dtype)
            cshapes = jax.eval_shape(
                lambda p, f: transformer.init_cache(p, cfg, B, shape.seq,
                                                    frontend=f),
                pshapes, fr)
            cspecs = serve_mod.cache_specs(cshapes, mesh)
            step = serve_mod.make_serve_step(cfg, mesh, pspecs, cspecs,
                                             batch=B, donate=True)
            csh = shardings_for(mesh, cspecs)
            cinputs = jax.tree.map(
                lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                                   sharding=sh),
                cshapes, csh)
            toks = emb = None
            if cfg.family == "audio":
                emb = jax.ShapeDtypeStruct((B, 1, cfg.d_model), cfg.dtype)
            else:
                toks = jax.ShapeDtypeStruct((B, 1), jax.numpy.int32)
            lowered = step.lower(pshapes, cinputs, toks, emb, fr)

    compiled = lowered.compile()
    return lowered, compiled, {"params": pshapes}


def _cell_costs(compiled) -> dict:
    cost = hlo_mod.cost_summary(compiled)
    coll = hlo_mod.collective_stats(compiled.as_text())
    return {"flops": cost["flops"], "bytes": cost["bytes"],
            "operand_bytes": coll.operand_bytes,
            "wire_bytes": coll.wire_bytes, "count": coll.count,
            "by_kind": coll.by_kind}


def _reduced(cfg, r: int):
    pro, period, repeats = transformer.layer_plan(cfg)
    return dataclasses.replace(cfg, n_layers=len(pro) + len(period) * r,
                               scan_layers=False)


def extrapolated_costs(cfg, shape, mesh, opt_flags=()) -> dict:
    """Exact per-device costs: XLA cost_analysis counts a lax.scan body once,
    so we lower UNROLLED reduced models at R=1 and R=2 repeats and extend
    linearly to the full depth (exact, since the repeating group is
    homogeneous by construction)."""
    pro, period, repeats = transformer.layer_plan(cfg)
    if repeats <= 2:
        _, compiled, _ = lower_cell(_reduced(cfg, repeats), shape, mesh,
                                    opt_flags=opt_flags)
        return _cell_costs(compiled)
    _, c1, _ = lower_cell(_reduced(cfg, 1), shape, mesh, opt_flags=opt_flags)
    _, c2, _ = lower_cell(_reduced(cfg, 2), shape, mesh, opt_flags=opt_flags)
    a, b = _cell_costs(c1), _cell_costs(c2)

    def lin(x, y):
        return x + (y - x) * (repeats - 1)

    by_kind = {}
    for k in set(a["by_kind"]) | set(b["by_kind"]):
        ka = a["by_kind"].get(k, {"bytes": 0.0, "count": 0})
        kb = b["by_kind"].get(k, {"bytes": 0.0, "count": 0})
        by_kind[k] = {"bytes": lin(ka["bytes"], kb["bytes"]),
                      "count": lin(ka["count"], kb["count"])}
    return {key: lin(a[key], b[key])
            for key in ("flops", "bytes", "operand_bytes", "wire_bytes",
                        "count")} | {"by_kind": by_kind}


def _cache_bytes(cfg, shape) -> float:
    pshapes, _ = _param_shapes_and_specs(cfg)
    fr = None
    if cfg.family == "vlm":
        fr = jax.ShapeDtypeStruct(
            (shape.batch, cfg.n_frontend_tokens, cfg.d_model), cfg.dtype)
    cshapes = jax.eval_shape(
        lambda p, f: transformer.init_cache(p, cfg, shape.batch, shape.seq,
                                            frontend=f), pshapes, fr)
    return float(sum(np.prod(a.shape) * a.dtype.itemsize
                     for a in jax.tree.leaves(cshapes)))


def analyse(cfg, shape, mesh, compiled, costs: dict) -> dict:
    chips = int(np.prod(list(mesh.shape.values())))
    mem = hlo_mod.memory_summary(compiled)
    tokens = shape.batch * shape.seq
    if shape.kind == "train":
        mflops = model_flops_train(cfg.active_params(), tokens)
        mbytes = perf_mod.min_hbm_bytes_train(cfg, tokens)
    elif shape.kind == "prefill":
        mflops = model_flops_decode(cfg.active_params(), tokens)
        mbytes = perf_mod.min_hbm_bytes_prefill(cfg, tokens)
    else:
        mflops = model_flops_decode(cfg.active_params(), shape.batch)
        mbytes = perf_mod.min_hbm_bytes_decode(cfg, shape.batch,
                                               _cache_bytes(cfg, shape))
    terms = RooflineTerms(flops=costs["flops"] * chips,
                          hbm_bytes=costs["bytes"] * chips,
                          collective_bytes=costs["operand_bytes"] * chips,
                          chips=chips, model_flops=mflops,
                          model_bytes=mbytes)
    return {
        "arch": cfg.name, "shape": shape.name,
        "mesh": "x".join(str(mesh.shape[a]) for a in mesh.axis_names),
        "chips": chips,
        "cost_per_device": {"flops": costs["flops"],
                            "bytes": costs["bytes"]},
        "memory_per_device": mem,
        "hbm_ok": bool(mem["total_per_device"] <= 16 * 2**30),
        "collectives": {"operand_bytes": costs["operand_bytes"],
                        "wire_bytes": costs["wire_bytes"],
                        "count": costs["count"], "by_kind": costs["by_kind"]},
        "roofline": terms.row(),
    }


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             opt_flags=(), out_dir: str | None = None, verbose=True) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh_name = "2x16x16" if multi_pod else "16x16"
    skip = skip_reason(cfg, shape)
    rec: dict
    if skip:
        rec = {"arch": cfg.name, "shape": shape.name, "mesh": mesh_name,
               "status": skip}
    else:
        t0 = time.time()
        mesh = make_production_mesh(multi_pod=multi_pod)
        lowered, compiled, _ = lower_cell(cfg, shape, mesh,
                                          opt_flags=opt_flags)
        costs = extrapolated_costs(cfg, shape, mesh, opt_flags=opt_flags)
        rec = analyse(cfg, shape, mesh, compiled, costs)
        rec["status"] = "OK"
        rec["compile_seconds"] = time.time() - t0
        if verbose:
            print(compiled.memory_analysis())
            ca = compiled.cost_analysis()
            print({k: ca[k] for k in ("flops", "bytes accessed")
                   if k in ca})
    if verbose:
        print(json.dumps({k: v for k, v in rec.items()
                          if k in ("arch", "shape", "mesh", "status")},
                         indent=None))
    out_dir = out_dir or OUT_DIR
    os.makedirs(out_dir, exist_ok=True)
    tag = "opt-" + "-".join(opt_flags) + "_" if opt_flags else ""
    fname = f"{tag}{cfg.name}_{shape.name}_{mesh_name}.json".replace("/", "_")
    with open(os.path.join(out_dir, fname), "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--opt", default="", help="comma-joined opt flags")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    archs = ARCHS if args.all or not args.arch else [args.arch]
    shapes = list(SHAPES) if args.all or not args.shape else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    opt_flags = tuple(f for f in args.opt.split(",") if f)

    failures = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                label = f"{arch} × {shape} × {'2x16x16' if mp else '16x16'}"
                try:
                    rec = run_cell(arch, shape, mp, opt_flags=opt_flags,
                                   out_dir=args.out)
                    print(f"[dryrun] {label}: {rec['status']}")
                except Exception as e:
                    failures.append((label, repr(e)))
                    traceback.print_exc()
                    print(f"[dryrun] {label}: FAIL {e}")
    if failures:
        raise SystemExit(f"{len(failures)} dry-run failures: "
                         + "; ".join(l for l, _ in failures))
    print("[dryrun] all requested cells compiled OK")


if __name__ == "__main__":
    main()
