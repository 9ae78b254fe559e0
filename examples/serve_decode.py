"""PIM-offloaded decode serving: session-resident weights, per-token matvec
offload, tokens/sec end to end (DESIGN.md §14).

Builds a small float32 decoder, pins every layer's q/k/v/o and MLP
projection matrices on the banks once (`DecodeEngine`), then drives
continuous multi-stream greedy decode — each stream a tenant of the
session's scheduler — and checks the generated tokens are identical to the
pure-JAX ``greedy_generate`` reference on the same params and prompt.

    PYTHONPATH=src python examples/serve_decode.py
    PYTHONPATH=src python examples/serve_decode.py --banks 8 --ranks 2 \
        --streams 4 --max-new 24
"""
import argparse
import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch import serve as serve_mod
from repro.launch.cli import cpu_rehearsal_env, enable_compile_cache
from repro.models import transformer
from repro.pim.decode import DecodeEngine
from repro.runtime.elastic import carve_mesh


def main(args):
    cfg = dataclasses.replace(get_config(args.model, smoke=True),
                              n_layers=args.layers, d_model=256, n_heads=8,
                              n_kv_heads=4, d_ff=512, vocab=256,
                              dtype=jnp.float32, fast_decode=True)
    params, specs = transformer.init(jax.random.PRNGKey(0), cfg)
    B, S, max_new = args.streams, args.prompt_len, args.max_new
    prompt = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab)

    mesh = carve_mesh(jax.devices(), model_parallel=1)
    ref = np.asarray(serve_mod.greedy_generate(params, cfg, mesh, specs,
                                               prompt, max_new=max_new))

    with DecodeEngine(params, cfg, ranks=args.ranks or None) as eng:
        print(f"decode engine: {eng.session.n_banks} bank(s), "
              f"{eng.session.n_ranks} rank(s), {cfg.n_layers} layers, "
              f"{len(eng.pins)} pinned projections "
              f"(setup {eng.setup_s * 1e3:.0f} ms)")
        out = eng.generate(np.asarray(prompt), max_new)
        rep = eng.report()
        cs = eng.session.stats().get("cache", {})

    for b in range(B):
        print(f"  stream-{b}: {out[b].tolist()}")
    assert (out == ref).all(), "PIM decode diverged from greedy_generate"
    print(f"token-identical to greedy_generate across {B} stream(s)")
    print(f"{rep['new_tokens']} new tokens at {rep['tokens_per_s']:.1f} "
          f"tok/s ({rep['time_per_output_token_s'] * 1e3:.1f} ms/token); "
          f"prefill {rep['prefill_s']:.2f}s, "
          f"cache hits {cs.get('hits', 0)} / misses {cs.get('misses', 0)}")
    print("per-step PIM phases (s):",
          {k: round(v, 3) for k, v in rep["pim_s"].items()})


if __name__ == "__main__":
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="tinyllama-1.1b",
                    help="arch id for the smoke config base")
    ap.add_argument("--banks", type=int, default=0,
                    help="re-exec with N forced host devices")
    ap.add_argument("--ranks", type=int, default=0,
                    help="rank count for rank-sharded matvecs (0 = flat)")
    ap.add_argument("--streams", type=int, default=4,
                    help="concurrent decode streams (stacked in each request)")
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=20)
    args = ap.parse_args()
    if args.banks:
        env = cpu_rehearsal_env(args.banks)
        cmd = [sys.executable, os.path.abspath(__file__)]
        for flag in ("model", "ranks", "streams", "layers", "prompt-len",
                     "max-new"):
            cmd += [f"--{flag}",
                    str(getattr(args, flag.replace("-", "_")))]
        raise SystemExit(subprocess.call(cmd, env=env))
    main(args)
