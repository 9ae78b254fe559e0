"""Multi-bank agreement: the full PrIM suite + banked exchanges on 8
placeholder devices, run in a subprocess (device count locks at jax init, so
the flag can't be set in-process)."""
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import sys; sys.path.insert(0, {src!r})
import numpy as np
from repro.core import make_bank_grid
from repro import prim
g = make_bank_grid()
assert g.n_banks == 8, g.n_banks
rng = np.random.default_rng(3)

a = rng.integers(0, 100, 1003).astype(np.int32); b = rng.integers(0, 100, 1003).astype(np.int32)
out, _ = prim.va.pim(g, a, b); assert (out == prim.va.ref(a, b)).all()
A = rng.normal(size=(67, 32)).astype(np.float32); x = rng.normal(size=32).astype(np.float32)
out, _ = prim.gemv.pim(g, A, x); np.testing.assert_allclose(out, prim.gemv.ref(A, x), rtol=1e-4, atol=1e-4)
x = rng.integers(0, 1000, 509).astype(np.int32)
out, _ = prim.sel.pim(g, x); assert (out == prim.sel.ref(x)).all()
x = np.sort(rng.integers(0, 50, 515)).astype(np.int32)
out, _ = prim.uni.pim(g, x); assert (out == prim.uni.ref(x)).all()
adj = prim.bfs.random_graph(101, 3)
out, _ = prim.bfs.pim(g, adj, 0); assert (out == prim.bfs.ref(adj, 0)).all()
s1 = rng.integers(0, 4, 33).astype(np.int32); s2 = rng.integers(0, 4, 47).astype(np.int32)
out, _ = prim.nw.pim(g, s1, s2, block=8); assert (out == prim.nw.ref(s1, s2)).all()
px = rng.integers(0, 256, 5003).astype(np.int32)
out, _ = prim.hist.pim_short(g, px); assert (out == prim.hist.ref(px, 256)).all()
x = rng.integers(0, 100, 5001).astype(np.int32)
for via in ("host", "fabric"):
    out, _ = prim.red.pim(g, x, via=via); assert out == prim.red.ref(x)
    s, _ = prim.scan.pim_rss(g, x, via=via); assert (s == prim.scan.ref(x)).all()
    s, _ = prim.scan.pim_ssa(g, x, via=via); assert (s == prim.scan.ref(x)).all()
xm = rng.normal(size=(64, 64)).astype(np.float32)
out, _ = prim.trns.pim(g, xm, m=8, n=8); assert (out == prim.trns.ref(xm)).all()

# bank-local phases must not lower to collectives even at 8 banks
from repro.core import assert_collective_free
dx = g.to_banks(np.arange(64, dtype=np.int32))
assert_collective_free(g.bank_local(lambda v: v * 3), dx)
print("MULTIBANK-OK")
"""


@pytest.mark.slow
def test_prim_on_8_banks():
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    out = subprocess.run([sys.executable, "-c", SCRIPT.format(src=src)],
                         env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "MULTIBANK-OK" in out.stdout


EP_SCRIPT = r"""
import sys; sys.path.insert(0, "__SRC__")
import jax, jax.numpy as jnp, numpy as np
from repro.models import moe
from repro.models.layers import ModelConfig
mesh = jax.make_mesh((2, 4), ("data", "model"))
cfg = ModelConfig(d_model=32, d_ff=16, moe_experts=8, moe_top_k=2,
                  moe_capacity_factor=8.0, dtype=jnp.float32)
params, _ = moe.init(jax.random.PRNGKey(0), cfg)
x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32), jnp.float32)
y0, _ = moe.apply(params, cfg, x)
with jax.set_mesh(mesh):
    y1, _ = jax.jit(lambda p, xx: moe.apply_ep(p, cfg, xx))(params, x)
    g2 = jax.jit(jax.grad(lambda p: moe.apply_ep(p, cfg, x)[0].sum()
                          .astype(jnp.float32)))(params)
np.testing.assert_allclose(np.asarray(y0), np.asarray(y1), rtol=2e-4, atol=2e-4)
g = jax.grad(lambda p: moe.apply(p, cfg, x)[0].sum().astype(jnp.float32))(params)
for k in ("router", "wi", "wo"):
    np.testing.assert_allclose(np.asarray(g[k], np.float32),
                               np.asarray(g2[k], np.float32),
                               rtol=5e-3, atol=5e-3)

# elastic: carve a degraded mesh (8 -> 6 devices) and reshard a tree onto it
from repro.runtime.elastic import carve_mesh, reshard, simulate_failure
from jax.sharding import PartitionSpec as P
m8 = carve_mesh(jax.devices(), model_parallel=2)
m6 = simulate_failure(m8, n_lost=2, model_parallel=2)
assert m6.devices.size == 6
tree = {"w": jnp.arange(24.0).reshape(12, 2)}
out = reshard(tree, m6, {"w": P("data", "model")})
np.testing.assert_allclose(np.asarray(out["w"]), np.asarray(tree["w"]))
print("EP-ELASTIC-OK")
"""


@pytest.mark.slow
def test_moe_ep_and_elastic_on_8_devices():
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    out = subprocess.run([sys.executable, "-c",
                          EP_SCRIPT.replace("__SRC__", src)],
                         env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "EP-ELASTIC-OK" in out.stdout
