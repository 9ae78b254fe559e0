"""Decode-engine battery (DESIGN.md §14): token parity with the pure-JAX
``greedy_generate``, phase-tagged telemetry that reconciles with measured
wall time, and residency — warm decode steps move zero weight bytes.

The in-process tests share one module-scoped engine run (2 layers, 2
streams, traced session).  The multi-bank legs re-exec in a subprocess with
``--xla_force_host_platform_device_count=8`` like the other ``slow`` tests.
"""
import dataclasses
import os
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import pim
from repro.configs import get_config
from repro.launch import serve as serve_mod
from repro.models import transformer
from repro.models.pim_bridge import validate_decode_config
from repro.pim.decode import PIM_GROUPS, PROJ_WORKLOADS, TENANT, DecodeEngine
from repro.runtime.elastic import carve_mesh
from repro.runtime.trace import NULL_TRACER, set_tracer

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))

STREAMS, PROMPT, MAX_NEW = 2, 4, 6


def _tiny_cfg(layers=2):
    return dataclasses.replace(
        get_config("tinyllama-1.1b", smoke=True), n_layers=layers,
        d_model=128, n_heads=4, n_kv_heads=2, d_ff=256, vocab=256,
        dtype=jnp.float32, fast_decode=True)


def _spans(session, name):
    return [sp for sp in session.tracer.spans if sp.name == name]


@pytest.fixture(scope="module")
def decode_run():
    """One warm engine run: pin every projection, decode, close — the
    session's tracer spans and telemetry rows outlive the close."""
    cfg = _tiny_cfg()
    params, specs = transformer.init(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (STREAMS, PROMPT),
                                0, cfg.vocab)
    mesh = carve_mesh(jax.devices(), model_parallel=1)
    ref = np.asarray(serve_mod.greedy_generate(params, cfg, mesh, specs,
                                               prompt, max_new=MAX_NEW))
    s = pim.session(trace=True)
    try:
        eng = DecodeEngine(params, cfg, session=s)
        n_scatter_pin = len(_spans(s, "scatter"))
        out = eng.generate(np.asarray(prompt), MAX_NEW)
    finally:
        s.close()
        set_tracer(NULL_TRACER)
    return types.SimpleNamespace(cfg=cfg, eng=eng, session=s, out=out,
                                 ref=ref, n_scatter_pin=n_scatter_pin)


# -- parity -------------------------------------------------------------------

def test_tokens_identical_to_greedy_generate(decode_run):
    np.testing.assert_array_equal(decode_run.out, decode_run.ref)
    assert decode_run.out.shape == (STREAMS, PROMPT + MAX_NEW)
    assert decode_run.out.dtype == np.int32


def test_report_counts_generation_steps_only(decode_run):
    rep = decode_run.eng.report()
    assert rep["steps"] == PROMPT + MAX_NEW - 1
    assert rep["new_tokens"] == STREAMS * MAX_NEW
    assert rep["tokens_per_s"] > 0
    assert rep["time_per_output_token_s"] * rep["new_tokens"] == pytest.approx(
        rep["generate_s"])
    assert rep["setup_s"] > 0                       # the pin pass was timed
    assert set(rep["pim_s"]) == set(PIM_GROUPS)


# -- phase accounting: tagged telemetry vs engine-measured wall ---------------

def test_every_step_wall_is_covered_by_pim_plus_host_phases(decode_run):
    for sr in decode_run.eng.steps:
        accounted = sum(sr.pim_s.values()) + sr.host_s
        tol = 0.25 * sr.wall_s + 5e-3
        assert abs(accounted - sr.wall_s) <= tol, (sr.step, accounted,
                                                   sr.wall_s)


def test_telemetry_rows_tag_every_layer_and_projection(decode_run):
    cfg, eng = decode_run.cfg, decode_run.eng
    want = {(li, p) for li in range(cfg.n_layers) for p in PROJ_WORKLOADS}
    assert set(eng.proj_seconds()) == want
    assert all(v >= 0 for v in eng.proj_seconds().values())
    n_banks = decode_run.session.n_banks
    rows = [r.row(n_banks) for r in decode_run.session.telemetry.records]
    tagged = [r for r in rows if "tag_proj" in r]
    # every step submits one request per projection x n_layers, each
    # carrying every stream's vector, under the engine's tenant
    assert len(tagged) == ((PROMPT + MAX_NEW - 1) * cfg.n_layers
                           * len(PROJ_WORKLOADS))
    assert {r["tag_proj"] for r in tagged} == set(PROJ_WORKLOADS)
    assert {r["tag_layer"] for r in tagged} == set(range(cfg.n_layers))
    for r in tagged:
        assert r["workload"] == PROJ_WORKLOADS[r["tag_proj"]]
        assert r["tag_streams"] == STREAMS
        assert r["tenant"] == TENANT
    for sr in eng.steps:
        assert sr.requests == cfg.n_layers * len(PROJ_WORKLOADS)
        assert sr.matvecs == STREAMS * sr.requests


def test_serve_spans_carry_the_phase_tags(decode_run):
    serves = _spans(decode_run.session, "serve")
    tagged = [sp for sp in serves if "proj" in sp.args]
    assert tagged, "no tagged serve spans"
    assert {sp.args["proj"] for sp in tagged} == set(PROJ_WORKLOADS)
    assert all(sp.args["tenant"] == TENANT and sp.args["streams"] == STREAMS
               for sp in tagged)


# -- residency: warm steps move activations only ------------------------------

def test_warm_steps_emit_zero_weight_scatter_bytes(decode_run):
    s = decode_run.session
    # pin() places chunks outside the request path (no spans); after it,
    # every decode step serves weights from the banks — zero scatter spans
    assert decode_run.n_scatter_pin == 0
    assert not _spans(s, "scatter")
    cached = _spans(s, "scatter_cached")
    assert cached, "warm steps should serve weights from the banks"
    assert sum(sp.args["bytes"] for sp in cached) > 0
    cs = s.stats()["cache"]
    assert cs["misses"] == len(decode_run.eng.pins)      # pins only
    assert cs["hits"] >= (PROMPT + MAX_NEW - 1) * len(decode_run.eng.pins)


def test_cold_engine_rescatters_weights_every_step():
    """The bench's cold leg: resident=False disables the cache, so every
    step pushes every weight again — same tokens, orders more bytes."""
    cfg = _tiny_cfg(layers=1)
    params, _ = transformer.init(jax.random.PRNGKey(0), cfg)
    prompt = np.asarray([[1, 2]], np.int32)
    s = pim.session(trace=True, resident=False)
    try:
        eng = DecodeEngine(params, cfg, session=s)
        assert eng.pins == [] and eng.setup_s == 0.0     # nothing to pin
        out = eng.generate(prompt, 2)
    finally:
        s.close()
        set_tracer(NULL_TRACER)
    assert out.shape == (1, 4)
    assert not _spans(s, "scatter_cached")
    steps = len(eng.steps)
    weight_nbytes = sum(
        sum(a.nbytes for a in h.value.values())
        for h in eng.handles.values())
    scattered = sum(sp.args["bytes"] for sp in _spans(s, "scatter"))
    assert scattered >= steps * weight_nbytes


# -- bridge contract ----------------------------------------------------------

@pytest.mark.parametrize("arch,match", [
    ("stablelm-12b", "parallel_block"),
    ("xlstm-125m", "mixer"),
    ("deepseek-moe-16b", "ffn"),
])
def test_bridge_rejects_out_of_contract_archs(arch, match):
    cfg = get_config(arch, smoke=True)
    with pytest.raises(ValueError, match=match):
        validate_decode_config(cfg)


def test_bridge_rejects_non_float32_params():
    cfg = dataclasses.replace(_tiny_cfg(), dtype=jnp.bfloat16)
    with pytest.raises(ValueError, match="float32"):
        validate_decode_config(cfg)


# -- 8 banks / 2 ranks: parity + residency in a real multi-device run ---------

SCRIPT8 = r"""
import sys; sys.path.insert(0, {src!r})
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro import pim
from repro.configs import get_config
from repro.launch import serve as serve_mod
from repro.models import transformer
from repro.runtime.elastic import carve_mesh
cfg = dataclasses.replace(get_config("tinyllama-1.1b", smoke=True),
                          n_layers=2, d_model=128, n_heads=4, n_kv_heads=2,
                          d_ff=256, vocab=256, dtype=jnp.float32,
                          fast_decode=True)
params, specs = transformer.init(jax.random.PRNGKey(0), cfg)
prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 4), 0, cfg.vocab)
mesh = carve_mesh(jax.devices(), model_parallel=1)
ref = np.asarray(serve_mod.greedy_generate(params, cfg, mesh, specs,
                                           prompt, max_new=6))
s = pim.session(ranks=2, banks_per_rank=4, trace=True)
eng = pim.DecodeEngine(params, cfg, session=s)
out = eng.generate(np.asarray(prompt), 6)
np.testing.assert_array_equal(out, ref)
n_scatter = sum(1 for sp in s.tracer.spans if sp.name == "scatter")
assert n_scatter == 0, n_scatter                   # decode pushed no weights
assert any(sp.name == "scatter_cached" for sp in s.tracer.spans)
recs = [r for r in s.telemetry.records if r.tags.get("proj")]
assert recs and all(r.n_ranks == 2 for r in recs)
s.close()
print("DECODE8-OK", flush=True)
"""


@pytest.mark.slow
def test_decode_parity_8_banks_2_ranks():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    env.pop("REPRO_TRACE", None)
    out = subprocess.run([sys.executable, "-c", SCRIPT8.format(src=SRC)],
                         env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "DECODE8-OK" in out.stdout
