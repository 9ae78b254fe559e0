"""``chip_smoke.py`` rehearsed on the CPU at tiny sizes, and the library's
import-time contract: importing ``repro`` touches no device.

The script itself refuses any device but a TPU, so these tests drive its
phase functions directly: the one-chip phases in-process, the ``--chips 4``
phases in a subprocess with 4 forced host devices.
"""
import os
import subprocess
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
SRC = os.path.join(ROOT, "src")
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

TINY = dict(scale=1, serial_scales={"NW": 1, "BFS": 1})


def _run(code: str, **env) -> subprocess.CompletedProcess:
    return subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          env=dict(os.environ, PYTHONPATH=SRC, **env),
                          capture_output=True, text=True, timeout=900)


@pytest.fixture(scope="module")
def watch():
    return chip_smoke.CompileWatch()


def test_registry_phase_cpu(watch):
    rows = chip_smoke.registry_phase(watch, banks=1, **TINY)
    from repro import pim
    assert [r["workload"] for r in rows] == list(pim.registry())
    assert all(r["bytes_in"] > 0 and r["run_s"] > 0 for r in rows)


def test_decode_phase_cpu(watch):
    from repro.configs.tinyllama_1_1b import SMOKE
    out = chip_smoke.decode_phase(watch, SMOKE, streams=2, prompt_len=3,
                                  max_new=3)
    assert out["resident_bytes"] > 0


def test_compile_seconds_merge_concurrent_spans(watch):
    """Rank threads compile at once: overlapping spans count once, and only
    their part inside the timed block counts."""
    saved, watch.spans = watch.spans, [(5.0, 6.0), (0.0, 2.0), (1.0, 3.0)]
    try:
        assert watch.compile_seconds(0.0, 10.0) == 4.0
        assert watch.compile_seconds(1.5, 5.5) == 2.0
        assert watch.compile_seconds(3.0, 5.0) == 0.0
    finally:
        watch.spans = saved


def test_tinyllama_config_is_full_width():
    cfg = chip_smoke.tinyllama_f32()
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.d_ff, cfg.vocab) == (22, 2048, 32, 4, 5632, 32000)
    assert cfg.dtype.__name__ == "float32"


FOUR = r"""
import chip_smoke
from repro.configs.tinyllama_1_1b import SMOKE
chip_smoke.four_chips(chip_smoke.CompileWatch(), cfg=SMOKE, scale=1,
                      serial_scales={"NW": 1, "BFS": 1})
print("FOUR-CHIPS-OK")
"""


def test_four_chip_phases_on_4_host_devices():
    out = _run(FOUR, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    assert out.returncode == 0, out.stderr[-4000:]
    assert "FOUR-CHIPS-OK" in out.stdout
    assert "2 rank(s)" in out.stdout and "4 bank(s)" in out.stdout


def test_script_refuses_cpu():
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"),
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "needs a TPU" in out.stderr


IMPORT_ALL = r"""
import importlib, pkgutil
import repro
from jax._src import xla_bridge
names = [m.name for m in pkgutil.walk_packages(repro.__path__, "repro.")]
for name in names:
    importlib.import_module(name)
    assert not xla_bridge.backends_are_initialized(), name
print("IMPORTED", len(names))
"""


def test_import_initialises_no_backend():
    out = _run(IMPORT_ALL)
    assert out.returncode == 0, out.stderr[-4000:]
    assert int(out.stdout.split()[-1]) > 50


CACHE = r"""
import jax
from repro.launch.cli import CACHE_DIR, cpu_rehearsal_env, enable_compile_cache
print(enable_compile_cache(), jax.config.jax_compilation_cache_dir)
print(CACHE_DIR)
env = cpu_rehearsal_env(3)
print(env["JAX_PLATFORMS"], env["XLA_FLAGS"])
"""


@pytest.mark.parametrize("env_dir", [None, "/cache/from/env"])
def test_compile_cache_location(env_dir):
    env = {} if env_dir is None else {"JAX_COMPILATION_CACHE_DIR": env_dir}
    base = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    out = subprocess.run([sys.executable, "-c", CACHE], cwd=ROOT,
                         env=dict(base, PYTHONPATH=SRC, **env),
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-4000:]
    (used, configured), (fixed,), rehearsal = (
        line.split() for line in out.stdout.splitlines())
    assert fixed == os.path.join(ROOT, ".jax_cache")
    if env_dir is None:
        assert used == configured == fixed
    else:                                 # JAX reads the variable itself
        assert used == configured == env_dir
    assert rehearsal == ["cpu", "--xla_force_host_platform_device_count=3"]
