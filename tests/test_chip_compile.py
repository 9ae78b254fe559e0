"""Compiles for a described TPU v5e chip: the Pallas kernels that serialized
``pim()`` reaches, at the shapes ``kernels/ops.py`` pads to, the chunked
GEMV-B / GEMV-G compute phases at TinyLlama 1.1B widths and, with a stack of
vectors, at DeepSeek-V2-Lite's expert widths, and the chunked HST phase at
the benchmark's chunk size.

Nothing runs: the TPU compiler that ships with JAX compiles for a chip that
is described, not attached, and raises what the chip's compiler would raise
(an unsupported primitive in a kernel, a store the TPU cannot do, a program
that does not fit).  The topology is described inside a fixture, so only
the process that runs these tests loads the TPU library.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core.banked import AXIS, BankGrid
from repro.kernels import gemv as kgemv
from repro.kernels import histogram as khist
from repro.kernels import reduce as kred
from repro.kernels import scan as kscan
from repro.prim import gemv_fused, hist

#: a VA/RED/SCAN/HST-sized operand of the chip smoke run (scale 256), and
#: the smallest padded length
LONG, SHORT = 65536 * 256, kred.MIN_BLOCK


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def bank_grid(topo):
    """A one-bank grid on the described chip."""
    return BankGrid(mesh=Mesh(np.array([topo.devices[0]]), (AXIS,)))


@pytest.fixture(scope="module")
def four_banks(topo):
    """A bank per chip of the described 2x2 host."""
    return BankGrid(mesh=Mesh(np.array(topo.devices), (AXIS,)))


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described chip cannot be read back without one:
    keep it out of any persistent compilation cache."""
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_hlo(fn, *specs) -> str:
    text = jax.jit(fn).lower(*specs).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    return text


@pytest.mark.parametrize("n", [SHORT, LONG])
@pytest.mark.parametrize("dtype", [jnp.int32, jnp.float32])
def test_reduce_kernel_compiles(one_chip, n, dtype):
    _kernel_hlo(lambda x: kred.reduce_sum(x, block=4096),
                _spec((n,), dtype, one_chip))


@pytest.mark.parametrize("n", [SHORT, LONG])
@pytest.mark.parametrize("dtype", [jnp.int32, jnp.float32])
def test_scan_kernel_compiles(one_chip, n, dtype):
    _kernel_hlo(lambda x: kscan.scan_exclusive(x, block=4096),
                _spec((n,), dtype, one_chip))


def test_histogram_kernel_compiles(one_chip):
    _kernel_hlo(lambda v: khist.histogram(v, 256, block=4096),
                _spec((LONG,), jnp.int32, one_chip))


@pytest.mark.parametrize("m,n", [(131072, 256), (2048, 5632)])
def test_gemv_kernel_compiles(one_chip, m, n):
    bn = min(512, 1 << (n - 1).bit_length())
    n_pad = -(-n // bn) * bn
    _kernel_hlo(lambda a, x: kgemv.gemv(a, x, block_m=128, block_n=bn),
                _spec((m, n_pad), jnp.float32, one_chip),
                _spec((n_pad,), jnp.float32, one_chip))


@pytest.mark.parametrize("nbins", [256, 257])
def test_hst_phase_compiles(bank_grid, nbins):
    """One chunk of a 1536 x 1024 image (of four) on one bank: the counts
    are an int8 contraction (a convolution on the chip), with no scatter."""
    fn = hist._local(bank_grid, nbins)
    text = fn.lower(_spec((1, 393216), jnp.int32,
                          bank_grid.sharding(P(AXIS)))).compile().as_text()
    assert re.search(r"\bconvolution\(", text)
    assert not re.search(r"\bscatter\(", text)


#: TinyLlama 1.1B projections as (d_out, d_in) row-major GEMV operands
TINYLLAMA_B = {"q": (2048, 2048), "k": (256, 2048), "v": (256, 2048),
               "o": (2048, 2048), "down": (2048, 5632)}


@pytest.mark.parametrize("proj", sorted(TINYLLAMA_B))
def test_gemv_b_phase_compiles(bank_grid, proj):
    rows, d_in = TINYLLAMA_B[proj]
    banked = bank_grid.sharding(P(AXIS))
    fn = gemv_fused._local_b(bank_grid)
    lowered = fn.lower(_spec((1, rows, d_in), jnp.float32, banked),
                       _spec((1, rows), jnp.float32, banked),
                       _spec((d_in,), jnp.float32, bank_grid.sharding(P())))
    assert "HIGHEST" in lowered.as_text()          # full float32 matvec
    lowered.compile()


def test_gemv_g_phase_compiles(bank_grid):
    banked = bank_grid.sharding(P(AXIS))
    w = _spec((1, 5632, 2048), jnp.float32, banked)
    fn = gemv_fused._local_g(bank_grid)
    lowered = fn.lower(w, w, _spec((2048,), jnp.float32,
                                   bank_grid.sharding(P())))
    assert "HIGHEST" in lowered.as_text()
    lowered.compile()


#: DeepSeek-V2-Lite's expert matvecs (one of two row chunks), the vectors
#: of the decode cell's 8 streams stacked as columns (GEMV-B's bias a
#: column)
STACKED = {"GEMV-B": (1024, 1408), "GEMV-G": (704, 2048)}


@pytest.mark.parametrize("workload", sorted(STACKED))
def test_stacked_gemv_phase_compiles(bank_grid, workload):
    rows, d_in = STACKED[workload]
    banked = bank_grid.sharding(P(AXIS))
    w = _spec((1, rows, d_in), jnp.float32, banked)
    x = _spec((d_in, 8), jnp.float32, bank_grid.sharding(P()))
    if workload == "GEMV-B":
        lowered = gemv_fused._local_b(bank_grid).lower(
            w, _spec((1, rows, 1), jnp.float32, banked), x)
    else:
        lowered = gemv_fused._local_g(bank_grid).lower(w, w, x)
    assert "HIGHEST" in lowered.as_text()
    lowered.compile()


def test_gemv_phases_compile_on_4_banks(four_banks):
    """The ``--chips 4`` path: q rows split over four banks, no collective
    (banks cannot communicate)."""
    banked = four_banks.sharding(P(AXIS))
    x = _spec((2048,), jnp.float32, four_banks.sharding(P()))
    w = _spec((4, 512, 2048), jnp.float32, banked)
    for lowered in (gemv_fused._local_b(four_banks).lower(
                        w, _spec((4, 512), jnp.float32, banked), x),
                    gemv_fused._local_g(four_banks).lower(w, w, x)):
        text = lowered.compile().as_text()
        assert "all-reduce" not in text and "all-gather" not in text
