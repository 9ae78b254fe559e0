"""The benchmark measures only on a chip it knows: a CPU, too few chips and
an unknown ``device_kind`` are refused with no result line."""
import dataclasses
import json
import os
import shutil
import subprocess
import sys

import pytest
from benchcase import BENCH, REPO

import harness


@dataclasses.dataclass
class FakeDevice:
    platform: str
    device_kind: str


def test_peaks_table_knows_the_v5e():
    peaks = harness.load_peaks("TPU v5 lite")
    assert peaks["bf16_flops_per_s"] == 197e12
    assert peaks["int8_ops_per_s"] == 393e12
    assert peaks["hbm_bytes_per_s"] == 819e9
    assert peaks["hbm_bytes"] == 16e9
    assert "TPU v5e" in json.loads((BENCH / "peaks.json").read_text())[
        "source"]


def test_unknown_device_kind_is_refused():
    with pytest.raises(harness.Refused):
        harness.load_peaks("TPU v9 imaginary")


def test_cpu_and_too_few_chips_are_refused():
    with pytest.raises(harness.Refused):
        harness.require_devices(1, [FakeDevice("cpu", "cpu")])
    tpu = FakeDevice("tpu", "TPU v5 lite")
    with pytest.raises(harness.Refused):
        harness.require_devices(4, [tpu])
    assert harness.require_devices(1, [tpu, tpu]) == [tpu]


def _run(cwd, workload="prim-resident-gemv"):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", workload, "--seed",
         "4294967311", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_on_cpu_exits_nonzero_without_a_result():
    out = _run(REPO)
    assert out.returncode == 2, out.stderr[-2000:]
    assert "refused" in out.stderr and out.stdout.strip() == ""


def test_run_without_the_program_exits_nonzero(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    out = _run(tmp_path)
    assert out.returncode != 0
    assert not out.stdout.strip().startswith("{")
