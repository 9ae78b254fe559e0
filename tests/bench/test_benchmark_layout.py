"""The benchmark is data: every cell of ``BENCHMARK.json`` resolves its
configuration, traffic, driver, reference and metric files by name, its
names and units keep to the allowed characters, and a new cell, mix or
metric is added as files and entries alone."""
import json
import re
import shutil
import subprocess
import sys

import pytest
from benchcase import BENCH, REPO

import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCHMARK = json.loads((REPO / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCHMARK["workloads"]]


def test_top_level_keys_and_paths():
    assert set(BENCHMARK) == {"command", "paths", "run_seconds", "configs",
                              "workloads", "end_to_end", "per_layer"}
    assert BENCHMARK["command"] == ["python3", "bench/run.py"]
    for p in BENCHMARK["paths"]:
        assert (REPO / p).is_dir() and not p.startswith("/")
    assert 1 <= BENCHMARK["run_seconds"] <= 51


def test_names_and_units():
    metrics = BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]
    names = ([m["name"] for m in metrics] + CELLS
             + [c["name"] for c in BENCHMARK["configs"]])
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for w in BENCHMARK["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 0 < len(w["why"]) <= 200
    for c in BENCHMARK["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_its_files(cell):
    c = harness.resolve(BENCHMARK, cell)
    entry = next(e for e in BENCHMARK["configs"]
                 if e["name"] == c.entry["config"])
    assert (REPO / entry["file"]).is_file()
    assert c.config["reduced"] == entry["reduced"]
    assert harness.load_driver(c.traffic["driver"]).run
    assert harness.load_reference(c.config["reference"])
    for m in c.end_to_end + c.per_layer:
        assert callable(harness.load_metric(m["name"]).read)
    assert c.config["checks"]


@pytest.mark.parametrize("config", [c["name"] for c in BENCHMARK["configs"]])
def test_reduced_names_every_key_changed_from_the_source(config):
    """A configuration runs its source's values (``published``) except
    under the keys that ``reduced`` names, and names no other."""
    c = harness.load_config(config)
    changed = {k for k, v in c["published"].items() if c.get(k) != v}
    assert changed == set(c["reduced"])


@pytest.mark.parametrize("cell", CELLS)
def test_cell_reports_what_its_layers_move(cell):
    c = harness.resolve(BENCHMARK, cell)
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer
    for m in c.per_layer:
        assert m["moves"] in e2e, (cell, m["name"])


def test_unknown_cell_is_an_error():
    with pytest.raises(KeyError):
        harness.resolve(BENCHMARK, "no-such-cell")


def test_new_cell_mix_and_metric_are_files_and_entries(tmp_path):
    """A later change adds a traffic mix, a metric and a cell by writing new
    files and appending entries: no file the benchmark has is edited."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*")
              if p.is_file()}
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    (tmp_path / "bench/traffic/resident-gemv-2c.json").write_text(json.dumps(
        {"driver": "prim_closed_loop", "clients": 2, "mix": ["GEMV"],
         "sample": 2, "warm_rounds": 1}))
    (tmp_path / "bench/metrics/prim_attempted.py").write_text(
        "def read(run):\n    return run.attempted or None\n")
    bench["workloads"].append({"name": "prim-resident-gemv-2c",
                               "config": "prim-gemv-32r",
                               "traffic": "resident-gemv-2c", "chips": 1,
                               "why": "two clients"})
    bench["per_layer"].append({"name": "prim_attempted", "unit": "requests",
                               "better": "higher", "source": "host_clock",
                               "layer": "scheduler",
                               "moves": "prim_requests_per_s",
                               "workloads": ["prim-resident-gemv-2c"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    script = (
        "import json, sys, time\n"
        f"sys.path[:0] = [{str(tmp_path / 'bench')!r}, "
        f"{str(REPO / 'src')!r}]\n"
        "import jax, harness\n"
        "cell = harness.resolve(harness.load_benchmark(), "
        "'prim-resident-gemv-2c')\n"
        "cell.config.update(gemv_rows=512, gemv_cols=256)\n"
        "ctx = harness.Context(cell=cell, seed=5, seconds=0.2, trace=False,"
        " t_process=time.perf_counter(), devices=jax.devices()[:1],"
        " peaks=harness.load_peaks('TPU v5 lite'),"
        f" out_dir=__import__('pathlib').Path({str(tmp_path)!r}))\n"
        "run, line = harness.run_cell(ctx)\n"
        "print(json.dumps([line, harness.metrics_of(run, cell.per_layer)]))\n")
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, env={"JAX_PLATFORMS": "cpu",
                                         "PATH": "/usr/bin:/bin"},
                         timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    line, per_layer = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    assert per_layer["prim_attempted"]["value"] == line["attempted"] > 0
    after = {p: p.read_bytes() for p in before}
    assert after == before
