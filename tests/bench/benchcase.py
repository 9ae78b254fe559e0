"""Helpers for the benchmark's CPU rehearsals: resolve a cell of
``BENCHMARK.json``, shrink it to a size the CPU runs in seconds, and drive
it through the harness with the chip check skipped."""
import copy
import pathlib
import sys
import time

REPO = pathlib.Path(__file__).resolve().parents[2]
BENCH = REPO / "bench"
for p in (str(BENCH), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

#: the dataset sizes of a CPU rehearsal, per unit of ``scale``; GEMV's
#: width stays at its small value whatever the scale
SMALL = {"gemv_rows": 512, "gemv_cols": 256, "va_elements": 65536,
         "hst_pixels": 65536}
WIDTHS = ("gemv_cols",)


def small_cell(name: str, scale: int = 1, clients: int | None = None):
    """Cell ``name`` with every dataset size at ``scale`` units of
    :data:`SMALL`."""
    import harness
    cell = harness.resolve(harness.load_benchmark(), name)
    cell.config = copy.deepcopy(cell.config)
    cell.traffic = dict(cell.traffic)
    for k, v in SMALL.items():
        if k in cell.config:
            cell.config[k] = v if k in WIDTHS else v * scale
    if clients is not None:
        cell.traffic["clients"] = clients
    return cell


def cpu_context(cell, tmp_path, seed: int = 2**31 + 17, seconds: float = 0.3,
                trace: bool = False, devices=None):
    import jax

    import harness
    return harness.Context(
        cell=cell, seed=seed, seconds=seconds, trace=trace,
        t_process=time.perf_counter(),
        devices=devices or jax.devices()[:1],
        peaks=harness.load_peaks("TPU v5 lite"), out_dir=tmp_path,
        log=lambda msg: None)
