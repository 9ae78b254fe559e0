"""The DeepSeek-V2 decode cell on the CPU at the smoke size, through the
harness: the program's run reads ``correct: true``; the control (each
matvec at one bfloat16 pass, in the program's place) and a planted fault
(one routed expert's output dropped) read ``correct: false``."""
import copy

import pytest
from benchcase import cpu_context
from controls import control_in_place

import harness

CELL = "decode-v2lite-b8"


def small_decode_cell():
    """The cell with the smoke model's sizes and a few short streams."""
    from repro.configs.deepseek_v2_lite import SMOKE, to_hf
    cell = harness.resolve(harness.load_benchmark(), CELL)
    cell.config = copy.deepcopy(cell.config)
    cell.config.update(to_hf(SMOKE))
    cell.traffic = dict(cell.traffic, streams=3, context_min=8,
                        context_max=24, max_new=64, warm_steps=1)
    return cell


def _run(tmp_path, **kw):
    cell = small_decode_cell()
    return harness.run_cell(cpu_context(cell, tmp_path, seconds=0.5, **kw))


def test_decode_cell_is_correct(tmp_path):
    run, line = _run(tmp_path)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"setup_s", "prim_requests_per_s",
                                    "prim_latency_p95_ms"}
    err = line["checks"]["logit_err"]
    assert err["value"] < err["limit"] / 10
    assert run.compiles_in_window == 0
    assert run.attempted >= 3 and run.attempted % 3 == 0
    assert all(r.cache_hit for r in run.records)


def test_decode_cell_traced_reads_its_layers(tmp_path):
    run, line = _run(tmp_path, trace=True)
    assert line["correct"] is True
    m = line["metrics"]
    # the device-trace readers need a TPU's planes: not on the CPU
    assert {"decode.experts_ms", "decode.host_ms"} <= set(m)
    assert m["decode.experts_ms"]["value"] > 0
    steps = run.facts["steps"]
    # every MoE layer: shared + top-k experts, up and down, per stream
    assert all(s["expert_requests"] == 3 * (1 + 2) * 2 for s in steps)


def test_decode_control_reads_not_correct(tmp_path):
    cell = small_decode_cell()
    with control_in_place(cell):
        _, line = harness.run_cell(cpu_context(cell, tmp_path, seconds=0.5))
    assert line["correct"] is False and line["failed"] == 0
    # about 1e-2 here (ten times the limit and more on the chip, at the
    # cell's own size: PERF.md section 2)
    err = line["checks"]["logit_err"]
    assert err["value"] > err["limit"]


def test_dropped_expert_reads_not_correct(tmp_path, monkeypatch):
    from repro.pim import decode
    combine = decode._combine
    monkeypatch.setattr(
        decode, "_combine",
        lambda x, shared, ys, gates: combine(x, shared, ys[1:], gates[1:]))
    _, line = _run(tmp_path)
    assert line["correct"] is False
    assert line["checks"]["logit_err"]["value"] > \
        line["checks"]["logit_err"]["limit"]


@pytest.mark.parametrize("seed", [5, 2**31 + 99])
def test_weights_and_contexts_follow_the_seed(seed):
    from repro.configs.deepseek_v2_lite import SMOKE
    drv = harness.load_driver("decode_closed_loop")
    a, b = drv.make_params(SMOKE, seed), drv.make_params(SMOKE, seed)
    c = drv.make_params(SMOKE, seed + 1)
    import numpy as np
    wa = a["group"][0]["ffn"]["wi"]
    assert np.array_equal(wa, b["group"][0]["ffn"]["wi"])
    assert not np.array_equal(wa, c["group"][0]["ffn"]["wi"])
    # row-major in the layout the engine pins: its extraction copies nothing
    assert wa[0, 3].T.flags.c_contiguous
    assert abs(float(wa.std()) * np.sqrt(SMOKE.d_model) - 1) < 0.05


def test_decode_readers_on_a_stub_trace():
    """The device-trace readers of the cell against a trace summary with
    known module times, and nothing read where nothing was recorded."""
    import types

    import kernel_costs
    peaks = harness.load_peaks("TPU v5 lite")
    trace = types.SimpleNamespace(
        chips=1, window_s=10.0, idle_share=0.9,
        kernel_time=lambda rx: (0.004, 3) if "bias" in rx else (0.0, 0))
    recs = [types.SimpleNamespace(workload=w, tags={"proj": p})
            for w, p in (("GEMV-B", "q"), ("GEMV-G", "e5.up"),
                         ("GEMV-B", "e63.down"))]
    facts = {"n_chunks": 2, "n_banks": 1,
             "matvec_shapes": {"q": [3072, 2048], "e5.up": [1408, 2048],
                               "e63.down": [2048, 1408]}}
    run = harness.Run(trace=trace, records=recs, facts=facts, peaks=peaks)
    least = sum(f * 2 * kernel_costs.least_time_s(
        *kernel_costs.gemv(r // 2, c), peaks)
        for f, (r, c) in ((1, (3072, 2048)), (2, (1408, 2048)),
                          (1, (2048, 1408))))
    got = harness.load_metric("decode_gemv_roofline").read(run)
    assert got == pytest.approx(100 * least / 0.004)
    assert harness.load_metric("device.idle_share.decode").read(run) == \
        pytest.approx(90.0)
    empty = harness.Run()
    for name in ("decode_gemv_roofline", "device.idle_share.decode",
                 "decode.experts_ms", "decode.host_ms"):
        assert harness.load_metric(name).read(empty) is None, name
    old = harness.Run(facts={"steps": [{"wall_s": 1.0, "host_s": 0.5}]})
    assert harness.load_metric("decode.host_ms").read(old) is None
