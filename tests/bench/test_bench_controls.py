"""Each cell's control comes out not correct: the plain reference computed
one precision lower, put in the program's place, reads above the limit that
the configuration states, at a size a CPU test holds, and a whole run with
it in place prints ``correct: false``.  (On the chip the same controls are
read at the cells' own sizes by ``chip_controls.py``.)"""
import numpy as np
import pytest
from benchcase import cpu_context, small_cell
from controls import control_in_place

import harness


def _limits(name):
    return harness.resolve(harness.load_benchmark(), name).config["checks"]


def test_gemv_control_fails_and_program_passes():
    ref = harness.load_reference("prim_reference")
    rng = np.random.default_rng(1)
    a = rng.standard_normal((4096, 256), dtype=np.float32)
    xs = rng.standard_normal((4, 256), dtype=np.float32)
    exact = np.asarray(a @ xs.T).T
    limit = _limits("prim-resident-gemv")["gemv_err"]
    assert ref.gemv_error(a, xs, exact) < limit
    assert ref.gemv_error(a, xs, ref.gemv_control(a, xs)) > limit


def test_va_control_fails():
    """float32 arithmetic loses exactness past 2**24, as VA's operands,
    drawn over the whole non-negative int32 range, do."""
    ref = harness.load_reference("prim_reference")
    args = ref.make_column_args("VA", np.random.default_rng(2),
                                {"va_elements": 4096})
    assert not ref.same_answer(ref.column_control("VA", args),
                               ref.column_ref("VA", args))


@pytest.mark.parametrize("name", ["VA", "HST"])
def test_column_reference_matches_numpy(name):
    ref = harness.load_reference("prim_reference")
    args = ref.make_column_args(name, np.random.default_rng(3), {
        "va_elements": 4096, "hst_pixels": 4096, "hst_bins": 256})
    want = {"VA": lambda a, b: a + b,
            "HST": lambda x, n: np.histogram(x, bins=n, range=(0, n))[0]
            }[name](*args)
    assert ref.same_answer(ref.column_ref(name, args), want)


@pytest.mark.parametrize("cell,check", [("prim-resident-gemv", "gemv_err"),
                                        ("prim-stream-cols",
                                         "wrong_answers")])
def test_control_in_place_reads_not_correct(tmp_path, cell, check):
    c = small_cell(cell, clients=2)
    with control_in_place(c):
        _, line = harness.run_cell(cpu_context(c, tmp_path, seconds=0.3))
    assert line["correct"] is False and line["failed"] == 0
    assert line["checks"][check]["value"] > line["checks"][check]["limit"]
