"""A run with the served path broken underneath comes out not correct: the
harness drives the whole cell on the CPU (the chip check skipped) with one
fault planted in the program, once for each fault the cell can have."""
import dataclasses

import numpy as np
import pytest

from repro.prim import registry


def _break_merge(monkeypatch, workload: str, fault):
    """Wrap ``workload``'s merge phase with ``fault(grid, meta, parts,
    merge)`` in the registry the session is built from."""
    entry = registry.REGISTRY[workload]
    merge = entry.chunked.merge
    chunked = dataclasses.replace(
        entry.chunked, merge=lambda g, m, parts: fault(g, m, parts, merge))
    monkeypatch.setitem(registry.REGISTRY, workload,
                        dataclasses.replace(entry, chunked=chunked))


def _altered(g, m, parts, merge):
    out = np.array(merge(g, m, parts), copy=True)
    out.reshape(-1)[0] += 1
    return out


def _half_left_out(g, m, parts, merge):
    """The second half of the chunks never arrives: zeros in their place."""
    keep = len(parts) // 2
    return merge(g, m, parts[:keep] + [np.zeros_like(p) for p in
                                       parts[keep:]])


@pytest.mark.parametrize("fault", [_altered, _half_left_out])
def test_resident_gemv_faults(monkeypatch, run_small, fault):
    _break_merge(monkeypatch, "GEMV", fault)
    _, line = run_small("prim-resident-gemv", scale=2, clients=2)
    assert line["correct"] is False
    assert line["checks"]["gemv_err"]["value"] > \
        line["checks"]["gemv_err"]["limit"]


@pytest.mark.parametrize("fault", [_altered, _half_left_out])
@pytest.mark.parametrize("workload", ["VA", "HST"])
def test_stream_cols_faults(monkeypatch, run_small, workload, fault):
    _break_merge(monkeypatch, workload, fault)
    _, line = run_small("prim-stream-cols", clients=2)
    assert line["correct"] is False
    assert line["checks"]["wrong_answers"]["value"] > 0
