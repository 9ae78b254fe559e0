"""Fixtures of the benchmark's CPU rehearsals (helpers in benchcase.py)."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchcase import cpu_context, small_cell  # noqa: E402


@pytest.fixture
def run_small(tmp_path):
    """``run_small(name, **kw) -> (run, line)`` on the CPU."""
    def go(name, scale=1, clients=None, **kw):
        import harness
        cell = small_cell(name, scale, clients)
        return harness.run_cell(cpu_context(cell, tmp_path, **kw))
    return go
