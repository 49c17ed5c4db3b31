"""Tests of the benchmark itself, run by explicit path:

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/tests

They run on the CPU at toy sizes; nothing here needs a chip.
"""

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))


@pytest.fixture
def short(monkeypatch):
    """A ramp of one second at least, and no persistent compile cache on
    the CPU."""
    import harness
    import system
    monkeypatch.setattr(harness, "RAMP_MIN_S", 1.0)
    monkeypatch.setattr(system, "enable_compile_cache", lambda: None)
