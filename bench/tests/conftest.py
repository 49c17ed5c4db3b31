"""Tests of the benchmark itself, run by explicit path:

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/tests

They run on the CPU at toy sizes; nothing here needs a chip.
"""

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))
