"""Puts ``benchmarks/``, where the harness's package ``hadar_bench``
lives, on the import path.  A module of its own, not a ``conftest.py``:
other tests import ``conftest`` by name, and must find the one in
``tests/``."""
import os
import sys

BENCHMARKS = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "benchmarks")
if BENCHMARKS not in sys.path:
    sys.path.insert(0, BENCHMARKS)
