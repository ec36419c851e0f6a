"""The benchmark's own tests (run by hand: ``JAX_PLATFORMS=cpu python3 -m
pytest benchmark/tests -q``; tier-1 collects ``tests/`` only)."""

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for path in (ROOT, BENCH):
    if path not in sys.path:
        sys.path.insert(0, path)
