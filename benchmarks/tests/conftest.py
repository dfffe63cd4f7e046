"""Tests of the benchmark's own code.  Not collected by the repo's tier-1
run (``pytest tests/``); run them with

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)
