"""Harness tests: ``python -m pytest benchmark/tests`` from the repo
root, on the CPU. Not collected by tier-1's ``pytest tests/``."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)
