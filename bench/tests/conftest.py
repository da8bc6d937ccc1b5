"""Harness tests, run by hand on the CPU (the repository's tier-1 run
collects only ``tests/``):

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for path in (os.path.join(ROOT, "src"), BENCH):
    if path not in sys.path:
        sys.path.insert(0, path)
