"""Harness tests, run by hand on the CPU (the repository's tier-1 run
collects only ``tests/``):

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

Every cell of ``BENCHMARK.json`` is rehearsed here at the sizes of its
configuration's ``bench/configs/<config>.rehearsal.json``, beside
``<config>.json`` and ``<config>.py``: a configuration enters by those
three files, its traffic and metric files and its entries in
``BENCHMARK.json`` (``tiny.py``).
"""

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for path in (os.path.join(ROOT, "src"), BENCH):
    if path not in sys.path:
        sys.path.insert(0, path)
