"""Cells of ``BENCHMARK.json`` cut to a size the CPU rehearses in seconds,
with the kernels in interpret mode.  Only the tests use these sizes.

A configuration enters the benchmark by files of its own, found by name:
``bench/configs/<config>.json`` (the configuration as the chip runs it),
``<config>.py`` beside it (its source of artifacts) and
``<config>.rehearsal.json``, the keys this rehearsal overrides (a nested
group updated key by key, any other value replaced), plus its traffic mix
``bench/traffic/<traffic>.json`` and a reader ``bench/metrics/<metric>.py``
for each metric.  The chip run never reads the rehearsal file, and a
configuration without one is refused here rather than rehearsed at its
full size."""

import json
import os

import run as bench

SEED = 3_000_000_017        # above 2**31: seeds need not fit 32 bits
SECONDS = 1.0


def cell(name: str, root: str = bench.ROOT) -> "bench.Cell":
    c = bench.load_cell(name, root)
    path = os.path.splitext(c.generator)[0] + ".rehearsal.json"
    if not os.path.isfile(path):
        raise FileNotFoundError(f"cell {name!r}: its configuration has no "
                                f"rehearsal sizes; add {path}")
    with open(path) as f:
        sizes = json.load(f)
    for key, value in sizes.items():
        if isinstance(value, dict):
            c.cfg[key].update(value)
        else:
            c.cfg[key] = value
    for fleet in c.traffic.get("fleets", {}).values():
        fleet["processes"] = min(fleet["processes"], 2)
        fleet["clients"] = 2 * fleet["processes"]
    if "check_clients" in c.traffic:
        c.traffic["check_clients"] = 2
    return c


def run(c, trace: bool = False, seed: int = SEED) -> dict:
    return bench.run_cell(c, seed, SECONDS, trace, impl="interpret")
