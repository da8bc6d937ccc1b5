"""Cells of ``BENCHMARK.json`` cut to a size the CPU rehearses in seconds,
with the kernels in interpret mode.  Only the tests use these sizes."""

import run as bench

SIZES = {
    "dockerhub-nginx": {"artifact": {"image_bytes": 262144,
                                     "edits_per_version": 10,
                                     "insert_delete_pairs": 2,
                                     "edit_max_bytes": 4096}},
}
SEED = 3_000_000_017        # above 2**31: seeds need not fit 32 bits
SECONDS = 1.0


def cell(name: str, root: str = bench.ROOT) -> "bench.Cell":
    c = bench.load_cell(name, root)
    for key, value in SIZES.get(c.cfg["name"], {}).items():
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
