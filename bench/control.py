"""The control of the benchmark's correctness check: the program run with a
boundary rule other than the one its configuration states.

    python bench/control.py --workload NAME --seconds S --seeds N [N ...]

The publisher's device scan cuts with ``mask_bits`` one higher than the
configuration states (8 KiB chunks on average instead of 4 KiB): the step a
change that wants fewer chunks to hash and index would take.  Every chunk
boundary, fingerprint and CDMT root then differs from the reference, and
the check must read ``correct: false``.  For each seed this prints the
sound run's compared numbers and the control's, one JSON line each, from
one process.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

import run as bench


def control_scan(device_scan):
    """``device_scan`` with a boundary rule one bit coarser."""
    def make(impl):
        scan = device_scan(impl)

        def coarse(data, params):
            return scan(data, dataclasses.replace(
                params, mask_bits=params.mask_bits + 1))
        return coarse
    return make


def readings(cell, seed: int, seconds: float, control: bool,
             impl: str = "pallas") -> dict:
    from repro.kernels import ops
    sound = ops.device_scan
    if control:
        ops.device_scan = control_scan(sound)
    try:
        result = bench.run_cell(cell, seed, seconds, trace=False, impl=impl)
    finally:
        ops.device_scan = sound
    return {"seed": seed, "control": control, "correct": result["correct"],
            "checks": {k: v["value"] for k, v in result["checks"].items()}}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    sys.path.insert(0, os.path.join(bench.ROOT, "src"))
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 3
    cell = bench.load_cell(args.workload)
    for seed in args.seeds:
        for control in (False, True):
            print(json.dumps(readings(cell, seed, args.seconds, control)),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
