"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS before first init.

Single pod : (data=16, model=16)              = 256 chips (TPU v5e pod)
Multi-pod  : (pod=2, data=16, model=16)       = 512 chips

``pod`` is declared outermost so XLA maps it onto the slowest (inter-pod)
links; by default it extends data parallelism (gradient all-reduce across
pods amortized over grad accumulation), and the pipeline launcher reuses it
as the pipeline-stage axis.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax

# TPU v5e hardware constants (per chip) — used by benchmarks/roofline.py
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # bytes/s
ICI_BW_PER_LINK = 50e9          # bytes/s per link (~3 links usable per axis)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """Arbitrary mesh (tests / hillclimb sweeps).  Auto axis types: the
    framework shards via PartitionSpecs + logical-axis constraints."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def require_devices(n: int) -> None:
    have = len(jax.devices())
    if have < n:
        raise RuntimeError(
            f"mesh needs {n} devices but only {have} present — the dry-run "
            f"entrypoint must set XLA_FLAGS=--xla_force_host_platform_"
            f"device_count={n} BEFORE any jax import (see launch/dryrun.py)")
