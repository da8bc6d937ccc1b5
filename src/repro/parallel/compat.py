"""The shard_map entry point every call site imports.

``shard_map`` is ``jax.shard_map`` (``check_vma``, ``axis_names``).
``axis_index_input`` gives a stage its index along a manual axis as data.
"""

from __future__ import annotations

import jax

shard_map = jax.shard_map


def axis_index_input(n: int):
    """Host-side iota to pass through shard_map with ``in_specs=P(axis)``;
    inside the body, ``operand[0]`` is the device's index along ``axis``."""
    import jax.numpy as jnp
    return jnp.arange(n, dtype=jnp.int32)
