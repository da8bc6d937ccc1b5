"""Pallas TPU kernel: content-defined-chunking boundary scan (gear hash).

The paper's CDC hot loop (Sec. III-A, VI-D) is a byte-serial rolling hash.
Two rewrites make it a tiled vector program that Mosaic compiles:

1. **Serial recurrence → bounded convolution.**  ``h_i = 2 h_{i-1} + g_i``
   (mod 2^32) forgets a term after 32 doublings, so
   ``h_i = Σ_{j<32} 2^j g_{i-j}``.  The kernel builds that sum in five
   doubling steps, ``a ← a + (a shifted by k) << k`` for k = 1, 2, 4, 8,
   16, in int32 (wraparound is mod 2^32).

2. **A flat stream → (rows, 128) tiles.**  Byte ``p`` of a window lives at
   ``(p // 128, p % 128)``.  "Shifted by k" is then two aligned rotations:
   a lane roll by k, and for lanes ``< k`` the same roll of the row above
   (a sublane roll by one).  No unaligned slice or 1-D concatenate remains.

The gear lookup ``G[byte]`` is a chain of 256 compare/selects against
int32 constants on the VPU.  It is exact by construction: no MXU pass, so
no question of how many mantissa bits a matmul keeps.

Layout: a window of ``n`` bytes (a multiple of ``BLOCK``) is a
``(n // 128, 128)`` uint8 array.  Grid step ``i`` hashes ``BLOCK_ROWS``
rows.  Its halo is a second view of the same array: the whole aligned
uint8 tile ``(32, 128)`` just before the block.  Step 0 reads that tile
from ``prev`` instead, the 4096 bytes that precede the window in the
stream; an SMEM flag says whether the window starts the stream, in which
case the halo contributes zero.  Inside a step a ``fori_loop`` walks
``SUB_ROWS``-row sub-tiles: gear values go to a VMEM scratch with one
(8, 128) int32 tile of look-back ahead of them, so every load and store is
tile aligned.  VMEM per step: two 64 KiB uint8 input buffers, two 256 KiB
int32 output buffers, and a 260 KiB scratch — about 1 MiB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.cdc import gear_table

LANES = 128
TILE_ROWS = 32                  # rows of one uint8 (32, 128) tile
TILE_BYTES = TILE_ROWS * LANES  # 4096: the halo each window carries
BLOCK_ROWS = 512
BLOCK = BLOCK_ROWS * LANES      # 64 KiB of stream per grid step
SUB_ROWS = 32                   # rows per inner-loop sub-tile
LOOKBACK = 8                    # int32 rows (one tile) kept ahead of a sub-tile

_GEAR_I32 = [int(v) for v in gear_table().view(np.int32)]


def _lookup(x: jax.Array) -> jax.Array:
    """``G[x]`` for int32 byte values, as 256 compare/selects."""
    g = jnp.zeros_like(x)
    for v, t in enumerate(_GEAR_I32):
        g = jnp.where(x == v, jnp.int32(t), g)
    return g


def _shift(a: jax.Array, k: int) -> jax.Array:
    """``a`` moved ``k`` (< 128) positions later along the row-major stream."""
    rolled = pltpu.roll(a, k, 1)                 # [r, c] <- [r, c-k mod 128]
    above = pltpu.roll(rolled, 1, 0)             # [r, c] <- rolled[r-1, c]
    lane = jax.lax.broadcasted_iota(jnp.int32, a.shape, 1)
    return jnp.where(lane >= k, rolled, above)


def _gear_cdc_kernel(first_ref, data_ref, halo_ref, prev_ref, hash_ref, g_ref):
    """One grid step: rolling gear hash of BLOCK bytes (uint32 bits in int32)."""
    i = pl.program_id(0)
    before = jnp.where(i == 0, prev_ref[...].astype(jnp.int32),
                       halo_ref[...].astype(jnp.int32))
    g_before = _lookup(before[TILE_ROWS - LOOKBACK:])
    # the stream's first window has no predecessor: its halo is not stream
    # bytes and contributes nothing (h_i sums only over positions >= 0)
    at_start = jnp.logical_and(i == 0, first_ref[0] != 0)
    g_ref[0:LOOKBACK, :] = jnp.where(at_start, 0, g_before)

    def body(s, carry):
        r0 = pl.multiple_of(s * SUB_ROWS, SUB_ROWS)
        x = data_ref[pl.ds(r0, SUB_ROWS), :].astype(jnp.int32)
        g_ref[pl.ds(r0 + LOOKBACK, SUB_ROWS), :] = _lookup(x)
        a = g_ref[pl.ds(r0, LOOKBACK + SUB_ROWS), :]
        for k in (1, 2, 4, 8, 16):               # window 1 -> 2 -> ... -> 32
            a = a + (_shift(a, k) << k)
        hash_ref[pl.ds(r0, SUB_ROWS), :] = a[LOOKBACK:]
        return carry

    jax.lax.fori_loop(0, BLOCK_ROWS // SUB_ROWS, body, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gear_hash_pallas(window: jax.Array, prev: jax.Array, first: jax.Array, *,
                     interpret: bool) -> jax.Array:
    """Rolling gear hash of one window of a uint8 stream.

    ``window``: (n,) uint8 with ``n`` a multiple of BLOCK.  ``prev``:
    (TILE_BYTES,) uint8, the stream bytes just before the window.
    ``first``: (1,) int32, nonzero when the window starts the stream (then
    ``prev`` is ignored).  Returns (n,) uint32, bit-identical to
    ``cdc.gear_hash_stream`` of the stream at these positions.
    """
    n = window.shape[0]
    assert n % BLOCK == 0, "pad the window to BLOCK (see ops.gear_hash)"
    rows = n // LANES
    tiles_per_block = BLOCK_ROWS // TILE_ROWS
    out = pl.pallas_call(
        _gear_cdc_kernel,
        grid=(rows // BLOCK_ROWS,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
            pl.BlockSpec((TILE_ROWS, LANES),
                         lambda i: (jnp.maximum(i * tiles_per_block - 1, 0), 0)),
            pl.BlockSpec((TILE_ROWS, LANES), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
        scratch_shapes=[pltpu.VMEM((LOOKBACK + BLOCK_ROWS, LANES), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(first, window.reshape(rows, LANES), window.reshape(rows, LANES),
      prev.reshape(TILE_ROWS, LANES))
    return jax.lax.bitcast_convert_type(out.reshape(n), jnp.uint32)
