"""Pallas TPU kernel: parallel polynomial page fingerprints.

Fast-path dedup fingerprint for device-resident checkpoint shards
(DESIGN.md §4): every fixed-size page gets a pair of 32-bit polynomial
fingerprints ``fp_k = Σ_i b_i · p_k^(S-1-i)  (mod 2^32)`` with two
independent bases.  Pages whose 64-bit fp pair matches a stored page are
*candidate* duplicates — the host confirms with blake2b before dropping any
byte, so the kernel only needs to be collision-*rare*, not collision-free.

Layout that Mosaic compiles: a grid step takes a ``(PAGE_TILE, S)`` uint8
block of whole pages (32 rows: one uint8 tile tall) and the two weight
vectors as one lane-dense ``(2, S)`` int32 operand, resident across steps.
Each fingerprint is an elementwise multiply by one weight *row* and a lane
reduction, in int32 on the VPU: wraparound is exactly mod 2^32, with no
rounding question as an MXU formulation would raise.  No column of the
weights is ever gathered.  The two sums land in the two columns of a
``(PAGE_TILE, 2)`` output block.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ref import fp_weights, _squared_weights

PAGE_TILE = 32         # pages per grid step: one uint8 (32, 128k) tile row


def _chunk_fp_kernel(pages_ref, w_ref, fp_ref):
    pages = pages_ref[...].astype(jnp.int32)          # (PAGE_TILE, S)
    for k in range(2):                                # one base per column
        fp_ref[:, k:k + 1] = jnp.sum(pages * w_ref[k:k + 1, :], axis=1,
                                     keepdims=True, dtype=jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def page_fingerprint_pallas(pages: jax.Array, *, interpret: bool) -> jax.Array:
    """Fingerprint (n_pages, page_size) uint8 pages → (n_pages, 2) int32.

    ``n_pages`` must be a multiple of PAGE_TILE (ops.py pads with zero pages
    and truncates).  Bit-identical to ``ref.page_fingerprint_ref``.
    """
    n_pages, page_size = pages.shape
    assert n_pages % PAGE_TILE == 0, "pad pages to PAGE_TILE (see ops.py)"
    w = jnp.stack([jnp.asarray(fp_weights(page_size)),
                   jnp.asarray(_squared_weights(page_size))])   # (2, S)

    return pl.pallas_call(
        _chunk_fp_kernel,
        grid=(n_pages // PAGE_TILE,),
        in_specs=[
            pl.BlockSpec((PAGE_TILE, page_size), lambda i: (i, 0)),
            pl.BlockSpec((2, page_size), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((PAGE_TILE, 2), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pages, 2), jnp.int32),
        interpret=interpret,
    )(pages, w)
