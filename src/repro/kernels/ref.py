"""Pure-jnp oracles for every Pallas kernel.

These are the test oracle and the semantics contract: tests sweep
shapes/dtypes and compare each kernel (``impl="interpret"`` on the CPU,
``impl="pallas"`` on a TPU) with the functions here.  ``ops`` runs them only
when a caller names ``impl="ref"``; nothing selects them in place of a
kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cdc import GEAR_WINDOW, gear_table

# ---------------------------------------------------------------------------
# Gear rolling hash (CDC boundary scan)
# ---------------------------------------------------------------------------


@jax.jit
def gear_hash_ref(data: jax.Array) -> jax.Array:
    """Rolling gear hash per byte position.

    ``h_i = sum_{j=0}^{31} 2^j * G[b_{i-j}]  (mod 2^32)`` — the unrolled form
    of ``h_i = 2*h_{i-1} + G[b_i]`` (the gear register forgets after 32
    shifts).  All arithmetic in int32: XLA int32 wraparound IS mod 2^32.
    Input: uint8 (n,). Output: uint32 (n,).
    """
    table = jnp.asarray(gear_table().view(np.int32))
    g = table[data.astype(jnp.int32)]                      # (n,) int32 gather
    n = data.shape[0]
    h = jnp.zeros((n,), dtype=jnp.int32)
    valid = jnp.arange(n)
    for j in range(GEAR_WINDOW):
        shifted = jnp.roll(g, j)
        shifted = jnp.where(valid >= j, shifted, 0)        # zero wrapped prefix
        h = h + (shifted << j)
    return jax.lax.bitcast_convert_type(h, jnp.uint32)


def boundary_mask_ref(data: jax.Array, mask_bits: int) -> jax.Array:
    """Candidate-boundary mask: hash low ``mask_bits`` bits all zero."""
    h = gear_hash_ref(data)
    return (h & jnp.uint32((1 << mask_bits) - 1)) == 0


# ---------------------------------------------------------------------------
# Parallel polynomial chunk fingerprint
# ---------------------------------------------------------------------------

FP_MULTIPLIER = np.int64(0x01000193)  # FNV prime, used as polynomial base


def fp_weights(page_size: int) -> np.ndarray:
    """w_i = p^(page_size-1-i) mod 2^32 as int32 (two's complement)."""
    w = np.zeros(page_size, dtype=np.uint64)
    acc = np.uint64(1)
    m = np.uint64(0xFFFFFFFF)
    with np.errstate(over="ignore"):
        for i in range(page_size - 1, -1, -1):
            w[i] = acc
            acc = (acc * np.uint64(FP_MULTIPLIER)) & m
    return w.astype(np.uint32).view(np.int32)


def page_fingerprint_ref(pages: jax.Array) -> jax.Array:
    """64-ish-bit fingerprints of fixed-size pages.

    Input: uint8 (n_pages, page_size). Output: int32 (n_pages, 2) — two
    independent polynomial fingerprints (base p and p^2) evaluated mod 2^32.
    XLA int32 arithmetic wraps (two's complement) — exactly mod 2^32.
    """
    n_pages, page_size = pages.shape
    w1 = jnp.asarray(fp_weights(page_size))                       # (S,)
    w2 = jnp.asarray(_squared_weights(page_size))
    b = pages.astype(jnp.int32)
    fp1 = jnp.sum(b * w1[None, :], axis=1, dtype=jnp.int32)
    fp2 = jnp.sum(b * w2[None, :], axis=1, dtype=jnp.int32)
    return jnp.stack([fp1, fp2], axis=-1)


@functools.lru_cache(maxsize=None)
def _squared_weights(page_size: int) -> np.ndarray:
    w = np.zeros(page_size, dtype=np.uint64)
    acc = np.uint64(1)
    m = np.uint64(0xFFFFFFFF)
    p2 = (np.uint64(FP_MULTIPLIER) * np.uint64(FP_MULTIPLIER)) & m
    with np.errstate(over="ignore"):
        for i in range(page_size - 1, -1, -1):
            w[i] = acc
            acc = (acc * p2) & m
    return w.astype(np.uint32).view(np.int32)


# ---------------------------------------------------------------------------
# Attention (flash-attention oracle)
# ---------------------------------------------------------------------------


def mha_ref(q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool = True,
            scale: float | None = None) -> jax.Array:
    """Plain softmax attention.  q: (B,H,S,D), k/v: (B,H,S,D) (kv heads
    already repeated to H).  fp32 accumulation."""
    b, h, s, d = q.shape
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if causal:
        qi = jnp.arange(s)[:, None]
        ki = jnp.arange(k.shape[2])[None, :]
        logits = jnp.where(qi >= ki, logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)
