"""Pallas TPU kernel: blockwise fused (flash) attention for prefill.

The 32k-token prefill shapes make attention the compute hot spot of the LM
substrate.  Standard flash decomposition: for each query tile, stream key/
value tiles through VMEM keeping a running (max, sum, weighted-V) in fp32 —
O(S) memory instead of O(S²), MXU-aligned (128×128) tiles.

Grid: (batch·heads, q_tiles, kv_tiles) with the kv axis innermost ("arbitrary"
semantics — accumulator carried in VMEM scratch across kv steps).  Causal
masking skips fully-masked kv tiles via a predicated early-out on the whole
tile (Mosaic turns uniform predicates into cheap scalar branches).

GQA is handled by the ops.py wrapper (q heads grouped per kv head before the
call), so the kernel sees matched head counts.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Q_TILE = 128
KV_TILE = 128
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                  *, scale: float, causal: bool, kv_tiles: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # causal: kv tile strictly after q tile contributes nothing
    run = jnp.logical_or(not causal,
                         ki * KV_TILE <= qi * Q_TILE + (Q_TILE - 1))

    @pl.when(run)
    def _step():
        q = q_ref[0].astype(jnp.float32)                     # (Q_TILE, D)
        k = k_ref[0].astype(jnp.float32)                     # (KV_TILE, D)
        v = v_ref[0].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = qi * Q_TILE + jax.lax.broadcasted_iota(
                jnp.int32, (Q_TILE, KV_TILE), 0)
            k_pos = ki * KV_TILE + jax.lax.broadcasted_iota(
                jnp.int32, (Q_TILE, KV_TILE), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)

        m_prev = m_scr[...]                                  # (Q_TILE, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                               # (Q_TILE, KV_TILE)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == kv_tiles - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "scale", "interpret"))
def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, scale: float | None = None,
                           interpret: bool) -> jax.Array:
    """Fused attention.  q/k/v: (BH, S, D) with S % 128 == 0, matched heads.

    Returns (BH, S, D) in q.dtype; fp32 accumulation inside.
    """
    bh, sq, d = q.shape
    skv = k.shape[1]
    assert sq % Q_TILE == 0 and skv % KV_TILE == 0
    scale = float(scale if scale is not None else 1.0 / np.sqrt(d))
    q_tiles, kv_tiles = sq // Q_TILE, skv // KV_TILE

    kernel = functools.partial(_flash_kernel, scale=scale, causal=causal,
                               kv_tiles=kv_tiles)
    return pl.pallas_call(
        kernel,
        grid=(bh, q_tiles, kv_tiles),
        in_specs=[
            pl.BlockSpec((1, Q_TILE, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, KV_TILE, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, KV_TILE, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, Q_TILE, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((Q_TILE, 1), jnp.float32),   # running max m
            pltpu.VMEM((Q_TILE, 1), jnp.float32),   # running sum l
            pltpu.VMEM((Q_TILE, d), jnp.float32),   # output accumulator
        ],
        interpret=interpret,
    )(q, k, v)
