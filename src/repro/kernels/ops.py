"""jit'd public wrappers around the Pallas kernels.

Every call names its implementation with ``impl=``:

* ``"pallas"`` — the kernel compiled by Mosaic.  It needs a TPU and raises,
  naming the platform it found, when JAX's first device is anything else;
* ``"interpret"`` — the same kernel through the Pallas interpreter, which
  is how the tests run it on the CPU;
* ``"ref"`` — the pure-jnp oracle of ``ref.py``.

Nothing picks an implementation for the caller, so a run on the wrong
machine fails instead of quietly measuring the interpreter.  The wrappers
handle padding and windowing so callers never see tile sizes.
"""

from __future__ import annotations

import functools
from typing import Callable, Iterator, List, Literal, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cdc
from . import ref
from .chunk_fp import PAGE_TILE, page_fingerprint_pallas
from .flash_attention import Q_TILE, flash_attention_pallas
from .gear_cdc import BLOCK, TILE_BYTES, gear_hash_pallas

Impl = Literal["pallas", "interpret", "ref"]

# Bytes per device call of the gear kernel.  A stream is scanned in whole
# windows with the 4096 preceding bytes carried as the next window's halo;
# the last window pads to a power of two from BLOCK up, so a process
# compiles the kernel for at most a dozen lengths, whatever it ingests.
WINDOW = 64 << 20


def require_tpu() -> None:
    """Raise unless JAX's first device is a TPU."""
    platform = jax.devices()[0].platform
    if platform != "tpu":
        raise RuntimeError(
            f"impl='pallas' compiles for a TPU, but JAX found platform "
            f"{platform!r}; use impl='interpret' or impl='ref' off the chip")


def _interpret(impl: Impl) -> bool:
    if impl == "pallas":
        require_tpu()
        return False
    if impl == "interpret":
        return True
    raise ValueError(f"impl must be 'pallas' or 'interpret' here, got {impl!r}")


# ---------------------------------------------------------------------------
# CDC boundary scan
# ---------------------------------------------------------------------------


def _as_bytes(data) -> np.ndarray:
    if isinstance(data, (bytes, bytearray, memoryview)):
        return np.frombuffer(data, dtype=np.uint8)
    return np.asarray(data, dtype=np.uint8)


def _window_len(n: int) -> int:
    """Padded length of a window holding ``n`` bytes."""
    return min(WINDOW, max(BLOCK, 1 << (n - 1).bit_length()))


def _windows(buf: np.ndarray, impl: Impl, kernel: Callable
             ) -> Iterator[Tuple[int, int, jax.Array]]:
    """``(start, length, kernel output)`` for each window of ``buf``."""
    interpret = _interpret(impl)
    for start in range(0, buf.size, WINDOW):
        part = buf[start:start + WINDOW]
        size = _window_len(part.size)
        window = part if part.size == size else np.pad(part, (0, size - part.size))
        prev = (buf[start - TILE_BYTES:start] if start
                else np.zeros(TILE_BYTES, np.uint8))
        first = np.array([start == 0], np.int32)
        yield start, part.size, kernel(window, prev, first,
                                       interpret=interpret)


def gear_hash(data, impl: Impl) -> np.ndarray:
    """Rolling gear hash (uint32) per byte of a uint8 stream."""
    buf = _as_bytes(data)
    if impl == "ref":
        return np.asarray(ref.gear_hash_ref(jnp.asarray(buf)))
    out = np.empty(buf.size, np.uint32)
    for start, n, h in _windows(buf, impl, gear_hash_pallas):
        out[start:start + n] = np.asarray(h)[:n]
    return out


@functools.partial(jax.jit, static_argnames=("mask_bits", "interpret"))
def _boundary_mask(window, prev, first, *, mask_bits: int, interpret: bool):
    """Candidate chunk boundaries: low ``mask_bits`` of the rolling hash zero."""
    h = gear_hash_pallas(window, prev, first, interpret=interpret)
    return (h & jnp.uint32((1 << mask_bits) - 1)) == 0


def chunk_boundaries_accelerated(data, params: cdc.CDCParams,
                                 impl: Impl) -> List[int]:
    """Full CDC: device boundary scan + host min/max pass (DESIGN.md §4).
    The same cut offsets as ``cdc.chunk_boundaries``."""
    if params.algorithm != "gear":
        raise ValueError(
            f"the device scan computes the gear hash, not {params.algorithm!r}")
    buf = _as_bytes(data)
    if impl == "ref":
        candidate = np.flatnonzero((gear_hash(buf, impl) & params.mask) == 0)
    else:
        kernel = functools.partial(_boundary_mask, mask_bits=params.mask_bits)
        candidate = np.concatenate(
            [np.zeros(0, np.int64)]
            + [np.flatnonzero(np.asarray(mask)[:n]) + start
               for start, n, mask in _windows(buf, impl, kernel)])
    return cdc.cuts_from_candidates(candidate + 1, buf.size, params)


def device_scan(impl: Impl) -> Callable[[bytes, cdc.CDCParams], List[int]]:
    """A boundary scan for ``DedupStore(scan=...)`` / ``ImageClient(scan=...)``
    that runs the gear kernel on the device.  With ``impl="pallas"`` it
    checks here that JAX's first device is a TPU, and raises otherwise: it
    never falls back to the interpreter or to the host scan."""
    _interpret(impl)
    return functools.partial(chunk_boundaries_accelerated, impl=impl)


# ---------------------------------------------------------------------------
# Page fingerprints
# ---------------------------------------------------------------------------


def page_fingerprints(pages: jax.Array, impl: Impl) -> jax.Array:
    """(n_pages, page_size) uint8 → (n_pages, 2) int32 fingerprint pairs."""
    if impl == "ref":
        return ref.page_fingerprint_ref(pages)
    n = pages.shape[0]
    pad = (-n) % PAGE_TILE
    padded = jnp.pad(pages, ((0, pad), (0, 0)))
    out = page_fingerprint_pallas(padded, interpret=_interpret(impl))
    return out[:n]


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    impl: Impl, causal: bool = True,
                    scale: Optional[float] = None) -> jax.Array:
    """Fused attention over (B, H, S, D) with (B, KVH, S, D) k/v (GQA ok).

    Repeats kv heads to match q heads, flattens (B,H) for the kernel, pads S
    to the 128 tile.  fp32 accumulation; returns q.dtype.
    """
    b, h, s, d = q.shape
    kvh = k.shape[1]
    if kvh != h:
        assert h % kvh == 0
        rep = h // kvh
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    if impl == "ref":
        return ref.mha_ref(q, k, v, causal=causal, scale=scale)

    skv = k.shape[2]
    pad_q = (-s) % Q_TILE
    pad_kv = (-skv) % Q_TILE
    qf = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0))).reshape(b * h, s + pad_q, d)
    kf = jnp.pad(k, ((0, 0), (0, 0), (0, pad_kv), (0, 0))).reshape(b * h, skv + pad_kv, d)
    vf = jnp.pad(v, ((0, 0), (0, 0), (0, pad_kv), (0, 0))).reshape(b * h, skv + pad_kv, d)
    out = flash_attention_pallas(qf, kf, vf, causal=causal, scale=scale,
                                 interpret=_interpret(impl))
    return out[:, :s, :].reshape(b, h, s, d)
