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
from repro.obs import NULL_TRACER, Tracer
from . import ref
from .chunk_fp import PAGE_TILE, page_fingerprint_pallas
from .flash_attention import Q_TILE, flash_attention_pallas
from .gear_cdc import BLOCK, LANES, TILE_BYTES, gear_hash_pallas

Impl = Literal["pallas", "interpret", "ref"]

# Bytes per device call of the gear kernel.  A stream is scanned in whole
# windows with the 4096 preceding bytes carried as the next window's halo;
# the last window pads to a power of two from BLOCK up, so a process
# compiles the kernel for at most a dozen lengths, whatever it ingests.
WINDOW = 64 << 20

# The candidate mask leaves the device bit-packed: 32 stream positions per
# uint32 word, so one row of 128 words covers 32 rows of the kernel's
# (rows, 128) output, WORD_BITS * LANES stream positions.
WORD_BITS = 32
ROW_SPAN = WORD_BITS * LANES


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


def _windows(buf: np.ndarray, impl: Impl, kernel: Callable,
             tracer: Tracer = NULL_TRACER
             ) -> Iterator[Tuple[int, int, int, jax.Array]]:
    """``(start, length, bytes copied in, kernel output)`` for each window
    of ``buf``.

    The window is staged (the last one padded on the host) and copied to
    the device with its halo in a ``scan.h2d`` span, and the kernel runs in
    a ``scan.kernel`` span; with the tracer enabled each span waits for its
    work, so it times what the host waited for."""
    interpret = _interpret(impl)
    block = tracer.enabled
    for start in range(0, buf.size, WINDOW):
        with tracer.span("scan.h2d"):
            part = buf[start:start + WINDOW]
            size = _window_len(part.size)
            window = (part if part.size == size
                      else np.pad(part, (0, size - part.size)))
            prev = (buf[start - TILE_BYTES:start] if start
                    else np.zeros(TILE_BYTES, np.uint8))
            first = np.array([start == 0], np.int32)
            args = jax.device_put((window, prev, first))
            if block:
                jax.block_until_ready(args)
        with tracer.span("scan.kernel"):
            out = kernel(*args, interpret=interpret)
            if block:
                out.block_until_ready()
        del args                    # the device copies go with the call
        yield start, part.size, window.nbytes + prev.nbytes + first.nbytes, out


def gear_hash(data, impl: Impl) -> np.ndarray:
    """Rolling gear hash (uint32) per byte of a uint8 stream."""
    buf = _as_bytes(data)
    if impl == "ref":
        return np.asarray(ref.gear_hash_ref(jnp.asarray(buf)))
    out = np.empty(buf.size, np.uint32)
    for start, n, _, h in _windows(buf, impl, gear_hash_pallas):
        out[start:start + n] = np.asarray(h)[:n]
    return out


@functools.partial(jax.jit, static_argnames=("mask_bits", "interpret"))
def _boundary_mask(window, prev, first, *, mask_bits: int, interpret: bool):
    """Candidate chunk boundaries of an ``n``-byte window (the low
    ``mask_bits`` of the rolling hash zero), bit-packed into
    ``(n // ROW_SPAN, 128)`` uint32 words, n/8 bytes.

    Bit ``k`` of word ``(R, c)`` is window position ``(32 R + k) * 128 + c``:
    rows ``32 R`` to ``32 R + 31`` of the kernel's ``(rows, 128)`` hash fold
    into row ``R`` of words, lane by lane.  ``_candidates`` decodes it."""
    h = gear_hash_pallas(window, prev, first, interpret=interpret)
    h = h.reshape(-1, WORD_BITS, LANES)
    hit = (h & jnp.uint32((1 << mask_bits) - 1)) == 0
    bit = jnp.uint32(1) << jnp.arange(WORD_BITS, dtype=jnp.uint32)[:, None]
    return jnp.where(hit, bit, jnp.uint32(0)).sum(axis=1, dtype=jnp.uint32)


def _candidates(packed: np.ndarray, n: int) -> Tuple[np.ndarray, int]:
    """The set bits of a packed mask (``_boundary_mask``'s order) as
    ascending window positions below ``n``, and the count of non-zero words
    decoded.  Only rows of words that hold a position below ``n`` are
    searched, and only their non-zero words are expanded into positions."""
    words = packed[:-(-n // ROW_SPAN)].reshape(-1)
    at = np.flatnonzero(words)
    # position of each word's bit 0: row R = at // 128, lane c = at % 128
    base = (at // LANES) * ROW_SPAN + at % LANES
    bits = np.unpackbits(words[at].astype("<u4").view(np.uint8),
                         bitorder="little").view(bool)
    k = np.flatnonzero(bits)                  # word index * 32 + bit
    pos = base[k // WORD_BITS] + k % WORD_BITS * LANES
    pos.sort()
    return pos[:np.searchsorted(pos, n)], at.size


def chunk_boundaries_accelerated(data, params: cdc.CDCParams, impl: Impl,
                                 tracer: Tracer = NULL_TRACER) -> List[int]:
    """Full CDC: device boundary scan + host min/max pass (DESIGN.md §4).
    The same cut offsets as ``cdc.chunk_boundaries``.

    Traced as one ``scan`` span holding, per window, ``scan.h2d``,
    ``scan.kernel``, ``scan.d2h`` (the bit-packed mask back to the host,
    n/8 bytes for an n-byte window) and ``scan.cuts`` (the candidate offsets
    decoded from its non-zero words), then one ``scan.cuts`` for the min/max
    pass; the counts are the ``scan`` span's attributes, ``candidate_words``
    the non-zero words decoded."""
    if params.algorithm != "gear":
        raise ValueError(
            f"the device scan computes the gear hash, not {params.algorithm!r}")
    buf = _as_bytes(data)
    with tracer.span("scan") as sp:
        if impl == "ref":
            found = [np.flatnonzero((gear_hash(buf, impl) & params.mask) == 0)]
        else:
            kernel = functools.partial(_boundary_mask,
                                       mask_bits=params.mask_bits)
            found = [np.zeros(0, np.int64)]
            windows = h2d_bytes = d2h_bytes = candidate_words = 0
            for start, n, copied, packed in _windows(buf, impl, kernel,
                                                     tracer):
                with tracer.span("scan.d2h"):
                    host = np.asarray(packed)
                with tracer.span("scan.cuts"):
                    offsets, words = _candidates(host, n)
                    found.append(offsets + start)
                windows += 1
                h2d_bytes += copied
                d2h_bytes += host.nbytes
                candidate_words += words
            sp.annotate(windows=windows, h2d_bytes=h2d_bytes,
                        d2h_bytes=d2h_bytes, candidate_words=candidate_words)
        with tracer.span("scan.cuts"):
            candidate = np.concatenate(found)
            cuts = cdc.cuts_from_candidates(candidate + 1, buf.size, params)
        sp.annotate(candidates=candidate.size, cuts=len(cuts))
    return cuts


def device_scan(impl: Impl, tracer: Tracer = NULL_TRACER
                ) -> Callable[[bytes, cdc.CDCParams], List[int]]:
    """A boundary scan for ``DedupStore(scan=...)`` / ``ImageClient(scan=...)``
    that runs the gear kernel on the device.  With ``impl="pallas"`` it
    checks here that JAX's first device is a TPU, and raises otherwise: it
    never falls back to the interpreter or to the host scan.  ``tracer``
    records each scan's spans (see ``chunk_boundaries_accelerated``)."""
    _interpret(impl)
    return functools.partial(chunk_boundaries_accelerated, impl=impl,
                             tracer=tracer)


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
