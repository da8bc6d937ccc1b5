"""CDC invariants: reconstruction, determinism, byte-shift locality."""

import numpy as np
import pytest

from repro.core import cdc


def _rand(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, size=n,
                                                dtype=np.uint8).tobytes()


PARAMS = cdc.CDCParams(mask_bits=10, min_size=128, max_size=8192)


class TestReconstruction:
    def test_concat_reproduces_data(self):
        data = _rand(100_000)
        chunks = list(cdc.chunk_bytes(data, PARAMS))
        assert b"".join(chunks) == data

    def test_empty(self):
        assert list(cdc.chunk_bytes(b"", PARAMS)) == []

    def test_tiny(self):
        data = b"x"
        assert b"".join(cdc.chunk_bytes(data, PARAMS)) == data

    def test_bounds_respected(self):
        data = _rand(200_000)
        sizes = [len(c) for c in cdc.chunk_bytes(data, PARAMS)]
        assert all(s <= PARAMS.max_size for s in sizes)
        assert all(s >= PARAMS.min_size for s in sizes[:-1])  # last may be short

    def test_deterministic(self):
        data = _rand(50_000, seed=3)
        a = cdc.chunk_boundaries(data, PARAMS)
        b = cdc.chunk_boundaries(data, PARAMS)
        assert a == b

    def test_rabin_reference_agrees_on_reconstruction(self):
        data = _rand(60_000, seed=4)
        p = cdc.CDCParams(mask_bits=10, min_size=128, max_size=8192,
                          algorithm="rabin")
        chunks = list(cdc.chunk_bytes(data, p))
        assert b"".join(chunks) == data


class TestByteShiftResistance:
    """The paper's core CDC claim (Sec. III-A): an edit only perturbs
    chunks local to the edit."""

    def test_insert_preserves_most_chunks(self):
        data = _rand(300_000, seed=1)
        fps_a = set(cdc.chunk_boundaries(data, PARAMS))
        chunks_a = {bytes(c) for c in cdc.chunk_bytes(data, PARAMS)}
        edited = data[:150_000] + b"INSERTED" + data[150_000:]
        chunks_b = list(cdc.chunk_bytes(edited, PARAMS))
        shared = sum(1 for c in chunks_b if bytes(c) in chunks_a)
        assert shared / len(chunks_b) > 0.9, "edit must stay local"

    def test_prefix_insert_shifts_nothing_after_sync(self):
        data = _rand(200_000, seed=2)
        chunks_a = {bytes(c) for c in cdc.chunk_bytes(data, PARAMS)}
        edited = b"PREFIX" + data
        chunks_b = list(cdc.chunk_bytes(edited, PARAMS))
        shared = sum(1 for c in chunks_b if bytes(c) in chunks_a)
        # fixed-width chunking would share ~0 here (the byte-shift problem)
        assert shared / len(chunks_b) > 0.9


# Hypothesis property tests live in tests/test_properties.py (optional dep).


def test_mask_to_boundaries_matches_direct():
    data = np.frombuffer(_rand(50_000, seed=7), dtype=np.uint8)
    h = cdc.gear_hash_stream(data)
    mask = (h & np.uint32(PARAMS.mask)) == 0
    candidate = np.flatnonzero(mask) + 1
    assert cdc.cuts_from_candidates(candidate, data.size, PARAMS) == \
        cdc.chunk_boundaries(data, PARAMS)
