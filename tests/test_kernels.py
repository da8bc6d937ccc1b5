"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import cdc
from repro.kernels import ops, ref
from repro.kernels.gear_cdc import BLOCK, LANES
from repro.obs import Tracer


def _bytes(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, size=n, dtype=np.uint8)


class TestGearCDC:
    @pytest.mark.parametrize("n", [1, 100, BLOCK - 1, BLOCK, BLOCK + 1,
                                   2 * BLOCK + 777, 3 * BLOCK])
    def test_matches_ref(self, n):
        data = jnp.asarray(_bytes(n, seed=n))
        out_ref = np.asarray(ref.gear_hash_ref(data))
        out_pl = np.asarray(ops.gear_hash(data, impl="interpret"))
        np.testing.assert_array_equal(out_pl, out_ref)

    def test_matches_host_numpy(self):
        raw = _bytes(50_000, seed=1)
        h_np = cdc.gear_hash_stream(raw)
        h_ref = np.asarray(ref.gear_hash_ref(jnp.asarray(raw)))
        np.testing.assert_array_equal(h_np, h_ref)

    def test_boundary_mask_roundtrip(self):
        """Device boundary scan + host min/max pass == pure host CDC."""
        params = cdc.CDCParams(mask_bits=10, min_size=128, max_size=8192)
        raw = _bytes(80_000, seed=2).tobytes()
        assert ops.chunk_boundaries_accelerated(raw, params, impl="interpret") \
            == cdc.chunk_boundaries(raw, params)

    def test_blockwise_halo_correct(self):
        """Hashes at block boundaries depend on the previous block's tail —
        the halo operand must carry it."""
        data = jnp.asarray(_bytes(2 * BLOCK, seed=3))
        full = np.asarray(ops.gear_hash(data, impl="interpret"))
        reference = np.asarray(ref.gear_hash_ref(data))
        np.testing.assert_array_equal(full[BLOCK - 2: BLOCK + 2],
                                      reference[BLOCK - 2: BLOCK + 2])

    @pytest.mark.parametrize("n,window,mask_bits,zero_run,edges", [
        (BLOCK + 777, ops.WINDOW, 8, None, ()),
        (3 * BLOCK + 4097, BLOCK, 8, None, ()),
        (2 * BLOCK + 99, BLOCK, 1, (BLOCK - 5000, 2 * BLOCK + 50), ()),
        (BLOCK + 100, ops.WINDOW, 6, None,
         (0, 31, 32, 127, 128, 4095, 4096, BLOCK + 99)),
    ], ids=["odd_length", "windows", "dense", "edges"])
    def test_packed_mask_decodes_exactly(self, monkeypatch, n, window,
                                         mask_bits, zero_run, edges):
        """The bit-packed mask gives exactly the host's candidates: packed in
        the documented bit order, decoded in order, none from the padding.
        With ``mask_bits`` 1 every zero byte's hash is a candidate, so the
        zero run packs whole words of ones and so does the padded tail."""
        monkeypatch.setattr(ops, "WINDOW", window)
        buf = _bytes(n, seed=n)
        if zero_run:
            buf[slice(*zero_run)] = 0
        params = cdc.CDCParams(mask_bits=mask_bits, min_size=1,
                               max_size=1 << 30)   # a cut after each one
        for p in edges:                   # make position p a candidate
            buf[p] = next(b for b in range(256) if (
                cdc.gear_hash_stream(np.r_[buf[max(0, p - 31):p], b])[-1]
                & params.mask) == 0)
        want = np.flatnonzero((cdc.gear_hash_stream(buf) & params.mask) == 0)
        assert set(edges) <= set(want)

        tracer = Tracer(enabled=True)
        cuts = ops.chunk_boundaries_accelerated(buf, params, "interpret",
                                                tracer)
        assert cuts == cdc.chunk_boundaries(buf, params) \
            == sorted(set(want + 1) | {n})

        # each window's words against the host's mask of the padded stream
        kernel = functools.partial(ops._boundary_mask, mask_bits=mask_bits)
        windows = list(ops._windows(buf, "interpret", kernel))
        padded = [ops._window_len(size) for _, size, _, _ in windows]
        hit = (cdc.gear_hash_stream(np.pad(buf, (0, sum(padded) - n)))
               & params.mask) == 0
        weight = np.uint32(1) << np.arange(ops.WORD_BITS,
                                           dtype=np.uint32)[:, None]
        packed, words = [], 0
        for (start, size, _, out), length in zip(windows, padded):
            rows = hit[start:start + length].reshape(-1, ops.WORD_BITS, LANES)
            packed.append(np.asarray(out))
            np.testing.assert_array_equal(
                packed[-1], (rows * weight).sum(axis=1, dtype=np.uint32))
            words += np.count_nonzero(
                rows[:-(-size // ops.ROW_SPAN)].any(axis=1))
        dense = mask_bits == 1
        assert hit[n:].any() == dense     # the padding packs candidates
        assert any((w == 0xFFFFFFFF).any() for w in packed) == dense
        [scan] = tracer.take()
        assert scan.attrs == {
            "windows": len(windows),
            "h2d_bytes": sum(copied for _, _, copied, _ in windows),
            "d2h_bytes": sum(padded) // 8, "candidate_words": words,
            "candidates": want.size, "cuts": len(cuts)}


class TestChunkFingerprint:
    @pytest.mark.parametrize("n_pages,page", [(1, 256), (7, 512), (256, 256),
                                              (300, 1024), (513, 128)])
    def test_matches_ref(self, n_pages, page):
        pages = jnp.asarray(_bytes(n_pages * page, seed=n_pages).reshape(n_pages, page))
        np.testing.assert_array_equal(
            np.asarray(ops.page_fingerprints(pages, impl="interpret")),
            np.asarray(ref.page_fingerprint_ref(pages)))

    def test_distinct_pages_distinct_fps(self):
        pages = jnp.asarray(_bytes(64 * 256, seed=5).reshape(64, 256))
        fps = np.asarray(ops.page_fingerprints(pages, impl="ref"))
        assert len({tuple(r) for r in fps}) == 64


class TestFlashAttention:
    @pytest.mark.parametrize("b,h,kvh,s,d,dtype", [
        (1, 4, 4, 128, 64, jnp.float32),
        (2, 4, 2, 256, 64, jnp.float32),      # GQA
        (2, 8, 1, 256, 128, jnp.float32),     # MQA
        (1, 4, 4, 384, 64, jnp.bfloat16),     # non-tile-multiple S
        (1, 2, 2, 512, 32, jnp.float32),
    ])
    def test_matches_ref(self, b, h, kvh, s, d, dtype):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (b, h, s, d), dtype)
        k = jax.random.normal(ks[1], (b, kvh, s, d), dtype)
        v = jax.random.normal(ks[2], (b, kvh, s, d), dtype)
        o_ref = ops.flash_attention(q, k, v, impl="ref")
        o_pl = ops.flash_attention(q, k, v, impl="interpret")
        tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
        np.testing.assert_allclose(np.asarray(o_pl, np.float32),
                                   np.asarray(o_ref, np.float32),
                                   atol=tol, rtol=tol)

    def test_non_causal(self):
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q = jax.random.normal(ks[0], (1, 2, 256, 64), jnp.float32)
        k = jax.random.normal(ks[1], (1, 2, 256, 64), jnp.float32)
        v = jax.random.normal(ks[2], (1, 2, 256, 64), jnp.float32)
        np.testing.assert_allclose(
            np.asarray(ops.flash_attention(q, k, v, causal=False, impl="interpret")),
            np.asarray(ops.flash_attention(q, k, v, causal=False, impl="ref")),
            atol=2e-5, rtol=2e-5)


class TestBlockwiseJnpAttention:
    """The scan-based in-model attention must agree with naive attention."""

    @pytest.mark.parametrize("s,bq,bkv", [(256, 64, 64), (512, 128, 256)])
    def test_matches_naive(self, s, bq, bkv):
        from repro.models import layers as L
        ks = jax.random.split(jax.random.PRNGKey(2), 3)
        q = jax.random.normal(ks[0], (2, s, 4, 32), jnp.float32)
        k = jax.random.normal(ks[1], (2, s, 4, 32), jnp.float32)
        v = jax.random.normal(ks[2], (2, s, 4, 32), jnp.float32)
        out_b = L.blockwise_attention(q, k, v, causal=True, block_q=bq,
                                      block_kv=bkv)
        out_n = L.naive_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out_b), np.asarray(out_n),
                                   atol=2e-5, rtol=2e-5)

    def test_mla_shaped_dv_neq_dq(self):
        from repro.models import layers as L
        ks = jax.random.split(jax.random.PRNGKey(3), 3)
        q = jax.random.normal(ks[0], (1, 256, 4, 48), jnp.float32)
        k = jax.random.normal(ks[1], (1, 256, 4, 48), jnp.float32)
        v = jax.random.normal(ks[2], (1, 256, 4, 32), jnp.float32)   # dv≠dq
        out_b = L.blockwise_attention(q, k, v, causal=True, block_q=64,
                                      block_kv=64)
        out_n = L.naive_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out_b), np.asarray(out_n),
                                   atol=2e-5, rtol=2e-5)
