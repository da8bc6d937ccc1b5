"""The main-path kernels compile for a TPU v5e, and the device scan on the
commit path gives the host scan's recipes.

The compile tests describe a v5e topology that is not attached and compile
the kernels at their real sizes with Mosaic: what the chip's compiler
refuses fails here, with no chip.  The topology is described inside a
module-scoped fixture (never at import), which skips where libtpu cannot
describe it.  Nothing runs on a chip in this file.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import cdc
from repro.core.registry import Registry
from repro.delivery import ImageClient, RegistryServer, WireTransport
from repro.kernels import ops
from repro.kernels.chunk_fp import page_fingerprint_pallas
from repro.kernels.gear_cdc import TILE_BYTES, gear_hash_pallas
from repro.launch import compile_cache
from repro.obs import Tracer


@pytest.fixture(scope="module")
def no_compile_cache():
    """A described chip's compiles are written to a persistent cache but
    cannot be read back without the chip: keep the cache off here."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


@pytest.fixture(scope="module")
def topo(no_compile_cache):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_gear_kernel_compiles_for_v5e_at_64mib(one_chip):
    compiled = jax.jit(
        lambda w, p, f: gear_hash_pallas(w, p, f, interpret=False)
    ).lower(_shape((64 << 20,), jnp.uint8, one_chip),
            _shape((TILE_BYTES,), jnp.uint8, one_chip),
            _shape((1,), jnp.int32, one_chip)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "%gear_cdc" in text        # the name a profiler trace shows


def test_packed_boundary_mask_compiles_for_v5e_at_64mib(one_chip):
    """The device scan's jitted step: the kernel, then the candidate mask
    packed 32 positions to a uint32 word, 8 MiB out for 64 MiB in."""
    compiled = ops._boundary_mask.lower(
        _shape((64 << 20,), jnp.uint8, one_chip),
        _shape((TILE_BYTES,), jnp.uint8, one_chip),
        _shape((1,), jnp.int32, one_chip),
        mask_bits=12, interpret=False).compile()
    out = compiled.out_info
    assert (out.shape, out.dtype) == (((64 << 20) // 32 // 128, 128),
                                      jnp.uint32)
    assert compiled.memory_analysis().output_size_in_bytes == 8 << 20
    assert "%gear_cdc" in compiled.as_text()


def test_page_fingerprint_kernel_compiles_for_v5e_at_4096x4096(one_chip):
    compiled = jax.jit(
        lambda p: page_fingerprint_pallas(p, interpret=False)
    ).lower(_shape((4096, 4096), jnp.uint8, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


# ------------------------------------------------------- device scan, on CPU

PARAMS = cdc.CDCParams(mask_bits=10, min_size=128, max_size=8192)


def _versions(seed=11, size=300_000):
    """Three versions; edits, inserts and deletes shift chunk boundaries."""
    rng = np.random.default_rng(seed)
    data = bytearray(rng.integers(0, 256, size=size, dtype=np.uint8).tobytes())
    data[1000:9000] = b"\x00" * 8000            # a run no boundary falls in
    out = [bytes(data)]
    for _ in range(2):
        pos = int(rng.integers(0, len(data) - 600))
        data[pos:pos + 512] = rng.bytes(512)
        ins = int(rng.integers(0, len(data)))
        data[ins:ins] = rng.bytes(333)
        cut = int(rng.integers(0, len(data) - 200))
        del data[cut:cut + 150]
        out.append(bytes(data))
    return out


def test_device_scan_client_matches_host_scan_and_pulls():
    versions = _versions()
    server = RegistryServer(Registry())
    device = ImageClient(WireTransport(server), cdc_params=PARAMS,
                         scan=ops.device_scan("interpret"))
    host = ImageClient(None, cdc_params=PARAMS)
    for i, data in enumerate(versions):
        got = device.commit("app", f"v{i}", data)
        want = host.commit("app", f"v{i}", data)
        assert (got.fps, got.sizes) == (want.fps, want.sizes)
        assert device.index_for_tag("app", f"v{i}").root == \
            host.index_for_tag("app", f"v{i}").root
        device.push("app", f"v{i}")
    puller = ImageClient(WireTransport(server), cdc_params=PARAMS)
    for i, data in enumerate(versions):
        puller.pull("app", f"v{i}")
        assert puller.materialize("app", f"v{i}") == data


@pytest.mark.parametrize("window", [128 << 10, 1 << 20])
def test_traced_commit_spans_match_recipe_and_store(monkeypatch, window):
    """A commit through the traced device scan records the span tree the
    benchmark reads, with counts equal to the recipe's and the store's;
    its recipe is byte-identical to an untraced and a host-scan commit."""
    monkeypatch.setattr(ops, "WINDOW", window)
    tracer = Tracer(enabled=True)
    traced = ImageClient(None, cdc_params=PARAMS, tracer=tracer,
                         scan=ops.device_scan("interpret", tracer=tracer))
    untraced = ImageClient(None, cdc_params=PARAMS,
                           scan=ops.device_scan("interpret"))
    host = ImageClient(None, cdc_params=PARAMS)
    for i, data in enumerate(_versions()[:2]):
        store = traced.store
        new_before, dup_before = store.new_chunk_bytes, store.dup_chunk_bytes
        stored_before = len(store.chunks._mem)
        recipe = traced.commit("app", f"v{i}", data)
        assert recipe.to_json() \
            == untraced.commit("app", f"v{i}", data).to_json() \
            == host.commit("app", f"v{i}", data).to_json()

        [root] = tracer.take()
        assert root.name == "commit" and root.attrs["bytes"] == len(data)
        assert [c.name for c in root.children] == [
            "scan", "commit.hash", "commit.put", "commit.cdmt"]
        scan, hashed, put, cdmt = root.children
        starts = range(0, len(data), window)
        padded = [ops._window_len(min(window, len(data) - s)) for s in starts]
        assert [c.name for c in scan.children] == [
            "scan.h2d", "scan.kernel", "scan.d2h", "scan.cuts"] * len(starts) \
            + ["scan.cuts"]
        # the mask leaves the device 32 positions to a uint32 word, bit k
        # of word (R, c) at position (32 R + k) * 128 + c of its window; the
        # words of each row R that holds a stream byte are decoded
        hit = (cdc.gear_hash_stream(
            data + bytes(starts[-1] + padded[-1] - len(data)))
            & PARAMS.mask) == 0
        words = 0
        for s in starts:
            rows = -(-min(window, len(data) - s) // ops.ROW_SPAN)
            words += np.count_nonzero(hit[s:s + rows * ops.ROW_SPAN].reshape(
                rows, 32, 128).any(axis=1))
        assert scan.attrs == {
            "windows": len(starts),
            "h2d_bytes": sum(padded) + len(starts) * (TILE_BYTES + 4),
            "d2h_bytes": sum(padded) // 8, "candidate_words": words,
            "candidates": np.count_nonzero(hit[:len(data)]),
            "cuts": len(recipe.fps)}
        assert hashed.attrs == {"chunks": len(recipe.fps),
                                "bytes": sum(recipe.sizes)}
        assert put.attrs == {
            "new_chunks": len(store.chunks._mem) - stored_before,
            "new_bytes": store.new_chunk_bytes - new_before,
            "dup_bytes": store.dup_chunk_bytes - dup_before}
        assert put.attrs["new_bytes"] + put.attrs["dup_bytes"] == len(data)
        assert (put.attrs["dup_bytes"] > 0) == (i > 0)
        assert cdmt.attrs["nodes_created"] > 0
        assert root.duration >= sum(c.duration for c in root.children)


def test_device_scan_refuses_a_non_tpu_platform():
    platform = jax.devices()[0].platform
    if platform == "tpu":
        pytest.skip("a TPU is attached")
    with pytest.raises(RuntimeError, match=repr(platform)):
        ops.device_scan("pallas")
    with pytest.raises(RuntimeError, match=repr(platform)):
        ops.gear_hash(np.zeros(10, np.uint8), "pallas")


@pytest.mark.parametrize("env_dir", [None, "elsewhere"])
def test_compile_cache_directory(monkeypatch, tmp_path, env_dir):
    old_dir = jax.config.jax_compilation_cache_dir
    old_min = jax.config.jax_persistent_cache_min_compile_time_secs
    try:
        if env_dir is None:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            want = os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), ".jax_cache")
        else:
            want = str(tmp_path / env_dir)
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
            jax.config.update("jax_compilation_cache_dir", want)
        assert compile_cache.configure_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    finally:
        jax.config.update("jax_compilation_cache_dir", old_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          old_min)
