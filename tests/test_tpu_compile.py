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
    assert "tpu_custom_call" in compiled.as_text()


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
