"""Kernel-layer benchmark on the host: CDC boundary-scan throughput of the
numpy path, chunk hashing, and the jnp/interpret kernel paths.

Every row is a CPU rate.  The Pallas rows run the interpreter, which
checks correctness and says nothing about the kernels' speed on a TPU;
no device rate has been measured here.
"""

from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np

from repro.core import cdc, hashing
from repro.kernels import ops, ref

from benchmarks.common import Report, Timer


def run() -> Report:
    rep = Report("kernels")
    rng = np.random.default_rng(0)
    data = rng.integers(0, 256, size=8 * 2**20, dtype=np.uint8)  # 8 MiB

    with Timer() as t:
        cdc.gear_hash_stream(data)
    rep.add(kernel="gear_host_numpy", mbytes_per_s=len(data) / t.s / 2**20,
            note="32-tap shifted-add convolution")

    with Timer() as t:
        list(cdc.chunk_bytes(data.tobytes()))
    rep.add(kernel="cdc_end_to_end_host", mbytes_per_s=len(data) / t.s / 2**20,
            note="boundaries + slicing")

    with Timer() as t:
        hashing.fingerprint_many(
            [data[i:i + 4096].tobytes() for i in range(0, len(data), 4096)])
    rep.add(kernel="blake2b_chunks", mbytes_per_s=len(data) / t.s / 2**20,
            note="registry-grade ids")

    pages = data[:2**20].reshape(-1, 1024)
    out = ops.page_fingerprints(jnp.asarray(pages), impl="ref")
    out.block_until_ready()
    with Timer() as t:
        ops.page_fingerprints(jnp.asarray(pages), impl="ref").block_until_ready()
    rep.add(kernel="page_fp_jnp_ref", mbytes_per_s=pages.size / t.s / 2**20,
            note="device fast-path oracle")

    small = jnp.asarray(data[:65536])
    with Timer() as t:
        np.asarray(ops.gear_hash(small, impl="interpret"))
    rep.add(kernel="gear_pallas_interpret", mbytes_per_s=small.size / t.s / 2**20,
            note="correctness path only (Python-interpreted on CPU)")
    return rep


if __name__ == "__main__":
    run().print_csv()
