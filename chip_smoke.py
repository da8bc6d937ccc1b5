"""Chip smoke test: drive the CDC ingest path once on one TPU, check it
bit-exact, and print one JSON line.

    python chip_smoke.py [--seed N]

One process, no children.  Data is made from ``--seed``.  Phases run in
order and any failure raises, so the script exits non-zero and prints no
result:

1. Device: platform, device kind and count, JAX and libtpu versions.  Exit
   non-zero unless JAX's first device is a TPU.
2. Kernels: the compiled gear-hash kernel over a 64 MiB seeded stream, and
   over a stream that holds every byte value and long 0x00/0xFF runs
   across a window edge, each bit-exact against ``cdc.gear_hash_stream``;
   then the compiled page-fingerprint kernel over 4096 pages of 4096 B,
   bit-exact against ``ref.page_fingerprint_ref`` run on the CPU.
3. Delivery: a seeded ~256 MiB image in three versions (in-place edits,
   inserts and deletes).  A publisher ``ImageClient`` with the device scan
   commits and pushes each over ``MuxSocketTransport`` to a ``Registry``
   behind ``AsyncRegistryServer``; a fresh client pulls v0 and upgrades to
   v2.  Every recipe, fingerprint and CDMT root must equal the host scan's,
   every ``materialize`` must be byte-identical, and the upgrade must move
   fewer chunk bytes than the image holds.

Timings printed here are smoke timings, not a benchmark.  The last line
of standard output is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

from repro.launch.compile_cache import configure_compile_cache  # noqa: E402

KERNEL_STREAM = 64 << 20          # bytes through the gear kernel
PAGES, PAGE_SIZE = 4096, 4096     # page-fingerprint batch
IMAGE = 256 << 20                 # uncompressed image, nginx/redis class
EDITS = 100                       # churn edits per version
HOST_WINDOW = 4 << 20             # host reference: bytes per worker task
HOST_WORKERS = 4                  # 8 workers of 8 MiB met a 40 GiB host's limit


class CompileCounter:
    """Counts XLA compilations (executables built, persistent-cache hits
    included) and their seconds, from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.count = 0
        self.hits = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self):
        return self.count, self.hits, self.seconds


def host_windows(buf: np.ndarray, pool: ThreadPoolExecutor, reduce) -> list:
    """``reduce(h, start)`` of each HOST_WINDOW-sized window of
    ``cdc.gear_hash_stream(buf)``, computed on a thread pool.  Each window
    re-reads the 31 bytes before it, so its hashes are the stream's."""
    from repro.core import cdc

    def one(start):
        lo = max(0, start - (cdc.GEAR_WINDOW - 1))
        h = cdc.gear_hash_stream(buf[lo:start + HOST_WINDOW])[start - lo:]
        return reduce(h, start)

    return list(pool.map(one, range(0, buf.size, HOST_WINDOW)))


def host_gear_hash(buf: np.ndarray, pool: ThreadPoolExecutor) -> np.ndarray:
    """``cdc.gear_hash_stream(buf)``."""
    parts = host_windows(buf, pool, lambda h, start: h)
    return np.concatenate(parts) if parts else np.zeros(0, np.uint32)


def host_recipe(data: bytes, params, pool: ThreadPoolExecutor):
    """Cut offsets and fingerprints of the host scan (``cdc.chunk_boundaries``
    computed window by window, so host memory stays bounded)."""
    from repro.core import cdc, hashing
    buf = np.frombuffer(data, np.uint8)
    mask = np.uint32(params.mask)
    parts = host_windows(
        buf, pool, lambda h, start: np.flatnonzero((h & mask) == 0) + start)
    candidate = np.concatenate([np.zeros(0, np.int64)] + parts) + 1
    ends = cdc.cuts_from_candidates(candidate, buf.size, params)
    starts = [0] + ends[:-1]
    fps = [hashing.chunk_fingerprint(data[s:e]) for s, e in zip(starts, ends)]
    return ends, fps


def edge_stream(rng: np.random.Generator, window: int) -> np.ndarray:
    """Every byte value, then long 0x00/0xFF runs straddling the edge of
    the first device window, then random bytes; length not a multiple of
    any tile."""
    n = window + (3 << 20) + 12345
    buf = np.frombuffer(rng.bytes(n), np.uint8).copy()
    buf[:1 << 16] = np.tile(np.arange(256, dtype=np.uint8), 256)
    buf[window - 100_000:window + 50_000] = 0x00
    buf[window + 50_000:window + 200_000] = 0xFF
    buf[-5000:] = 0x00
    return buf


def kernel_phase(rng: np.random.Generator, impl: str, stream_bytes: int,
                 pages: int, page_size: int, counter: CompileCounter,
                 pool: ThreadPoolExecutor) -> None:
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref

    stream = np.frombuffer(rng.bytes(stream_bytes), np.uint8)
    c0, _, s0 = counter.snapshot()
    t0 = time.perf_counter()
    got = ops.gear_hash(stream, impl)
    first = time.perf_counter() - t0
    c1, _, s1 = counter.snapshot()
    t0 = time.perf_counter()
    again = ops.gear_hash(stream, impl)
    warm = time.perf_counter() - t0
    want = host_gear_hash(stream, pool)
    if not (np.array_equal(got, want) and np.array_equal(again, want)):
        bad = np.flatnonzero(got != want)
        raise AssertionError(
            f"gear hash differs from cdc.gear_hash_stream at "
            f"{bad.size} of {stream.size} positions (first {bad[:5]})")
    print(f"kernel gear_hash: {stream.size} B bit-exact; compiles "
          f"{c1 - c0} in {s1 - s0:.3f} s; first call {first:.3f} s, warm "
          f"call {warm:.3f} s (smoke timing, not a benchmark)")

    edge = edge_stream(rng, ops.WINDOW)
    got = ops.gear_hash(edge, impl)
    if not np.array_equal(got, host_gear_hash(edge, pool)):
        raise AssertionError("gear hash of the edge stream differs from "
                             "cdc.gear_hash_stream")
    print(f"kernel gear_hash edge stream: {edge.size} B (all byte values, "
          f"0x00/0xFF runs across the window edge) bit-exact")

    page_arr = stream[:pages * page_size].reshape(pages, page_size)
    c0, _, s0 = counter.snapshot()
    t0 = time.perf_counter()
    fps = np.asarray(ops.page_fingerprints(jnp.asarray(page_arr), impl))
    first = time.perf_counter() - t0
    c1, _, s1 = counter.snapshot()
    t0 = time.perf_counter()
    np.asarray(ops.page_fingerprints(jnp.asarray(page_arr), impl))
    warm = time.perf_counter() - t0
    with jax.default_device(jax.devices("cpu")[0]):
        want = np.asarray(ref.page_fingerprint_ref(jnp.asarray(page_arr)))
    if not np.array_equal(fps, want):
        raise AssertionError(
            f"page fingerprints differ from ref.page_fingerprint_ref on "
            f"{int(np.any(fps != want, axis=1).sum())} of {pages} pages")
    print(f"kernel page_fingerprints: {pages} x {page_size} B bit-exact; "
          f"compiles {c1 - c0} in {s1 - s0:.3f} s; first call {first:.3f} s, "
          f"warm call {warm:.3f} s (smoke timing, not a benchmark)")


def image_versions(rng: np.random.Generator, size: int, edits: int):
    """Three versions of a container-like image: zipf-weighted dictionary
    words with ~20% incompressible 512-byte spans, then per version
    ``edits`` edits (60% in-place, 25% insert, 15% delete) of 16 B–64 KiB,
    so chunk boundaries shift."""
    words = np.concatenate([rng.integers(97, 123, (512, 11), np.uint8),
                            np.full((512, 1), 32, np.uint8)], axis=1)
    v = words[rng.zipf(1.35, size // 12 + 1) % 512].ravel()[:size].copy()
    spans = v[:size // 512 * 512].reshape(-1, 512)
    rows = rng.choice(spans.shape[0], spans.shape[0] // 5, replace=False)
    spans[rows] = rng.integers(0, 256, (rows.size, 512), np.uint8)
    versions = [v]
    for _ in range(2):
        pos = np.sort(rng.integers(0, v.size, edits))
        kinds = rng.random(edits)
        sizes = rng.integers(16, 64 << 10, edits)
        pieces, cur = [], 0
        for p, kind, n in zip(pos, kinds, sizes):
            if p < cur:
                continue                          # overlaps the last edit
            pieces.append(v[cur:p])
            if kind < 0.85:                       # in-place modify / insert
                pieces.append(np.frombuffer(rng.bytes(int(n)), np.uint8))
            cur = p if 0.6 <= kind < 0.85 else min(v.size, p + n)
        pieces.append(v[cur:])
        v = np.concatenate(pieces)
        versions.append(v)
    return [x.tobytes() for x in versions]


def delivery_phase(rng: np.random.Generator, impl: str, image_bytes: int,
                   edits: int, counter: CompileCounter,
                   pool: ThreadPoolExecutor) -> None:
    from repro.core import cdc
    from repro.core.cdmt import CDMT, DEFAULT_PARAMS
    from repro.core.registry import Registry
    from repro.delivery import (AsyncRegistryServer, ImageClient,
                                MuxSocketTransport, RegistryServer)
    from repro.kernels import ops

    params = cdc.DEFAULT_PARAMS
    versions = image_versions(rng, image_bytes, edits)
    tags = [f"v{i}" for i in range(len(versions))]
    print(f"delivery image: {', '.join(str(len(v)) for v in versions)} B")
    lineage = "image"
    c0, h0, s0 = counter.snapshot()
    server = AsyncRegistryServer(RegistryServer(Registry()), workers=8)
    transports = []
    try:
        def connect():
            transports.append(MuxSocketTransport(server.address))
            return transports[-1]

        publisher = ImageClient(connect(), scan=ops.device_scan(impl))
        for tag, data in zip(tags, versions):
            t0 = time.perf_counter()
            recipe = publisher.commit(lineage, tag, data)
            commit_s = time.perf_counter() - t0
            print(f"delivery commit {tag}: {len(recipe.fps)} chunks, "
                  f"{commit_s:.3f} s (smoke timing, not a benchmark)")
            push = publisher.push(lineage, tag)
            print(f"delivery push {tag}: {push.chunks_moved} chunks / "
                  f"{push.chunk_bytes} B")
            ends, fps = host_recipe(data, params, pool)
            if list(np.cumsum(recipe.sizes)) != ends or recipe.fps != fps:
                raise AssertionError(
                    f"{tag}: device-scan recipe ({len(recipe.fps)} chunks) "
                    f"differs from the host scan's ({len(fps)} chunks)")
            root = publisher.index_for_tag(lineage, tag).root
            if CDMT.build(fps, params=DEFAULT_PARAMS).root != root:
                raise AssertionError(f"{tag}: CDMT root differs from the "
                                     f"host scan's")
            print(f"delivery {tag}: {len(data)} B, {len(fps)} chunks, recipe "
                  f"and CDMT root == host scan's")
        c1, h1, s1 = counter.snapshot()

        puller = ImageClient(connect())
        pulled = puller.pull(lineage, tags[0])
        upgrade = puller.upgrade(lineage)
        puller.pull(lineage, tags[1])
        for tag, data in zip(tags, versions):
            if publisher.materialize(lineage, tag) != data:
                raise AssertionError(f"publisher materialize {tag} differs")
            if puller.materialize(lineage, tag) != data:
                raise AssertionError(f"puller materialize {tag} differs")
            remote = puller.tag_trees[f"{lineage}:{tag}"].root
            if remote != publisher.index_for_tag(lineage, tag).root:
                raise AssertionError(f"{tag}: pulled CDMT root differs")
        if upgrade.tag != tags[-1] or upgrade.chunk_bytes >= len(versions[-1]):
            raise AssertionError(
                f"upgrade to {upgrade.tag} moved {upgrade.chunk_bytes} chunk "
                f"bytes for a {len(versions[-1])} B image")
        print(f"delivery pull {tags[0]}: {pulled.chunks_moved} chunks / "
              f"{pulled.chunk_bytes} B; upgrade -> {upgrade.tag}: "
              f"{upgrade.chunks_moved} of {upgrade.chunks_total} chunks / "
              f"{upgrade.chunk_bytes} B for a {len(versions[-1])} B image; "
              f"materialize byte-identical for {', '.join(tags)}")
        print(f"delivery compilations during commits: {c1 - c0} "
              f"(persistent-cache hits {h1 - h0}, {s1 - s0:.3f} s)")
    finally:
        for t in transports:
            t.close()
        server.stop()


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    sys.stdout.reconfigure(line_buffering=True)

    cache_dir = configure_compile_cache()
    import jax
    from importlib import metadata

    devices = jax.devices()
    dev = devices[0]
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} jax={jax.__version__} libtpu={libtpu} "
          f"compile_cache={cache_dir}")
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, but JAX found platform "
                         f"{dev.platform!r}")

    counter = CompileCounter()
    rng = np.random.default_rng(args.seed)
    with ThreadPoolExecutor(max_workers=HOST_WORKERS) as pool:
        kernel_phase(rng, "pallas", KERNEL_STREAM, PAGES, PAGE_SIZE, counter,
                     pool)
        delivery_phase(rng, "pallas", IMAGE, EDITS, counter, pool)
    count, hits, secs = counter.snapshot()
    print(f"compilations in all: {count} (persistent-cache hits {hits}, "
          f"{secs:.3f} s)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
