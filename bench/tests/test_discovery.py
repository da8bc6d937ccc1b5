"""A cell, configuration, traffic mix and per-layer metric added as new
files plus ``BENCHMARK.json`` entries are found by name: no file the
harness already has is edited.  The added mix runs another loop than the
cells' mixes: two publishes a round, then fresh clients with no state pull
at Poisson arrivals in bursts, each choosing one of two lineages by Zipf,
beside a seeded fleet that upgrades, while a third fleet pulls open-loop
through the whole window.

A second added configuration holds 256 MiB of weights on the device and
brings its own rehearsal sizes: its source steps the weights in ``next()``
and gives each artifact as a save that copies them to the host and
serializes them, which the harness times on the window's clock."""

import hashlib
import json
import os
import shutil
import struct

import pytest

import run as bench
import tiny

SOURCE = '''
import numpy as np


class Version:
    def __init__(self, tag, data):
        self.tag, self.data = tag, data
        self.artifacts = [("blobs", data), ("blobs-b", data[::-1])]


class Blobs:
    def __init__(self, cfg, seed, spans):
        self.rng = np.random.default_rng([seed, 7])
        self.n, self.k, self.spans = cfg["blob_bytes"], 0, spans

    def next(self):
        with self.spans.paused("bench.generate"):
            self.k += 1
            return Version(f"v{self.k - 1}", self.rng.bytes(self.n))

    def reference(self, version):
        return [("blobs", version.data), ("blobs-b", version.data[::-1])]

    def free(self):
        pass


def make(cfg, seed, spans):
    return Blobs(cfg, seed, spans)
'''

READER = '''
def read(run):
    return float(len(run.spans))
'''

WEIGHTS_SOURCE = '''
import struct

import jax
import jax.numpy as jnp
import numpy as np

A, C = 1664525, 1013904223      # a step: w = w * A + C + k, mod 2**32
MIX = 2654435761


def initial(n, seed):
    return np.arange(n, dtype=np.uint32) * np.uint32(MIX) \\
        + np.uint32(seed % 2**32)


def serialize(k, words):
    return struct.pack("<4sQ", b"WGT1", k) + words.tobytes()


class Version:
    def __init__(self, k, save):
        self.tag, self.k = f"v{k}", k
        self.artifacts = [("weights", save)]


class Weights:
    """Weights on the device; each version steps the top share of them."""

    def __init__(self, cfg, seed, spans):
        a = cfg["artifact"]
        self.n, self.seed, self.spans, self.k = a["bytes"] // 4, seed, spans, 0
        self.lo = self.n - int(self.n * a["trained_share"])
        self.w = jax.jit(lambda s: jnp.arange(self.n, dtype=jnp.uint32)
                         * jnp.uint32(MIX) + s)(jnp.uint32(seed % 2**32))
        lo = self.lo
        self.step = jax.jit(
            lambda w, k: w.at[lo:].set(w[lo:] * jnp.uint32(A)
                                       + jnp.uint32(C) + k)
        ).lower(self.w, jnp.uint32(0)).compile()

    def next(self):
        with self.spans.paused("bench.generate"):
            if self.k:
                self.w = self.step(self.w, jnp.uint32(self.k))
                self.w.block_until_ready()
            w, k = self.w, self.k
            self.k += 1
            return Version(k, lambda: serialize(k, np.asarray(w)))

    def reference(self, version):
        w = initial(self.n, self.seed)
        for i in range(1, version.k + 1):
            w[self.lo:] = w[self.lo:] * np.uint32(A) + np.uint32(C) \\
                + np.uint32(i)
        return [("weights", serialize(version.k, w))]

    def free(self):
        self.w = self.step = None


def make(cfg, seed, spans):
    return Weights(cfg, seed, spans)
'''

SAVE_READER = '''
def read(run):
    save = run.spans.get("bench.save")
    if not save or run.window_s <= 0:
        return None
    return 100.0 * save / run.window_s
'''

FULL_BYTES = 256 << 20
REHEARSED_BYTES = 204_800
HEADER = struct.calcsize("<4sQ")


def digests(root):
    out = {}
    for dirpath, _, files in os.walk(root):
        for name in files:
            if "__pycache__" not in dirpath:
                path = os.path.join(dirpath, name)
                with open(path, "rb") as f:
                    out[os.path.relpath(path, root)] = \
                        hashlib.sha256(f.read()).hexdigest()
    return out


def added(tmp_path):
    """The harness copied under ``tmp_path`` with the added files and
    entries; the digests of the copied files before anything was added."""
    shutil.copytree(os.path.join(bench.ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(bench.ROOT, "src"), tmp_path / "src")
    before = digests(tmp_path / "bench")
    b = tmp_path / "bench"
    with open(os.path.join(bench.ROOT, "bench", "configs",
                           "dockerhub-nginx.json")) as f:
        nginx = json.load(f)
    cfg = dict(nginx, name="random-blobs", blob_bytes=200_000)
    (b / "configs" / "random-blobs.json").write_text(json.dumps(cfg))
    (b / "configs" / "random-blobs.py").write_text(SOURCE)
    cfg = dict(nginx, name="device-weights",
               artifact={"lineage": "weights", "bytes": FULL_BYTES,
                         "trained_share": 0.25})
    (b / "configs" / "device-weights.json").write_text(json.dumps(cfg))
    (b / "configs" / "device-weights.py").write_text(WEIGHTS_SOURCE)
    (b / "configs" / "device-weights.rehearsal.json").write_text(
        json.dumps({"artifact": {"bytes": REHEARSED_BYTES}}))
    (b / "traffic" / "blob-stream.json").write_text(json.dumps({
        "setup_versions": 2,
        "fleets": {
            "fresh": {"clients": 4, "processes": 2, "pipeline_depth": 2,
                      "connections": 1, "state": "empty"},
            "warm": {"clients": 2, "processes": 1, "pipeline_depth": 2,
                     "connections": 1},
            "open": {"clients": 4, "processes": 2, "pipeline_depth": 2,
                     "connections": 1}},
        "background": [
            {"fleet": "open", "arrival": {"kind": "poisson", "per_s": 12.0},
             "lineage": {"zipf": 1.1}}],
        "round": [
            {"op": "publish"}, {"op": "publish"},
            {"op": "pull", "fleet": "fresh", "mode": "cold",
             "arrival": {"kind": "poisson", "per_s": 40.0, "burst": 2},
             "lineage": {"zipf": 1.1}},
            {"op": "pull", "fleet": "warm"}],
        "check_clients": 1}))
    (b / "metrics" / "span_kinds.py").write_text(READER)
    (b / "metrics" / "save_pct.py").write_text(SAVE_READER)
    with open(os.path.join(bench.ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"] += [
        {"name": "random-blobs", "source": "made up",
         "file": "bench/configs/random-blobs.json", "reduced": [],
         "why": "test"},
        {"name": "device-weights", "source": "made up",
         "file": "bench/configs/device-weights.json", "reduced": [],
         "why": "test"}]
    spec["workloads"] += [
        {"name": "blobs-publish", "config": "random-blobs",
         "traffic": "blob-stream", "chips": 1, "why": "test"},
        {"name": "weights-publish", "config": "device-weights",
         "traffic": "publish", "chips": 1, "why": "test"}]
    for m in spec["end_to_end"]:
        if m["name"] == "publish_MBps":
            m["workloads"].append("weights-publish")
    spec["per_layer"] += [
        {"name": "span_kinds", "unit": "count", "better": "lower",
         "source": "host_clock", "layer": "test", "moves": "wire_pct",
         "workloads": ["blobs-publish"]},
        {"name": "save_pct", "unit": "%", "better": "lower",
         "source": "program_span", "layer": "test", "moves": "publish_MBps",
         "workloads": ["weights-publish"]}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp_path, before


def unchanged(root, before):
    after = digests(root / "bench")
    return {k: v for k, v in after.items() if k in before} == before


def test_added_cell_needs_no_edit(tmp_path):
    root, before = added(tmp_path)
    b = root / "bench"
    copied = bench.load_module(str(b / "run.py"))
    cell = copied.load_cell("blobs-publish", str(root))
    assert cell.generator == str(b / "configs" / "random-blobs.py")
    assert [m["name"] for m in cell.per_layer] == ["span_kinds"]
    result = copied.run_cell(cell, tiny.SEED, tiny.SECONDS, True,
                             impl="interpret")
    assert result["correct"], result["checks"]
    assert result["metrics"]["span_kinds"]["value"] > 0
    assert result["pulls"] >= 6 + 12 and result["failed"] == 0
    assert result["checks"]["client_recipes_differ"]["value"] == 0
    assert unchanged(root, before)


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_device_weights_saved_on_the_clock(tmp_path, monkeypatch, trace):
    root, before = added(tmp_path)
    chip = bench.load_cell("weights-publish", str(root))
    assert chip.cfg["artifact"]["bytes"] == FULL_BYTES
    c = tiny.cell("weights-publish", str(root))
    assert c.cfg["artifact"] == {"lineage": "weights",
                                 "bytes": REHEARSED_BYTES,
                                 "trained_share": 0.25}
    runs = []

    class Recorded(bench.Run):
        def __init__(self):
            super().__init__()
            runs.append(self)

    monkeypatch.setattr(bench, "Run", Recorded)
    result = tiny.run(c, trace=trace)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["window_compiles"] == 0
    assert "bench.save" in result["window_spans"]
    # one save a commit, each of the serialized weights
    saved = len(result["chunks"]) * (HEADER + REHEARSED_BYTES)
    assert saved > 0
    assert runs[0].published_bytes == runs[0].scan_bytes == saved
    if trace:
        assert result["metrics"]["save_pct"]["value"] > 0
        assert result["device"]["generate_busy_s"] >= 0
    else:
        assert set(result["metrics"]) == {"publish_MBps", "wire_pct",
                                          "setup_s"}
    assert unchanged(root, before)


def test_configuration_without_rehearsal_sizes_is_refused(tmp_path,
                                                          monkeypatch):
    root, before = added(tmp_path)
    missing = root / "bench" / "configs" / "device-weights.rehearsal.json"
    missing.unlink()
    monkeypatch.setattr(bench, "run_cell", lambda *a, **k: pytest.fail(
        "a run started"))
    with pytest.raises(FileNotFoundError) as e:
        tiny.run(tiny.cell("weights-publish", str(root)))
    assert str(missing) in str(e.value)
    assert unchanged(root, before)
