"""A cell, configuration, traffic mix and per-layer metric added as new
files plus ``BENCHMARK.json`` entries are found by name: no file the
harness already has is edited.  The added mix runs another loop than the
cells' mixes: two publishes a round, then fresh clients with no state pull
at Poisson arrivals in bursts, each choosing one of two lineages by Zipf,
beside a seeded fleet that upgrades, while a third fleet pulls open-loop
through the whole window."""

import hashlib
import json
import os
import shutil

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


def test_added_cell_needs_no_edit(tmp_path):
    shutil.copytree(os.path.join(bench.ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(bench.ROOT, "src"), tmp_path / "src")
    before = digests(tmp_path / "bench")
    b = tmp_path / "bench"
    with open(os.path.join(bench.ROOT, "bench", "configs",
                           "dockerhub-nginx.json")) as f:
        cfg = json.load(f)
    cfg.update(name="random-blobs", blob_bytes=200_000)
    (b / "configs" / "random-blobs.json").write_text(json.dumps(cfg))
    (b / "configs" / "random-blobs.py").write_text(SOURCE)
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
    with open(os.path.join(bench.ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "random-blobs", "source": "made up",
                            "file": "bench/configs/random-blobs.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "blobs-publish",
                              "config": "random-blobs",
                              "traffic": "blob-stream", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "span_kinds", "unit": "count",
                              "better": "lower", "source": "host_clock",
                              "layer": "test", "moves": "wire_pct",
                              "workloads": ["blobs-publish"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    copied = bench.load_module(str(b / "run.py"))
    cell = copied.load_cell("blobs-publish", str(tmp_path))
    assert cell.generator == str(b / "configs" / "random-blobs.py")
    assert [m["name"] for m in cell.per_layer] == ["span_kinds"]
    result = copied.run_cell(cell, tiny.SEED, tiny.SECONDS, True,
                             impl="interpret")
    assert result["correct"], result["checks"]
    assert result["metrics"]["span_kinds"]["value"] > 0
    assert result["pulls"] >= 6 + 12 and result["failed"] == 0
    assert result["checks"]["client_recipes_differ"]["value"] == 0
    after = digests(b)
    assert {k: v for k, v in after.items() if k in before} == before
