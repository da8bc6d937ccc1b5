"""Every cell of ``BENCHMARK.json`` rehearsed on the CPU at a tiny size
(interpret-mode kernels, reached only from here), and ``run.py``'s refusal
to run without a TPU."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import run as bench
import tiny

with open(os.path.join(bench.ROOT, "BENCHMARK.json")) as f:
    SPEC = json.load(f)
CELLS = [w["name"] for w in SPEC["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_rehearsal(name):
    c = tiny.cell(name)
    result = tiny.run(c)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["window_compiles"] == 0
    assert set(result["metrics"]) == {m["name"] for m in c.end_to_end}
    assert "setup_s" in result["metrics"] and len(result["metrics"]) >= 2
    assert list(result)[-1] == "checks"
    assert result["versions_checked"] >= 1
    for fleet in c.traffic.get("fleets", {}).values():
        assert result["pulls"] >= fleet["clients"]


@pytest.mark.parametrize("name", CELLS)
def test_cell_rehearsal_traced(name):
    c = tiny.cell(name)
    result = tiny.run(c, trace=True)
    assert result["correct"], result["checks"]
    host_metrics = {m["name"] for m in c.per_layer
                    if m["source"] != "device_trace"}
    assert host_metrics <= set(result["metrics"])
    assert result["device"]["window_s"] > 0
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


def run_py(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "bench", "run.py"),
         "--workload", CELLS[0], "--seed", str(tiny.SEED), "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_run_without_a_tpu_exits_nonzero():
    p = run_py(bench.ROOT)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "needs 1 TPU chip" in p.stderr


def test_run_with_only_the_benchmark_files_exits_nonzero(tmp_path):
    shutil.copytree(os.path.join(bench.ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(bench.ROOT, "BENCHMARK.json"), tmp_path)
    p = run_py(str(tmp_path), {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert p.stdout == ""
