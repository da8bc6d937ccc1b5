"""The correctness check must fail the control and each fault a cell can
have, at a size a test run holds (the control is also read on the chip at
each cell's own size with ``bench/control.py``).

* control: the publisher's device scan cuts by a boundary rule one bit
  coarser than the configuration states;
* an answer altered where it is produced: one cut offset moved by a byte
  on the publisher, or one byte of one chunk flipped as a client stores it;
* a step that returns its state unchanged: a push that sends nothing, an
  upgrade that pulls nothing;
* half of the batch left out: each pull fetches half of its missing chunks.

The faults of the pulling fleet are planted in its process through a
``sitecustomize`` module on its ``PYTHONPATH``.  One chip has no exchange
between chips to leave out."""

import json
import os

import pytest

import control
import tiny
from repro.delivery import client as client_mod
from repro.kernels import ops

with open(os.path.join(tiny.bench.ROOT, "BENCHMARK.json")) as f:
    CELLS = [w["name"] for w in json.load(f)["workloads"]]


def failed_checks(result):
    return {k for k, c in result["checks"].items() if c["value"] > c["limit"]}


@pytest.mark.parametrize("name", CELLS)
def test_control_fails(name):
    sound = control.readings(tiny.cell(name), tiny.SEED, tiny.SECONDS,
                             control=False, impl="interpret")
    coarse = control.readings(tiny.cell(name), tiny.SEED, tiny.SECONDS,
                              control=True, impl="interpret")
    assert sound["correct"] and not coarse["correct"]
    assert coarse["checks"]["cuts_differ"] > 0
    assert coarse["checks"]["roots_differ"] > 0


def test_cut_moved_on_the_publisher(monkeypatch):
    device_scan = ops.device_scan

    def moved(impl):
        scan = device_scan(impl)

        def one_off(data, params):
            cuts = scan(data, params)
            cuts[len(cuts) // 2] += 1
            return cuts
        return one_off

    monkeypatch.setattr(ops, "device_scan", moved)
    result = tiny.run(tiny.cell("dockerhub-publish"))
    assert not result["correct"]
    assert {"cuts_differ", "fps_differ"} <= failed_checks(result)


def test_push_that_sends_nothing(monkeypatch):
    monkeypatch.setattr(client_mod.ImageClient, "_push",
                        lambda self, lineage, tag, parent_version=None:
                        client_mod.TransferReport(op="push", lineage=lineage,
                                                  tag=tag))
    result = tiny.run(tiny.cell("dockerhub-publish"))
    assert not result["correct"]
    assert "registry_differ" in failed_checks(result)


FLEET_FAULTS = {
    "upgrade_keeps_state": ("""
from repro.delivery import client, plan
client.ImageClient.upgrade = lambda self, lineage: plan.TransferReport(
    op="pull", lineage=lineage, tag="")
""", {"client_recipes_differ", "client_roots_differ", "client_bytes_differ"}),
    "half_the_chunks": ("""
from repro.delivery import client
_plan = client.ImageClient.plan_pull
def plan_pull(self, lineage, tag):
    p = _plan(self, lineage, tag)
    if tag != "v0":                 # set-up's seeding pull stays whole
        p.missing = p.missing[:len(p.missing) // 2]
    return p
client.ImageClient.plan_pull = plan_pull
""", {"ops_failed"}),
    "chunk_altered": ("""
from repro.core import store
_ingest = store.DedupStore.ingest_chunks
def ingest_chunks(self, name, fps, chunks, sizes, verify=True):
    chunks = dict(chunks)
    for fp in list(chunks)[:1]:
        chunks[fp] = bytes([chunks[fp][0] ^ 1]) + chunks[fp][1:]
    return _ingest(self, name, fps, chunks, sizes, verify)
store.DedupStore.ingest_chunks = ingest_chunks
""", {"client_bytes_differ"}),
}


@pytest.mark.parametrize("fault", sorted(FLEET_FAULTS))
def test_fleet_fault(fault, tmp_path, monkeypatch):
    code, must_fail = FLEET_FAULTS[fault]
    (tmp_path / "sitecustomize.py").write_text(
        "import sys\nif sys.argv[0].endswith('fleet_proc.py'):\n"
        + "".join("    " + line + "\n" for line in code.strip().splitlines()))
    monkeypatch.setenv("PYTHONPATH", str(tmp_path))
    result = tiny.run(tiny.cell("dockerhub-upgrade"))
    assert not result["correct"]
    assert must_fail <= failed_checks(result)
