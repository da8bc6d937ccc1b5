"""The trace reduction, on a trace recorded on one TPU v5e (a 2 MiB commit
through ``DedupStore.ingest`` with the device scan, the scan wrapped in a
``bench.scan`` annotation and the commit in ``bench.commit``), and on
made-up events."""

import os

import pytest

import trace_reduce as tr

RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "scan_2mib.xplane.pb")


class Ev:
    def __init__(self, name, start, dur):
        self.name, self.start_ns, self.duration_ns = name, start, dur


class Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


class Profile:
    def __init__(self, planes):
        self.planes = planes


def made_up():
    device = Plane("/device:TPU:0", [
        Line("XLA Modules", [Ev("jit_a", 100, 50), Ev("jit_b", 300, 100)]),
        Line("XLA Ops", [Ev("%k.1 = s32[8] custom-call()", 110, 30),
                         Ev("%fusion = pred[8] fusion()", 140, 5),
                         Ev("%k.1 = s32[8] custom-call()", 320, 60)])])
    host = Plane("/host:CPU", [Line("python", [
        Ev("bench.window", 0, 1000), Ev("bench.commit", 0, 600),
        Ev("bench.scan", 90, 80), Ev("bench.scan", 290, 120),
        Ev("bench.push", 600, 400), Ev("PjitFunction(x)", 95, 3)])])
    return tr.Trace(Profile([device, host]))


def test_busy_is_the_union_of_device_intervals():
    t = made_up()
    assert t.window == (0, 1000)
    assert t.busy() == pytest.approx(150e-9)          # 50 + 100, ops inside
    assert t.busy_in_spans("bench.scan") == pytest.approx(150e-9)
    assert t.busy_in_spans("bench.push") == 0


def test_idle_gaps_go_to_the_innermost_host_span():
    gaps = dict(made_up().idle_gaps())
    # gaps [0,100), [150,300) and [400,1000): the pieces inside a scan
    # ([90,100), [150,170), [290,300), [400,410)) are the scan's,
    # [600,1000) the push's, the rest the commit's
    assert gaps == pytest.approx({"bench.commit": 400e-9,
                                  "bench.scan": 50e-9,
                                  "bench.push": 400e-9})


def test_device_ops_by_name():
    ops = dict(made_up().device_ops())
    assert ops == pytest.approx({"%k.1": 90e-9, "%fusion": 5e-9})


def test_recorded_trace():
    t = tr.Trace.load(RECORDED)
    assert list(t.device_busy) == ["/device:TPU:0"]
    assert set(t.spans) == {"bench.commit", "bench.scan"}
    # one _boundary_mask module of 410,846 ns holds the kernel and the mask
    assert t.busy() == pytest.approx(410_846e-9)
    assert t.busy_in_spans("bench.scan") == pytest.approx(410_846e-9)
    gaps = dict(t.idle_gaps())
    assert set(gaps) == {"bench.commit", "bench.scan"}
    assert sum(gaps.values()) + t.busy() == pytest.approx(t.window_s)
    assert gaps["bench.commit"] > gaps["bench.scan"]
    assert [name for name, _ in t.device_ops()] == ["%gear_hash_pallas.1",
                                                   "%fusion"]


def test_scan_roofline_from_bytes_and_peaks():
    t = tr.Trace.load(RECORDED)
    busy = t.busy_in_spans("bench.scan")
    got = tr.roofline_pct(2 << 20, busy, "TPU v5 lite")
    assert got == pytest.approx(100 * (2 << 20) / 819e9 / busy)
    assert 0 < got < 100


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks for device kind"):
        tr.roofline_pct(1 << 20, 1e-3, "TPU v9 imaginary")
    assert tr.roofline_pct(0, 1e-3, "TPU v9 imaginary") is None
