"""Bytes scanned in the window, at the chip's peak HBM bandwidth, over the
device-busy time inside the scan spans.  Every scan must read its input
bytes, so the work counted does not change with how the scan is built."""

from trace_reduce import roofline_pct


def read(run):
    if run.trace is None:
        return None
    return roofline_pct(run.scan_bytes, run.trace.busy_in_spans("bench.scan"),
                        run.device_kind)
