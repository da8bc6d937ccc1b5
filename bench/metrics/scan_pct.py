"""Share of the publish window's wall time inside the device boundary
scan (the scan callable the benchmark hands to ``ImageClient``)."""


def read(run):
    scan = run.spans.get("bench.scan")
    if not scan or run.window_s <= 0:
        return None
    return 100.0 * scan / run.window_s
