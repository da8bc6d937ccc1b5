"""Wire bytes (``TransferReport.total_wire_bytes``) of the window's pulls,
or of its pushes where nothing was pulled, over the artifact bytes those
operations delivered, in percent."""


def read(run):
    if run.pulls:
        raw = sum(p["raw"] for p in run.pulls)
        wire = sum(p["wire"] for p in run.pulls)
    else:
        raw, wire = run.push_raw, run.push_wire
    return 100.0 * wire / raw if raw else None
