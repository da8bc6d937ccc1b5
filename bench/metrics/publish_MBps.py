"""Artifact bytes committed and pushed in the window, in 10^6 B, over the
window's wall time (the clock stops only while the benchmark makes the
next version's bytes or steps the trainer)."""


def read(run):
    if not run.published_bytes or run.window_s <= 0:
        return None
    return run.published_bytes / 1e6 / run.window_s
