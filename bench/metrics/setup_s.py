"""Seconds from process start to the window's first operation."""


def read(run):
    return run.setup_s
