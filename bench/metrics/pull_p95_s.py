"""95th percentile of the wall time of every pull completed in the window,
each timed from its wave's start."""

import numpy as np


def read(run):
    if not run.pulls:
        return None
    return float(np.percentile([p["seconds"] for p in run.pulls], 95))
