"""Time inside the ``fetch_batch`` spans of each pull, overlapping pool
threads counted once, over the summed time of the window's pulls."""


def read(run):
    pull = run.pull_parts.get("pull")
    return 100.0 * run.pull_parts["fetch"] / pull if pull else None
