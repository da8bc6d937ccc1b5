"""Self time of the ``plan_pull`` spans (index and recipe fetch, Algorithm
2 compare) over the summed time of the window's pulls."""


def read(run):
    pull = run.pull_parts.get("pull")
    return 100.0 * run.pull_parts["plan"] / pull if pull else None
