"""Time inside the ``ingest`` spans (coverage check and ``chunks.put``)
over the summed time of the window's pulls."""


def read(run):
    pull = run.pull_parts.get("pull")
    return 100.0 * run.pull_parts["ingest"] / pull if pull else None
