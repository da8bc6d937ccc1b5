"""Share of the publish window's wall time in ``ImageClient.commit``
outside the boundary scan: chunk slicing, blake2b, ``chunks.put`` and the
CDMT build."""


def read(run):
    commit = run.spans.get("bench.commit")
    if not commit or run.window_s <= 0:
        return None
    return 100.0 * (commit - run.spans.get("bench.scan", 0.0)) / run.window_s
