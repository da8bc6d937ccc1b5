"""Share of the publish window's wall time inside ``ImageClient.push``
(transport and registry on the push path)."""


def read(run):
    push = run.spans.get("bench.push")
    if not push or run.window_s <= 0:
        return None
    return 100.0 * push / run.window_s
