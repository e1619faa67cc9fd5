"""Share of the window's decode steps that launched the cloud resume:
the harness's count of ``cloud_fn`` calls over ``edge_fn`` calls."""


def read(ctx):
    counts = ctx["driver"].probe.counts
    if not counts.get("edge_fn"):
        return None
    return 100.0 * counts.get("cloud_fn", 0) / counts["edge_fn"]
