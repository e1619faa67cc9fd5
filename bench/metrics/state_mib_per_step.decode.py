"""Cache state the decode layer loops carry per token round, in MiB: the
program's ``splitee.decode.state_bytes`` count (each round's edge loop
over layers 0..its deepest split, and each cloud launch's loop from above
its shallowest offloaded split, priced at every row's whole state of each
layer they run) over its ``splitee.decode.steps`` count
(``ServeReport.telemetry``; absent from a program without the counter)."""


def read(ctx):
    report = getattr(ctx["driver"], "report", None)
    counts = (getattr(report, "telemetry", None) or {}).get("counts", {})
    state = counts.get("splitee.decode.state_bytes")
    steps = counts.get("splitee.decode.steps")
    if not state or not steps:
        return None
    return state / steps / 2 ** 20
