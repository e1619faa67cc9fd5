"""Layers the edge program's loop runs per decode token round: the
program's ``splitee.decode.edge_layers`` count (the round's deepest split
plus one) over its ``splitee.decode.steps`` count
(``ServeReport.telemetry``; absent from a program without a tracer or
without the layer counter)."""


def read(ctx):
    report = getattr(ctx["driver"], "report", None)
    counts = (getattr(report, "telemetry", None) or {}).get("counts", {})
    layers = counts.get("splitee.decode.edge_layers")
    steps = counts.get("splitee.decode.steps")
    if not layers or not steps:
        return None
    return layers / steps
