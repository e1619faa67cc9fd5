"""Host time per decode token round during which the session waits on no
device result: the program's ``splitee.decode.step`` total less its
``edge_wait`` and ``cloud_wait`` totals, over the step count, in ms
(``ServeReport.telemetry``; absent from a program without a tracer)."""


def read(ctx):
    report = getattr(ctx["driver"], "report", None)
    spans = (getattr(report, "telemetry", None) or {}).get("spans", {})
    step = spans.get("splitee.decode.step")
    if not step:
        return None
    waits = sum(spans.get(f"splitee.decode.{k}", {}).get("total_ms", 0.0)
                for k in ("edge_wait", "cloud_wait"))
    return (step["total_ms"] - waits) / step["n"]
