"""The controller's fold per decode token round: the program's
``splitee.decode.fold`` total (byte metering, the bandit update, the exit
histogram and the next token's copy to the device) over its
``splitee.decode.step`` count, in ms (``ServeReport.telemetry``; absent
from a program without a tracer)."""


def read(ctx):
    report = getattr(ctx["driver"], "report", None)
    spans = (getattr(report, "telemetry", None) or {}).get("spans", {})
    step = spans.get("splitee.decode.step")
    fold = spans.get("splitee.decode.fold")
    if not step or not fold:
        return None
    return fold["total_ms"] / step["n"]
