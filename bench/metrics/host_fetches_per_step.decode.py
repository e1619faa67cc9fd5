"""Blocking fetches of device results per decode token round: the
program's ``splitee.decode.host_fetches`` count over its
``splitee.decode.steps`` count (``ServeReport.telemetry``; absent from a
program that does not count its fetches)."""


def read(ctx):
    report = getattr(ctx["driver"], "report", None)
    counts = (getattr(report, "telemetry", None) or {}).get("counts", {})
    steps = counts.get("splitee.decode.steps")
    fetches = counts.get("splitee.decode.host_fetches")
    if not steps or fetches is None:
        return None
    return fetches / steps
