"""One offloaded decode step, from reading the split-layer hidden to the
cloud's token on the host: the program's ``splitee.decode.codec``,
``cloud`` and ``cloud_wait`` totals over its ``cloud_launches`` count,
in ms (``ServeReport.telemetry``; absent from a program without a
tracer, or when nothing was offloaded)."""


def read(ctx):
    report = getattr(ctx["driver"], "report", None)
    tel = getattr(report, "telemetry", None) or {}
    launches = tel.get("counts", {}).get("splitee.decode.cloud_launches")
    if not launches:
        return None
    spans = tel.get("spans", {})
    return sum(spans.get(f"splitee.decode.{k}", {}).get("total_ms", 0.0)
               for k in ("codec", "cloud", "cloud_wait")) / launches
