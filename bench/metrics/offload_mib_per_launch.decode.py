"""Wire bytes per cloud launch, in MiB: the program's
``splitee.decode.offload_bytes`` count (each offloaded row's hidden plus
the per-step state slice of the layers at or below its split, by the
cache manager's closed form) over its ``splitee.decode.cloud_launches``
count (``ServeReport.telemetry``; absent from a program without the
counter, or when nothing was offloaded)."""


def read(ctx):
    report = getattr(ctx["driver"], "report", None)
    counts = (getattr(report, "telemetry", None) or {}).get("counts", {})
    wire = counts.get("splitee.decode.offload_bytes")
    launches = counts.get("splitee.decode.cloud_launches")
    if not wire or not launches:
        return None
    return wire / launches / 2 ** 20
