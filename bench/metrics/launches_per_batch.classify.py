"""Device launches per micro-batch in the window: the harness's count of
calls to ``edge_fn``, ``edge_fn_s``, ``edge_scan_fn`` and ``cloud_fn``
over the micro-batches the window started."""

LAUNCHES = ("edge_fn", "edge_fn_s", "edge_scan_fn", "cloud_fn")


def read(ctx):
    driver = ctx["driver"]
    batches = getattr(driver, "batches", 0)
    if not batches:
        return None
    return sum(driver.probe.counts.get(n, 0) for n in LAUNCHES) / batches
