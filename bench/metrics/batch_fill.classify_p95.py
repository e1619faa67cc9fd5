"""Mean fill of the micro-batches the request scheduler formed, from
``RequestScheduler.snapshot()["mean_batch_fill"]`` (rows over capacity)."""


def read(ctx):
    snap = getattr(ctx["driver"], "snapshot", None)
    if not snap or snap.get("mean_batch_fill") is None:
        return None
    return 100.0 * snap["mean_batch_fill"]
