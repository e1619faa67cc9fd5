"""Device idle share of the traced window: 1 - (union of device-op
intervals / window), averaged over the chips (bench/trace_reduce.py)."""


def read(ctx):
    trace = ctx.get("trace")
    if not trace or trace.get("idle_share") is None:
        return None
    return 100.0 * trace["idle_share"]
