"""Useful operations of the decode work in the window over the window
times the chips' bf16 peak, in percent.

Useful: each push's prefill, then per token the layers up to its depth
and one head; an offload adds the remaining layers and the final head.
Masked layers and the exit heads nobody reads count for nothing."""


def read(ctx):
    driver = ctx["driver"]
    if not getattr(driver, "window_s", None):
        return None
    return 100.0 * driver.mfu()
