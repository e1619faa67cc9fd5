"""Useful operations of the classify work served in the window over the
window times the chips' bf16 peak, in percent.

Useful: for each sample, the layers up to its chosen depth and that
exit's head; for an offloaded sample also the remaining layers and the
final head; padding rows count for nothing (the configuration's
``sample_flops`` arithmetic). The peak is bf16 also for the float32
classifier: at XLA's default precision a TPU multiplies float32 in one
bf16 pass."""


def read(ctx):
    driver = ctx["driver"]
    if not getattr(driver, "window_s", None):
        return None
    return 100.0 * driver.mfu()
