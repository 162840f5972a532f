"""The optimizer's least HBM bytes per step (``yardstick/opt_bytes``) over
its device time, as a share of the chip's published HBM bandwidth."""


def read(ctx):
    if ctx.trace is None:
        return None
    t = ctx.trace.class_s("optimizer") / ctx.window_steps
    if t <= 0:
        return None
    return 100.0 * ctx.optimizer_bytes / t / ctx.peaks["hbm_bytes_per_s"]
