"""Model FLOPs of the traced steps (forward + backward, no recompute)
over the traced window, as a share of the chips' published bf16 peak."""


def read(ctx):
    if ctx.trace is None or ctx.trace.busy_s() <= 0:
        return None
    flops = ctx.flops_per_step * ctx.window_steps
    return 100.0 * flops / ctx.trace.window_s / (
        ctx.chips * ctx.peaks["bf16_flops"])
