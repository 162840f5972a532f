"""Global training tokens of the steps completed in the window, over the
window's wall time (host clock, profiler off)."""


def read(ctx):
    if ctx.trace is not None or not ctx.window_steps:
        return None
    return ctx.window_steps * ctx.tokens_per_step / ctx.window_s
