"""Device ms per step of the collectives (all-gather, all-reduce,
reduce-scatter, collective-permute, all-to-all): the vote's exchange,
from the trace, averaged over the chips."""


def read(ctx):
    if ctx.trace is None:
        return None
    v = ctx.trace.class_s("collective")
    return v / ctx.window_steps * 1e3 if v > 0 else None
