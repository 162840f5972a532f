"""Device ms per step of the ops outside the gradient that are not
collectives: momentum, sign, the vote's encode and decode, the update."""


def read(ctx):
    if ctx.trace is None:
        return None
    v = ctx.trace.class_s("optimizer")
    return v / ctx.window_steps * 1e3 if v > 0 else None
