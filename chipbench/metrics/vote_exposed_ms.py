"""Device ms per step in which a collective runs on a chip and no other
op does: the part of the vote's exchange that nothing hides, from the
trace, averaged over the chips."""


def read(ctx):
    if ctx.trace is None or ctx.trace.class_s("collective") <= 0:
        return None
    return ctx.trace.exposed_s("collective") / ctx.window_steps * 1e3
