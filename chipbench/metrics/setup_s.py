"""Process start to the first timed step: imports, compile or cache load,
the state made on the device, and the check steps that warm up."""


def read(ctx):
    if ctx.trace is not None:
        return None
    return ctx.setup_s
