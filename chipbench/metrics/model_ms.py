"""Device ms per step of the model's forward and backward (ops under
JAX's ``jvp(`` / ``transpose(`` names, and the microbatch loop's gradient
accumulation), from the trace."""


def read(ctx):
    if ctx.trace is None:
        return None
    v = ctx.trace.class_s("model")
    return v / ctx.window_steps * 1e3 if v > 0 else None
