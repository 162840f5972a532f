"""Device ms per step of the microbatch gradient accumulation: the
scan's carry add and the division by the microbatches (scope
`grad_accum`)."""
from chipbench.yardstick import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "grad_accum")
