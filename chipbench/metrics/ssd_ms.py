"""Device ms per step of the SSD chunked scan inside the mixers (scope
`ssd`), forward, backward and recompute."""
from chipbench.yardstick import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "ssd")
