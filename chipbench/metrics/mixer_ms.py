"""Device ms per step of the Mamba2 mixers (scope `mixer`: projections,
convolution, SSD scan, gated norm), forward, backward and recompute."""
from chipbench.yardstick import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "mixer")
