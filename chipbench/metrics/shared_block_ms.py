"""Device ms per step of zamba2's weight-shared attention + SwiGLU block
with its norms (scope `shared_block`), at all of its calls."""
from chipbench.yardstick import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "shared_block")
