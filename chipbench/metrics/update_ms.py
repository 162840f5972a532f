"""Device ms per step of the sign optimizer outside the vote: the
momentum (scope `sign_momentum`) and the update with the learning rate
and weight decay (scope `sign_update`)."""
from chipbench.yardstick import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "sign_momentum", "sign_update")
