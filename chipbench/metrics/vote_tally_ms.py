"""Device ms per step of the vote's tally stage (scope `vote_tally`):
the majority of what arrived."""
from chipbench.yardstick import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "vote_tally")
