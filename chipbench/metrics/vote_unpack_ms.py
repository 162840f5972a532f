"""Device ms per step of the vote's unpack stage (scope `vote_unpack`):
the decision decoded to signs."""
from chipbench.yardstick import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "vote_unpack")
