"""Device ms per step of the vote's pack stage (scope `vote_pack`): the
replica's signs into wire words."""
from chipbench.yardstick import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "vote_pack")
