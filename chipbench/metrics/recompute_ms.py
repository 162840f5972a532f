"""Device ms per step of the recompute that ``jax.checkpoint`` adds: ops
under JAX's ``rematted_computation``, whatever their scope (a cross-cut
of the other model metrics)."""
from chipbench.yardstick import scopes


def read(ctx):
    return scopes.scope_ms(ctx, scopes.REMAT)
