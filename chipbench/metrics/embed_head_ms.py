"""Device ms per step of the tied embedding, the final norm, the logits
and the cross-entropy (scopes `embed` and `lm_head`)."""
from chipbench.yardstick import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "embed", "lm_head")
