"""Seconds of set-up spent tracing to jaxprs and lowering them to MLIR
(the program's ``jit.trace_ns`` + ``jit.lower_ns``), outermost phases
only."""
from chipbench.yardstick import scopes


def read(ctx):
    return scopes.setup_phase_s(ctx, "jit.trace_ns", "jit.lower_ns")
