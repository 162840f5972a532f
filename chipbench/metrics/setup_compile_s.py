"""Seconds of set-up in backend compiles or their loads from the
persistent cache (the program's ``jit.compile_ns``)."""
from chipbench.yardstick import scopes


def read(ctx):
    return scopes.setup_phase_s(ctx, "jit.compile_ns")
