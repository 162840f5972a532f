"""Device time per program scope, and set-up time per JAX phase.

The trainer names its layers inside the compiled step with
``jax.named_scope`` (``src/repro/obs/scopes.py``): the scope is a path
segment of each op's ``op_name``. Under the gradient it is wrapped in
JAX's transform names, ``jvp(mixer)`` in the forward and
``transpose(jvp(mixer))`` in the backward, and under ``jax.checkpoint``
the recompute sits below ``rematted_computation``. A scope matches a whole
segment once those wrappers are taken off, never a part of one:
``dynamic_update_slice`` is not ``sign_update``.

Each op event of a reduced trace (``yardstick/trace.py``) carries the
``op_name`` that ``parse_hlo`` gave it: an unnamed fusion has the first
op name of the majority class of the computation it calls. Times are self
times inside the window, averaged over the chips, as ``class_s`` takes
them.

Set-up phases come from the program's compile watch
(``repro.obs.recorder``): the ``jit.*`` counters logged between the
process start and the first timed step. A program without the watch, or
a run not started by ``chipbench/run.py``, reads nothing.
"""
from __future__ import annotations

import re
import sys
from typing import Dict, Iterable, Optional

REMAT = "rematted_computation"
_WRAPPER = re.compile(r"^(?:jvp|transpose|vmap)\((.*)\)$")


def segments(op_name: str) -> set:
    """The path segments of an ``op_name``, each without the transform
    wrappers around it (``transpose(jvp(ssd))`` -> ``ssd``)."""
    out = set()
    for seg in op_name.split("/"):
        m = _WRAPPER.match(seg)
        while m:
            seg = m.group(1)
            m = _WRAPPER.match(seg)
        out.add(seg)
    return out


def in_scope(op_name: str, scopes: Iterable[str]) -> bool:
    """Whether the op is under any of `scopes`."""
    return bool(op_name) and not segments(op_name).isdisjoint(scopes)


def scope_s(red, scopes: Iterable[str]) -> float:
    """Self seconds inside the window of the ops under any of `scopes`
    (each op once), averaged over the devices."""
    want = frozenset(scopes)
    lo, hi = red.window
    tot = 0.0
    for evs in red.devices.values():
        for e in evs:
            if e.end > lo and e.start < hi and in_scope(e.op_name, want):
                frac = (min(e.end, hi) - max(e.start, lo)) / max(
                    e.end - e.start, 1)
                tot += e.self_ns * frac
    return tot / max(len(red.devices), 1) / 1e9


def scope_ms(ctx, *scopes: str) -> Optional[float]:
    """Device ms per step under `scopes`, or None where the trace holds
    none of them (a program without the scopes, a cell without the
    layer)."""
    if ctx.trace is None:
        return None
    v = scope_s(ctx.trace, scopes)
    return v / ctx.window_steps * 1e3 if v > 0 else None


def setup_counters(ctx) -> Optional[Dict[str, int]]:
    """The program's ``jit.*`` counters over set-up: their increments
    logged from the run's start (``chipbench/run.py``'s ``T_START``) to
    the first timed step, so the traced run's compile for its HLO text,
    after the window, is left out."""
    t_start = getattr(sys.modules.get("__main__"), "T_START", None)
    try:
        from repro.obs import recorder
    except ImportError:
        return None
    at = getattr(recorder, "jit_counters_at", None)
    if t_start is None or at is None:
        return None
    before, upto = at(t_start), at(t_start + ctx.setup_s)
    return {k: v - before.get(k, 0) for k, v in upto.items()}


def setup_phase_s(ctx, *counters: str) -> Optional[float]:
    """Seconds of set-up in the named ``jit.*_ns`` counters, or None."""
    c = setup_counters(ctx)
    if not c:
        return None
    v = sum(c.get(k, 0) for k in counters)
    return v / 1e9 if v > 0 else None
