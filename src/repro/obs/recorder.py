"""The telemetry layer's three primitives (DESIGN.md §13).

* **Counters** — one process-global :class:`CounterRegistry` of exact
  integers (wire bytes, messages, kernel launches, voter chunks,
  recompiles). Always on: incrementing an int in a dict is cheaper than
  any gate, and the launch/chunk accounting that `bench_vote_plan` and
  `bench_federated` assert against must exist with telemetry off.
  `kernels.ops.LAUNCHES` and `population.LAST_STATS` are deprecation
  shims reading this registry.
* **Spans** — host-side ``perf_counter`` timing with nesting, emitted by
  a :class:`TraceRecorder`. The default recorder is a :class:`Recorder`
  no-op whose ``span()`` returns one module-level singleton (no
  allocation, no branches in the traced program). Spans NEVER insert
  ops into a jitted graph; a span around code under ``jax.jit``
  measures *trace/dispatch* time, which is exactly the host-side cost
  the schedule walk pays per bucket — the rows say so via the
  ``host_side`` meta field. A live span also enters a
  ``jax.profiler.TraceAnnotation`` of its name, so it lands in any
  profiler trace beside the device ops. Device time is named by device
  scopes instead (``obs/scopes.py``).
* **Step records** — one structured row per training/scenario step
  unifying the ``WireReport`` and ``StepTrace`` fields (resolved
  strategy, payload bytes, compression vs f32, margin, flip-vs-oracle,
  per-phase seconds), written to the same JSONL sink.

Every JSONL row carries ``{"v": SCHEMA_VERSION, "kind": ...}``;
:func:`read_trace` validates the version so downstream tooling
(`scripts/trace_report.py`) fails loudly on schema drift instead of
misreading rows.

Counter semantics inside ``jit`` mirror the long-standing
``kernels.ops.LAUNCHES`` contract: an increment that runs at trace time
fires once per compilation, so the count taken at trace time equals
launches per execution. Call sites that need per-step increments (the
ScenarioRunner loop, `VoteBackend.execute` outside jit) run eagerly.
"""
from __future__ import annotations

import collections
import contextlib
import json
import threading
import time
import warnings
from typing import Any, Deque, Dict, IO, Iterator, List, Optional, Tuple

#: bump on any breaking change to the JSONL row shapes below
SCHEMA_VERSION = 1

#: the row kinds a schema-1 trace may contain
ROW_KINDS = ("meta", "span", "event", "step", "counters")


# ---------------------------------------------------------------------------
# counters
# ---------------------------------------------------------------------------


class CounterRegistry:
    """Exact-integer counters under dotted names (``vote.wire.bytes``,
    ``kernel.launches.fused_majority``, ...). Three write verbs:
    monotonic :meth:`inc`, last-value :meth:`set` (gauges like the
    streamed engine's most-recent-run accounting), and high-water
    :meth:`record_max`. All values are plain Python ints — arbitrary
    precision, no float drift, cheap enough to leave always-on."""

    __slots__ = ("_c",)

    def __init__(self) -> None:
        self._c: Dict[str, int] = {}

    def inc(self, name: str, delta: int = 1) -> None:
        self._c[name] = self._c.get(name, 0) + int(delta)

    def set(self, name: str, value: int) -> None:
        self._c[name] = int(value)

    def record_max(self, name: str, value: int) -> None:
        v = int(value)
        if v > self._c.get(name, 0):
            self._c[name] = v

    def get(self, name: str, default: int = 0) -> int:
        return self._c.get(name, default)

    def snapshot(self, prefix: str = "") -> Dict[str, int]:
        """A detached copy (optionally of one dotted namespace)."""
        if not prefix:
            return dict(self._c)
        return {k: v for k, v in self._c.items() if k.startswith(prefix)}

    def delta_since(self, before: Dict[str, int],
                    prefix: str = "") -> Dict[str, int]:
        """Nonzero changes vs an earlier :meth:`snapshot`."""
        out = {}
        for k, v in self.snapshot(prefix).items():
            d = v - before.get(k, 0)
            if d:
                out[k] = d
        return out

    def reset(self, prefix: str = "") -> None:
        if not prefix:
            self._c.clear()
            return
        for k in [k for k in self._c if k.startswith(prefix)]:
            del self._c[k]


#: THE process-global registry (always on; see module docstring)
COUNTERS = CounterRegistry()


# ---------------------------------------------------------------------------
# spans / recorders
# ---------------------------------------------------------------------------


class _NoopSpan:
    """The disabled span: one module-level singleton, allocation-free on
    the hot path (``rec.span("name")`` with no attrs allocates nothing —
    asserted by tests/test_obs.py)."""

    __slots__ = ()
    dur_s = 0.0

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **attrs) -> None:
        pass


_NOOP_SPAN = _NoopSpan()


class Recorder:
    """The default no-op recorder. ``enabled`` is False, ``span()``
    returns the singleton no-op context manager, ``step``/``event`` do
    nothing. Hot paths gate attr computation on ``rec.enabled`` so the
    disabled cost is one attribute read."""

    enabled: bool = False

    def span(self, name: str, **attrs) -> Any:
        return _NOOP_SPAN

    def event(self, name: str, **attrs) -> None:
        pass

    def step(self, **fields) -> None:
        pass

    def close(self) -> None:
        pass


class _Span:
    """A live span: ``perf_counter`` on enter/exit, row written on exit
    with nesting depth + parent seq from the recorder's span stack; the
    same interval is a ``jax.profiler.TraceAnnotation`` of its name."""

    __slots__ = ("_rec", "name", "attrs", "seq", "depth", "parent",
                 "_t0", "dur_s", "_ann")

    def __init__(self, rec: "TraceRecorder", name: str,
                 attrs: Dict[str, Any]):
        self._rec = rec
        self.name = name
        self.attrs = attrs
        self.seq = -1
        self.depth = 0
        self.parent = -1
        self._t0 = 0.0
        self.dur_s = 0.0
        self._ann = None

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)

    def __enter__(self) -> "_Span":
        from jax.profiler import TraceAnnotation
        rec = self._rec
        self.seq = rec._next_seq()
        self.depth = len(rec._stack)
        self.parent = rec._stack[-1].seq if rec._stack else -1
        rec._stack.append(self)
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        self.dur_s = t1 - self._t0
        rec = self._rec
        if rec._stack and rec._stack[-1] is self:
            rec._stack.pop()
        else:                       # mis-nested exit: recover, don't lie
            rec._stack = [s for s in rec._stack if s is not self]
        row = {"v": SCHEMA_VERSION, "kind": "span", "seq": self.seq,
               "parent": self.parent, "depth": self.depth,
               "name": self.name, "t0_s": self._t0 - rec._origin,
               "dur_s": self.dur_s}
        if self.attrs:
            row["attrs"] = self.attrs
        rec._write(row)
        return False


class TraceRecorder(Recorder):
    """JSONL sink: a ``meta`` header row, then ``span``/``event``/
    ``step`` rows as they happen, then a final ``counters`` snapshot on
    :meth:`close`. All timing is host-side ``perf_counter`` relative to
    the recorder's origin; nothing here touches a traced value, so the
    golden digest is bit-identical with tracing on (regression-tested).
    """

    enabled = True

    def __init__(self, path_or_file, meta: Optional[Dict[str, Any]] = None):
        if hasattr(path_or_file, "write"):
            self._f: IO[str] = path_or_file
            self._own = False
            self.path = getattr(path_or_file, "name", "<stream>")
        else:
            self._f = open(path_or_file, "w")
            self._own = True
            self.path = str(path_or_file)
        self._stack: List[_Span] = []
        self._seq = 0
        self._closed = False
        self._origin = time.perf_counter()
        head = {"v": SCHEMA_VERSION, "kind": "meta",
                "schema": SCHEMA_VERSION, "unix_time": time.time(),
                "host_side": True}
        if meta:
            head.update(meta)
        self._write(head)

    # -- plumbing --

    def _next_seq(self) -> int:
        s = self._seq
        self._seq += 1
        return s

    def _write(self, row: Dict[str, Any]) -> None:
        if self._closed:
            return
        self._f.write(json.dumps(row, default=_jsonable) + "\n")

    # -- the three primitives --

    def span(self, name: str, **attrs) -> _Span:
        return _Span(self, name, attrs)

    def event(self, name: str, **attrs) -> None:
        row = {"v": SCHEMA_VERSION, "kind": "event", "seq": self._next_seq(),
               "name": name,
               "t0_s": time.perf_counter() - self._origin}
        if attrs:
            row["attrs"] = attrs
        self._write(row)

    def step(self, **fields) -> None:
        self._write({"v": SCHEMA_VERSION, "kind": "step",
                     "seq": self._next_seq(), **fields})

    def counters(self, registry: CounterRegistry = None) -> None:
        reg = registry if registry is not None else COUNTERS
        self._write({"v": SCHEMA_VERSION, "kind": "counters",
                     "values": reg.snapshot()})

    def close(self) -> None:
        if self._closed:
            return
        self.counters()
        self._closed = True
        if self._own:
            self._f.close()
        else:
            self._f.flush()


def _jsonable(x):
    """Last-resort JSON coercion for attr values (enums, 0-d arrays)."""
    for attr in ("value", "item"):
        v = getattr(x, attr, None)
        if v is not None:
            try:
                return v() if callable(v) else v
            except Exception:
                pass
    return str(x)


def read_trace(path: str) -> List[Dict[str, Any]]:
    """Parse a JSONL trace, validating the schema version of every row
    (fails loudly on drift — the versioned-schema contract)."""
    rows = []
    with open(path) as f:
        for lineno, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            row = json.loads(line)
            if row.get("v") != SCHEMA_VERSION:
                raise ValueError(
                    f"{path}:{lineno}: trace row schema v={row.get('v')!r}"
                    f", this reader understands v={SCHEMA_VERSION}")
            if row.get("kind") not in ROW_KINDS:
                raise ValueError(
                    f"{path}:{lineno}: unknown row kind "
                    f"{row.get('kind')!r}; have {ROW_KINDS}")
            rows.append(row)
    return rows


# ---------------------------------------------------------------------------
# the active recorder (module global + context-manager scoping)
# ---------------------------------------------------------------------------

_NOOP = Recorder()
_ACTIVE: Recorder = _NOOP


def get_recorder() -> Recorder:
    """The active recorder (the no-op singleton unless one was set)."""
    return _ACTIVE


def set_recorder(rec: Optional[Recorder]) -> Recorder:
    """Install `rec` as the active recorder (None -> the no-op);
    returns the previous one so callers can restore it."""
    global _ACTIVE
    prev = _ACTIVE
    _ACTIVE = rec if rec is not None else _NOOP
    return prev


@contextlib.contextmanager
def recording(rec: Recorder) -> Iterator[Recorder]:
    """Scope `rec` as the active recorder; restores the previous one on
    exit (the recorder is NOT closed — callers own its lifetime)."""
    prev = set_recorder(rec)
    try:
        yield rec
    finally:
        set_recorder(prev)


# ---------------------------------------------------------------------------
# compile watch (set-up phase accounting)
# ---------------------------------------------------------------------------

#: JAX's phase events -> the counter of nanoseconds each adds to
JIT_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "jit.trace_ns",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jit.lower_ns",
    # a persistent-cache load runs inside this event too
    "/jax/core/compile/backend_compile_duration": "jit.compile_ns",
}
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

#: (perf_counter at the event's end, counter, amount) for each increment
#: of a ``jit.*`` counter, oldest first, so a caller can split them by
#: its own phases after the fact (:func:`jit_counters_at`)
JIT_LOG: Deque[Tuple[float, str, int]] = collections.deque(maxlen=1 << 16)

_COMPILE_WATCH_ON = False
_OPEN = threading.local()      # phase events open on this thread


def _jit_inc(name: str, amount: int) -> None:
    COUNTERS.inc(name, amount)
    JIT_LOG.append((time.perf_counter(), name, amount))


def _on_phase_start(event: str, value, **kw) -> None:
    if event in JIT_PHASES:
        _OPEN.n = getattr(_OPEN, "n", 0) + 1


def _on_phase_end(event: str, duration: float, **kw) -> None:
    name = JIT_PHASES.get(event)
    if name is None:
        return
    _OPEN.n = max(getattr(_OPEN, "n", 0) - 1, 0)
    if name == "jit.compile_ns":
        _jit_inc("jit.compiles", 1)
    # a phase nested in another (a jitted helper traced inside the step,
    # an eager op compiled while tracing) is already inside the outer
    # one's time: only outermost phases add nanoseconds
    if _OPEN.n:
        return
    _jit_inc(name, int(duration * 1e9))
    rec = get_recorder()
    if rec.enabled:
        rec.event(name[:-3], event=event, dur_s=duration)


def _on_event(event: str, **kw) -> None:
    if event == CACHE_HIT_EVENT:
        _jit_inc("jit.cache_hits", 1)


def install_compile_watch() -> bool:
    """Count JAX's set-up phases by their exact event names into the
    always-on counters: ``jit.trace_ns`` (tracing to a jaxpr),
    ``jit.lower_ns`` (jaxpr to MLIR), ``jit.compile_ns`` and
    ``jit.compiles`` (the backend compile, or its load from the
    persistent cache), ``jit.cache_hits`` (persistent-cache hits). Each
    increment is also logged in :data:`JIT_LOG`, and an active recorder
    gets a ``jit.trace`` / ``jit.lower`` / ``jit.compile`` event. Costs
    one Python call per phase event, none per step. Idempotent; returns
    False (and stays inert) if the installed jax has no monitoring hooks
    — telemetry must degrade, not crash."""
    global _COMPILE_WATCH_ON
    if _COMPILE_WATCH_ON:
        return True
    try:
        from jax import monitoring
        monitoring.register_scalar_listener(_on_phase_start)
        monitoring.register_event_duration_secs_listener(_on_phase_end)
        monitoring.register_event_listener(_on_event)
    except Exception:
        return False
    _COMPILE_WATCH_ON = True
    return True


def jit_counters_at(t: float) -> Dict[str, int]:
    """The ``jit.*`` counters' increments logged up to ``perf_counter``
    time `t` (e.g. the end of a program's set-up)."""
    out: Dict[str, int] = {}
    for when, name, amount in JIT_LOG:
        if when > t:
            break
        out[name] = out.get(name, 0) + amount
    return out


# ---------------------------------------------------------------------------
# shared helpers for the bench scripts
# ---------------------------------------------------------------------------


def emit_bench_json(rows, path: str) -> None:
    """THE bench JSON writer: ``{"rows": [{"name", "value", "derived"}]}``
    — the schema ``scripts/perf_gate.py`` gates. Accepts the benches'
    ``(name, value, derived)`` tuples or already-shaped dicts; every
    bench and the ``benchmarks.run`` driver route here (one writer, one
    schema, no copy-paste drift)."""
    out = []
    for r in rows:
        if isinstance(r, dict):
            out.append({"name": r["name"], "value": r["value"],
                        "derived": r.get("derived", "")})
        else:
            name, value, derived = r
            out.append({"name": name, "value": value, "derived": derived})
    with open(path, "w") as f:
        json.dump({"rows": out}, f, indent=1)


def add_trace_arg(ap) -> None:
    """Attach the shared ``--trace FILE`` option to a bench argparser."""
    ap.add_argument("--trace", default=None, metavar="FILE",
                    help="write an obs JSONL trace of this run "
                         "(render with scripts/trace_report.py)")


def activate_trace(args) -> Optional[TraceRecorder]:
    """Honour a parsed ``--trace`` flag: install a TraceRecorder as the
    active recorder (+ the compile watch) and return it, or None. The
    caller owns closing it (``finish_trace``)."""
    path = getattr(args, "trace", None)
    if not path:
        return None
    rec = TraceRecorder(path)
    set_recorder(rec)
    install_compile_watch()
    return rec


def finish_trace(rec: Optional[TraceRecorder]) -> None:
    """Close an ``activate_trace`` recorder (writes the final counters
    snapshot) and restore the no-op."""
    if rec is None:
        return
    set_recorder(None)
    rec.close()
    print(f"# wrote trace {rec.path}", flush=True)


# ---------------------------------------------------------------------------
# deprecation plumbing for the absorbed accounting surfaces
# ---------------------------------------------------------------------------

_WARNED: set = set()


def warn_deprecated(name: str, hint: str) -> None:
    """One DeprecationWarning per absorbed surface per process (the
    `vote_api.warn_legacy` pattern; obs cannot import vote_api)."""
    if name in _WARNED:
        return
    _WARNED.add(name)
    warnings.warn(f"{name} is deprecated: {hint} (DESIGN.md §13)",
                  DeprecationWarning, stacklevel=3)
