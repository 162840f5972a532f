"""Reduce a profiler trace of a training window to per-layer numbers.

Input: the ``.xplane.pb`` that ``jax.profiler`` wrote, and the optimized
HLO text of the step (``compiled.as_text()``). On a TPU the op events are
on the ``XLA Ops`` and ``Async XLA Ops`` lines of each ``/device:TPU:<n>``
plane, and each is named by its HLO instruction (``%fusion.15 = bf16[..]
fusion(..)``, or an ``hlo_op`` stat where the backend gives one). That
name is matched to an instruction of the step's HLO, whose opcode and
``op_name`` metadata place the event in one class. Of the async line only
collectives are kept: its other spans are copies in flight (prefetch,
rematerialisation offload) that overlap the ops that do the work.

* ``collective``: all-gather, all-reduce, reduce-scatter,
  collective-permute, all-to-all (and their -start/-done halves);
* ``model``: JAX's transform names mark the forward (``jvp(``) and the
  backward (``transpose(jvp(``); the gradient accumulation in the
  microbatch loop (an op of a ``while`` body outside those) counts here;
* ``optimizer``: every other op with an ``op_name`` (momentum, sign,
  vote encode/decode, update);
* ``other``: ops with no ``op_name`` (copies the compiler added) and ops
  of other programs.

A fusion often carries no ``op_name`` of its own (the update of a large
leaf, which reads its momentum and writes the parameter, is one). It
takes the class that most of the named instructions of the computation
it calls (``calls=%...``) have, and the first of their op names.

Times are self times: an event that encloses others on its line is
charged only the part they do not cover. Busy time is the union of all
op intervals; idle gaps are the rest of the window, each named after the
host span of the benchmark (``batch``, ``dispatch``, ``sync``) that
overlaps it most.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter",
               "collective-permute", "all-to-all")
SPAN_PREFIX = "chipbench."
ASYNC_LINE = "Async XLA Ops"
OP_LINES = ("XLA Ops", ASYNC_LINE)
HOST_SPANS = ("batch", "dispatch", "sync")

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+)\s*=\s*.*?\s([a-z][\w\-]*)\(")
_OPNAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_COMPUTATION = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")


def parse_hlo(text: str) -> Dict[str, Tuple[str, str]]:
    """HLO text -> {instruction name: (opcode, op_name)}; an instruction
    with no ``op_name`` that calls a computation takes one from it."""
    out: Dict[str, Tuple[str, str]] = {}
    calls: Dict[str, str] = {}
    inside: Dict[str, List[Tuple[str, str]]] = collections.defaultdict(list)
    comp = ""
    for line in text.splitlines():
        m = _INSTR.match(line)
        if not m:
            c = _COMPUTATION.match(line)
            if c:
                comp = c.group(1)
            continue
        op = _OPNAME.search(line)
        name, opcode = m.group(1), m.group(2)
        out[name] = (opcode, op.group(1) if op else "")
        inside[comp].append(out[name])
        called = _CALLS.search(line)
        if called and not op:
            calls[name] = called.group(1)
    for name, comp in calls.items():
        named = [(classify(o, n), n) for o, n in inside.get(comp, []) if n]
        if named:
            votes = collections.Counter(c for c, _ in named)
            top = max(votes, key=lambda c: (votes[c], c))
            out[name] = (out[name][0], next(n for c, n in named if c == top))
    return out


def op_of(event_name: str) -> str:
    """The instruction name of an op event named by its HLO text."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def classify(opcode: str, op_name: str) -> str:
    base = re.sub(r"-(start|done|update)$", "", opcode)
    if base in COLLECTIVES:
        return "collective"
    if not op_name:
        return "other"
    if "jvp(" in op_name or "transpose(" in op_name or "/while/body/" in op_name:
        return "model"
    return "optimizer"


@dataclasses.dataclass
class OpEvent:
    name: str
    start: int        # ns
    end: int          # ns
    self_ns: int
    cls: str
    op_name: str


def _union(iv: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def _length(iv) -> int:
    return sum(e - s for s, e in iv)


def _subtract(a, b) -> List[Tuple[int, int]]:
    """Intervals of union `a` not covered by union `b` (both sorted)."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def _stats(ev) -> Dict:
    return {k: v for k, v in ev.stats}


def _self_times(events: List[Tuple[int, int, object]]) -> List[int]:
    """Self time of each (start, end, payload) event, nested by interval
    on one line."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -events[i][1]))
    self_ns = [e - s for s, e, _ in events]
    stack: List[int] = []
    for i in order:
        s, e, _ = events[i]
        while stack and events[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            self_ns[stack[-1]] -= min(e, events[stack[-1]][1]) - s
        stack.append(i)
    return [max(x, 0) for x in self_ns]


@dataclasses.dataclass
class Reduction:
    window: Tuple[int, int]
    devices: Dict[str, List[OpEvent]]
    host_spans: List[Tuple[int, int, str]]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def _busy(self, evs) -> List[Tuple[int, int]]:
        lo, hi = self.window
        return _clip(_union([(e.start, e.end) for e in evs]), lo, hi)

    def busy_s(self) -> float:
        """Busy seconds, averaged over the devices."""
        if not self.devices:
            return 0.0
        return sum(_length(self._busy(v)) for v in self.devices.values()) \
            / len(self.devices) / 1e9

    def class_s(self, cls: str) -> float:
        """Self seconds of one class inside the window, averaged over the
        devices."""
        lo, hi = self.window
        tot = 0
        for evs in self.devices.values():
            for e in evs:
                if e.cls == cls and e.end > lo and e.start < hi:
                    frac = (min(e.end, hi) - max(e.start, lo)) / max(
                        e.end - e.start, 1)
                    tot += e.self_ns * frac
        return tot / max(len(self.devices), 1) / 1e9

    def exposed_s(self, cls: str = "collective") -> float:
        """Seconds of `cls` ops during which no op of another class runs
        on that device, averaged over the devices."""
        lo, hi = self.window
        tot = 0
        for evs in self.devices.values():
            mine = _clip(_union([(e.start, e.end) for e in evs
                                 if e.cls == cls]), lo, hi)
            rest = _union([(e.start, e.end) for e in evs if e.cls != cls])
            tot += _length(_subtract(mine, rest))
        return tot / max(len(self.devices), 1) / 1e9

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        acc: Dict[str, float] = collections.defaultdict(float)
        lo, hi = self.window
        for evs in self.devices.values():
            for e in evs:
                if e.end > lo and e.start < hi:
                    tail = e.op_name.rsplit("/", 2)[-2:] if e.op_name else []
                    key = f"{e.cls}:{e.name}:{'/'.join(tail)}"[:160]
                    acc[key] += e.self_ns / 1e9 / len(self.devices)
        return sorted(acc.items(), key=lambda kv: -kv[1])[:n]

    def idle_gaps(self, n: int = 10) -> List[Tuple[str, float]]:
        lo, hi = self.window
        gaps = []
        for evs in self.devices.values():
            busy = self._busy(evs)
            gaps += _subtract([(lo, hi)], busy)
        named = []
        for s, e in gaps:
            best, over = "none", 0
            for hs, he, name in self.host_spans:
                o = min(e, he) - max(s, hs)
                if o > over:
                    best, over = name, o
            named.append((best, (e - s) / 1e9))
        return sorted(named, key=lambda kv: -kv[1])[:n]


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def reduce_trace(xplane_path: str, hlo_text: str,
                 device_prefix: str = "/device:TPU:",
                 n_devices: Optional[int] = None) -> Reduction:
    """Read one trace. Device planes are those whose name starts with
    `device_prefix` followed by a number (the first `n_devices` of them)."""
    from jax.profiler import ProfileData
    hlo = parse_hlo(hlo_text)
    data = ProfileData.from_file(xplane_path)
    devices: Dict[str, List[OpEvent]] = {}
    spans: List[Tuple[int, int, str]] = []
    window = None
    for plane in data.planes:
        name = plane.name
        if name.startswith(device_prefix) and \
                name[len(device_prefix):].isdigit():
            for line in plane.lines:
                if line.name not in OP_LINES:
                    continue
                raw = []
                for ev in line.events:
                    st = _stats(ev)
                    op = str(st.get("hlo_op") or op_of(ev.name))
                    opcode, op_name = hlo.get(op, ("", ""))
                    cls = classify(opcode, op_name) if opcode else "other"
                    if line.name == ASYNC_LINE and cls != "collective":
                        continue
                    s = int(ev.start_ns)
                    raw.append((s, s + int(ev.duration_ns),
                                (op, cls, op_name)))
                selfs = _self_times(raw)
                devices.setdefault(name, []).extend(
                    OpEvent(p[0], s, e, sn, p[1], p[2])
                    for (s, e, p), sn in zip(raw, selfs))
        elif name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if not ev.name.startswith(SPAN_PREFIX):
                        continue
                    s = int(ev.start_ns)
                    iv = (s, s + int(ev.duration_ns))
                    short = ev.name[len(SPAN_PREFIX):]
                    if short == "window":
                        window = iv
                    else:
                        spans.append((iv[0], iv[1], short))
    if window is None:
        raise ValueError("the trace holds no chipbench.window span")
    if n_devices is not None:
        keep = sorted(devices, key=lambda k: int(k[len(device_prefix):]))
        devices = {k: devices[k] for k in keep[:n_devices]}
    return Reduction(window, devices, spans)
