"""One run of one benchmark cell: set-up, a timed or traced window, and
the check of the first steps against the reference.

Everything that belongs to one cell is data, found by name:

* ``BENCHMARK.json`` (the checkout's root): the cell's configuration,
  traffic and chips, and the metrics it reports;
* ``chipbench/configs/<config>.json``: the model's published sizes (read
  by the reference), and under ``program`` how the trainer is built;
* ``chipbench/traffic/<traffic>.json``: rows per replica, sequence
  length, the vote's wire, and the token generator's parameters;
* ``chipbench/limits/<cell>.json``: the limit of each compared number;
* ``chipbench/metrics/<metric>.py``: one reader per metric;
* ``chipbench/reference/<family>.py``: the plain reference of a family.

The run: build the trainer's step (``repro.launch.train.build``,
``repro.train.train_step.make_train_step``), make its state on the
device from the seed in one jitted call (the weights by the source's
initialisation rule, ``yardstick/init.py``, placed as the trainer's
``abstract_state`` says; the optimizer's state zero), and drive
``art.step_fn`` through three check steps, which compile and warm every
shape. Then the
window: batches are drawn and placed in the loop, steps are dispatched
with at most two in flight, and the window ends when the last one is
ready. Afterwards the state is freed and the reference follows the same
three steps from the same seed.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import importlib.util
import json
import math
import sys
import tempfile
import time
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
CHECK_STEPS = 3
TRACE_STEPS = 3
IN_FLIGHT = 2


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict          # the configuration file
    traffic: Dict         # the traffic file
    limits: Dict[str, float]
    end_to_end: List[Dict]
    per_layer: List[Dict]

    @property
    def rows(self) -> int:
        return self.traffic["rows_per_replica"] * self.chips

    @property
    def seq(self) -> int:
        return self.traffic["seq_len"]


def load_cell(root: Path, workload: str) -> Cell:
    bench = _json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _json(root / configs[w["config"]]["file"])
    traffic = _json(root / "chipbench" / "traffic" / f"{w['traffic']}.json")
    limits = _json(root / "chipbench" / "limits" / f"{workload}.json")

    def mine(ms):
        return [m for m in ms if workload in m.get("workloads", [workload])]
    return Cell(workload, int(w["chips"]), config, traffic, limits,
                mine(bench["end_to_end"]), mine(bench["per_layer"]))


def load_reader(root: Path, name: str):
    path = root / "chipbench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def reference_config(cell: Cell) -> Dict:
    """The configuration's published keys with the sizes it assumes and
    its training settings."""
    c = {k: v for k, v in cell.config.items() if k != "program"}
    c.update(cell.config.get("assumed_sizes", {}))
    c.update(cell.config["train"])
    return c


def tokens(cell: Cell, c: Dict, seed: int):
    """The cell's token generator for `seed`."""
    from chipbench.yardstick.tokens import MarkovTokens
    return MarkovTokens(c["vocab_size"], cell.rows, cell.seq, seed,
                        **cell.traffic.get("generator", {}))


# ---------------------------------------------------------------------------
# the program under test
# ---------------------------------------------------------------------------


def build_program(cell: Cell, devices):
    """(cfg, tcfg, art, mesh): the trainer as the configuration states."""
    import dataclasses as dc

    from repro.configs.base import VoteStrategy, get_config
    from repro.launch import train as LT
    from repro.launch.mesh import make_replica_mesh
    from repro.train import train_step as TS

    prog, train = cell.config["program"], cell.config["train"]
    base = get_config(prog["arch"])
    fields = dict(prog["fields"])
    if "ssm" in fields:
        fields["ssm"] = dc.replace(base.ssm, **fields["ssm"])
    cfg = dc.replace(base, **fields)
    _, tcfg = LT.build(prog["arch"], reduced=False, batch=cell.rows,
                       seq=cell.seq, microbatches=train["microbatches"])
    opt = tcfg.optimizer
    if opt.kind != train["optimizer"]:
        raise ValueError(f"{prog['arch']} trains {opt.kind}, the "
                         f"configuration states {train['optimizer']}")
    wire = cell.traffic.get("vote_strategy")
    opt = dc.replace(
        opt, learning_rate=train["learning_rate"],
        momentum=train["momentum"], momentum_dtype=train["momentum_dtype"],
        vote_strategy=VoteStrategy(wire) if wire else opt.vote_strategy)
    tcfg = dc.replace(tcfg, remat=train["remat"], optimizer=opt)
    if cfg.dtype != train["dtype"]:
        raise ValueError(f"the trainer holds {cfg.dtype}, the configuration "
                         f"states {train['dtype']}")
    mesh = make_replica_mesh(devices) if cell.chips > 1 else None
    art = TS.make_train_step(cfg, tcfg, mesh=mesh)
    if mesh is not None and wire and art.vote_strategy.value != wire:
        raise ValueError(f"the step votes {art.vote_strategy}, the traffic "
                         f"states {wire}")
    return cfg, tcfg, art, mesh


class _Spans:
    """Host spans written into the profiler's trace (no-ops untraced)."""

    def __init__(self, on: bool):
        self.on = on

    def __call__(self, name: str):
        if not self.on:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(f"chipbench.{name}")


def _compile_counter():
    import jax
    count = collections.Counter()

    def listen(event, duration, **_):
        if "backend_compile" in event:
            count["n"] += 1
    jax.monitoring.register_event_duration_secs_listener(listen)
    return count


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


class Program:
    """The trainer built once for a cell: state from a seed, and the check
    steps that compile, warm up, and read what the check compares."""

    def __init__(self, cell: Cell, devices):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from chipbench.yardstick import probes
        self.cell, self.devices = cell, list(devices)[:cell.chips]
        self.c = reference_config(cell)
        self.family = importlib.import_module(
            f"chipbench.reference.{self.c['reference']}")
        self.shapes = self.family.param_shapes(self.c)
        self.cfg, self.tcfg, self.art, self.mesh = build_program(
            cell, self.devices)
        prog_shapes = {k: tuple(v) for k, v in self.cfg.param_shapes().items()}
        want = {k: tuple(v) for k, v in self.shapes.items()}
        if prog_shapes != want:
            raise ValueError("the trainer's parameters differ from the "
                             "configuration's: " + str(sorted(
                                 set(prog_shapes.items())
                                 ^ set(want.items()))[:6]))
        if self.mesh is None:
            self.place_b = self.place_s = self.devices[0]
        else:
            self.place_b = NamedSharding(self.mesh, P("data"))
            self.place_s = NamedSharding(self.mesh, P())
        self.mom_norms = probes.replica_norms()
        self.delta = probes.delta_norms(self.shapes, self.c)
        self.checksum = probes.checksum()

    def tokens(self, seed: int):
        return tokens(self.cell, self.c, seed)

    def start(self, seed: int):
        """(params, opt_state) on the device in one jitted call: the
        weights by ``yardstick/init.py``'s rule (the reference makes the
        same ones itself), the optimizer's state zero."""
        import jax
        import jax.numpy as jnp

        from chipbench.yardstick import init as yinit
        from repro.train import train_step as TS
        p_abs, o_abs = TS.abstract_state(self.cfg, self.tcfg, self.art,
                                         self.mesh)

        def fn(key):
            return (yinit.init_leaves(self.shapes, key, self.c),
                    jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                                 o_abs))
        if self.mesh is None:
            return jax.jit(fn)(yinit.seed_key(seed))
        shardings = jax.tree.map(lambda s: s.sharding, (p_abs, o_abs))
        return jax.jit(fn, out_shardings=shardings)(yinit.seed_key(seed))

    def step(self, params, opt_state, gen, step: int):
        import jax
        b = jax.device_put(gen.batch(step), self.place_b)
        return self.art.step_fn(params, opt_state, {"tokens": b},
                                np.int32(step))

    def check_steps(self, params, opt_state, gen, seed: int):
        """Steps 0..CHECK_STEPS-1 through the window's own call; returns
        (params, opt_state, readings)."""
        import jax

        from chipbench.yardstick import init as yinit
        losses = []
        for step in range(CHECK_STEPS):
            params, opt_state, met = self.step(params, opt_state, gen, step)
            losses.append(met["loss"])
            if step == 0:
                g = self.mom_norms(opt_state["momentum"])
        d = self.delta(params, jax.device_put(yinit.seed_key(seed),
                                              self.place_s))
        jax.block_until_ready((params, opt_state, d, g))
        beta = float(self.c["momentum"])
        out = {"loss": [float(x) for x in losses],
               "grad_norm": {k: [float(x) / (1.0 - beta) for x in np.ravel(v)]
                             for k, v in jax.device_get(g).items()},
               "delta_norm": {k: float(v) for k, v in
                              jax.device_get(d).items()}}
        if self.mesh is not None:
            out["replicas_differ"] = self.replicas_differ(params)
        return params, opt_state, out

    def replicas_differ(self, params) -> int:
        """Leaves whose copies on the replicas' chips are not bit for bit
        the same (every replica applies the same vote)."""
        import jax
        n = 0
        for v in params.values():
            sums = {tuple(np.asarray(jax.device_get(self.checksum(s.data))))
                    for s in v.addressable_shards}
            n += len(sums) > 1
        return n


def run(root: Path, workload: str, seed: int, seconds: float, trace: bool,
        t_start: float, devices) -> Dict:
    import jax

    from chipbench.reference import common as refcommon
    from chipbench.yardstick import compare, opt_bytes
    from chipbench.yardstick import init as yinit

    cell = load_cell(root, workload)
    compiles = _compile_counter()
    span = _Spans(trace)
    prog_ = Program(cell, devices)
    devices, c, family = prog_.devices, prog_.c, prog_.family
    shapes, dev = prog_.shapes, prog_.devices[0]
    params, opt_state = prog_.start(seed)
    gen = prog_.tokens(seed)
    place_b = prog_.place_b

    # ---- check steps: compile, warm up, and read what the check takes ----
    params, opt_state, prog = prog_.check_steps(params, opt_state, gen, seed)

    # ---- the window ----
    n_steps = TRACE_STEPS if trace else None
    tmp = tempfile.TemporaryDirectory(prefix="chipbench-trace-") \
        if trace else None
    if trace:
        jax.profiler.start_trace(tmp.name)
    compiles_before = compiles["n"]
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    window_losses, inflight, step = [], collections.deque(), CHECK_STEPS
    with span("window"):
        while True:
            with span("batch"):
                b = jax.device_put(gen.batch(step), place_b)
            with span("dispatch"):
                params, opt_state, met = prog_.art.step_fn(
                    params, opt_state, {"tokens": b}, np.int32(step))
            window_losses.append(met["loss"])
            inflight.append(met["loss"])
            step += 1
            if len(inflight) > IN_FLIGHT:
                with span("sync"):
                    inflight.popleft().block_until_ready()
            done = (len(window_losses) >= n_steps if trace
                    else time.perf_counter() - t0 >= seconds)
            if done:
                break
        with span("sync"):
            jax.block_until_ready((params, opt_state, met))
    t1 = time.perf_counter()
    if trace:
        jax.profiler.stop_trace()
    log(f"setup_s={setup_s:.1f} window_s={t1 - t0:.1f} "
        f"steps={len(window_losses)}")
    in_window_compiles = compiles["n"] - compiles_before
    if in_window_compiles:
        log(f"warning: {in_window_compiles} compiles inside the window")
    peak = max((d_.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d_ in devices)
    window_losses = [float(x) for x in window_losses]
    failed = sum(not math.isfinite(x) for x in window_losses)

    hlo_text = None
    if trace:
        abstract = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=a.sharding),
            (params, opt_state, {"tokens": b}))
        hlo_text = prog_.art.step_fn.lower(*abstract, np.int32(0)) \
            .compile().as_text()
    leaves = [(int(np.prod(s)), yinit.leaf_dtype(k, c["dtype"]).itemsize)
              for k, s in shapes.items()]
    del params, opt_state, met, b, inflight

    # ---- metrics ----
    ctx = _Context(
        cell=cell, setup_s=setup_s, window_s=t1 - t0,
        window_steps=len(window_losses),
        tokens_per_step=cell.rows * cell.seq, chips=cell.chips,
        device_kind=dev.device_kind,
        flops_per_step=family.train_flops_per_step(c, cell.rows, cell.seq),
        optimizer_bytes=opt_bytes.optimizer_bytes(
            leaves, np.dtype(c["momentum_dtype"]).itemsize, cell.chips),
        trace=None)
    result_device = {"platform": dev.platform, "kind": dev.device_kind,
                     "count": len(devices), "memory_peak_bytes": int(peak)}
    breakdown = None
    if trace:
        from chipbench.yardstick import trace as ytrace
        red = ytrace.reduce_trace(ytrace.find_xplane(tmp.name), hlo_text,
                                  n_devices=len(devices))
        tmp.cleanup()
        ctx.trace = red
        result_device.update(busy_s=red.busy_s(), window_s=red.window_s)
        breakdown = {"device_ops": [[k, v] for k, v in red.top_ops(10)],
                     "idle_gaps": [[k, v] for k, v in red.idle_gaps(10)]}
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        v = load_reader(root, m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    # ---- the reference follows the check steps ----
    t_ref = time.perf_counter()
    ref = refcommon.run(family, c, "f32", devices, seed,
                        [gen.batch(s) for s in range(CHECK_STEPS)],
                        block_rows=1)
    log(f"reference_s={time.perf_counter() - t_ref:.1f}")
    nums = compare.numbers(prog, ref)
    correct = (compare.verdict(nums, cell.limits) and failed == 0
               and all(math.isfinite(x) for x in prog["loss"]))
    checks = {k: {"value": nums[k], "limit": cell.limits[k]}
              for k in cell.limits}
    log_readings(prog, ref, nums)
    out = {"correct": bool(correct), "attempted": len(window_losses),
           "failed": failed, "metrics": metrics, "device": result_device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


def log_readings(prog: Dict, ref: Dict, nums: Dict[str, float]) -> None:
    """The losses, every number computed, and the worst leaves, to stderr."""
    from chipbench.yardstick import compare
    log(f"losses program={prog['loss']} reference={ref['loss']}")
    log("numbers " + " ".join(f"{k}={v!r}" for k, v in nums.items()))
    rows = compare.per_leaf(prog, ref)
    for k in sorted(rows, key=lambda k: -rows[k]["gap"])[:6]:
        v = rows[k]
        log(f"leaf {k} grad_norm program={v['grad_prog']:.6g} reference="
            f"{v['grad_ref']:.6g} gap={v['gap']:.4f}")


@dataclasses.dataclass
class _Context:
    """What a metric reader sees (see ``chipbench/metrics``)."""
    cell: Cell
    setup_s: float
    window_s: float
    window_steps: int
    tokens_per_step: int
    chips: int
    device_kind: str
    flops_per_step: float
    optimizer_bytes: float
    trace: Optional[object]

    @property
    def peaks(self) -> Dict[str, float]:
        from chipbench.yardstick.peaks import peaks_for
        return peaks_for(self.device_kind)


def print_result(out: Dict) -> None:
    for k, v in out["checks"].items():
        log(f"check {k} value={v['value']!r} limit={v['limit']!r}")
    print(json.dumps(out), flush=True)
