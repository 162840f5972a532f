"""Fig. 4 analogs: fault-tolerance of the vote, two ways.

* ``rows()`` (the ``benchmarks.run`` driver path) — training a real
  (reduced) LM with majority vote while a fraction of the vote replicas
  inverts its signs. Runs the actual distributed train step on 8 fake
  devices in a subprocess (the bench process keeps 1 device).
* ``--scenario-grid`` — the Scenario Lab sweep (DESIGN.md §7): replays
  adversary fraction 0 -> 0.5 x {sign_flip, random, zero, colluding} x
  all three wire strategies through ``repro.sim.ScenarioRunner`` traces,
  from ONE config file (``benchmarks/configs/fig4_grid.json``), plus the
  boundary drills (blind >50%, stale adversaries, elastic shrink).
* ``--scenario-smoke`` — the CI lane: 3 scenarios x 2 strategies on the
  8-virtual-device host platform, each run on BOTH backends and asserted
  bit-identical (mesh collectives == virtual mesh), in well under 60 s.
* ``--breaking-point`` — the adaptive-attack lane (DESIGN.md §15):
  every attack class's measured breaking-point curve (adversary
  fraction -> loss drop) overlaid with the oblivious Theorem 2 failure
  bound, the defense-aware-vs-oblivious degradation gate, and the
  mesh==virtual / chunk-invariance identity asserts, written to
  ``BENCH_robustness.json`` (gated by scripts/perf_gate.py).

Usage:
    python -m benchmarks.bench_robustness                   # train sweep
    python -m benchmarks.bench_robustness --scenario-grid   # Fig. 4 grid
    python -m benchmarks.bench_robustness --scenario-smoke  # CI smoke
    python -m benchmarks.bench_robustness --breaking-point  # attack lane
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import textwrap

_CONFIG = os.path.join(os.path.dirname(__file__), "configs",
                       "fig4_grid.json")
_BP_JSON = os.path.join(os.path.dirname(__file__), "..",
                        "BENCH_robustness.json")

_WORKER = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, sys
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import compat
    from repro.configs.base import (ByzantineConfig, OptimizerConfig,
                                    TrainConfig, get_config, reduced_config)
    from repro.models import model as M
    from repro.train import train_step as TS

    mesh = compat.make_mesh((8, 1), ("data", "model"),
                            axis_types=(compat.AxisType.Auto,) * 2)
    out = {}
    for n_adv in [0, 1, 2, 3]:
        cfg = reduced_config(get_config("glm4-9b"), num_layers=2)
        tcfg = TrainConfig(
            global_batch=8, seq_len=32,
            optimizer=OptimizerConfig(kind="signum_vote", learning_rate=3e-3),
            byzantine=ByzantineConfig(mode="sign_flip",
                                      num_adversaries=n_adv))
        art = TS.make_train_step(cfg, tcfg, mesh=mesh)
        params, opt = TS.materialize_state(cfg, tcfg, art,
                                           jax.random.PRNGKey(0), mesh)
        batch = M.make_batch(cfg, 8, 32, jax.random.PRNGKey(1))
        batch = jax.tree.map(lambda a: jax.device_put(
            np.asarray(a), NamedSharding(mesh, P("data"))), batch)
        losses = []
        for i in range(40):
            params, opt, met = art.step_fn(params, opt, batch, jnp.int32(i))
            losses.append(float(met["loss"]))
        out[str(n_adv)] = [losses[0], losses[-1]]
    print("RESULT " + json.dumps(out))
""")


def rows():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    # a CPU semantics harness (8 forced host devices): it must not take
    # the accelerator this process may hold
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", _WORKER], env=env,
                          capture_output=True, text=True, timeout=1800)
    if proc.returncode != 0:
        raise RuntimeError(f"fig4 worker failed (rc {proc.returncode}):\n"
                           f"{proc.stderr[-2000:]}")
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")][0]
    res = json.loads(line[len("RESULT "):])
    out = []
    for n_adv, (first, last) in sorted(res.items()):
        pct = int(n_adv) / 8 * 100
        out.append((f"fig4/loss_drop_{pct:.0f}pct_adversarial",
                    first - last,
                    f"loss {first:.2f}->{last:.2f} (8 voters, "
                    f"{n_adv} sign-flippers)"))
    return out


# ---------------------------------------------------------------------------
# Scenario Lab sweeps
# ---------------------------------------------------------------------------


def scenario_traces(config_path: str = _CONFIG, backend: str = "virtual"):
    from repro.sim import ScenarioRunner, load_scenarios
    return [ScenarioRunner(spec, backend=backend).run()
            for spec in load_scenarios(config_path)]


def scenario_rows(config_path: str = _CONFIG, backend: str = "virtual",
                  traces=None):
    """One CSV row per scenario in the config: the Fig.-4 robustness
    surface from ScenarioRunner traces."""
    if traces is None:
        traces = scenario_traces(config_path, backend)
    out = []
    for trace in traces:
        spec, s = trace.spec, trace.summary()
        adv = spec.adversary
        out.append((
            f"fig4-grid/{spec.name}",
            s["loss_drop"],
            f"loss {s['first_loss']:.3f}->{s['final_loss']:.3f} "
            f"flip={s['mean_flip_fraction']:.3f} "
            f"margin={s['mean_margin']:.3f} "
            f"({spec.n_workers} voters, {adv.mode} f={adv.fraction}, "
            f"{spec.strategy.value}, ties->{s['tie_policy']})"))
    return out


def smoke_rows():
    """3 scenarios x 2 strategies, each replayed on BOTH backends on the
    8-virtual-device platform and asserted bit-identical."""
    from repro.configs.base import VoteStrategy
    from repro.sim import AdversarySpec, ElasticEvent, ScenarioRunner, \
        ScenarioSpec
    drills = [
        ("smoke/honest", dict()),
        ("smoke/flip_25_stale_25",
         dict(adversary=AdversarySpec("sign_flip", 0.25),
              straggler_fraction=0.25)),
        ("smoke/colluding_elastic",
         dict(adversary=AdversarySpec("colluding", 0.375),
              elastic=(ElasticEvent(4, 4),))),
    ]
    out = []
    for strategy in (VoteStrategy.PSUM_INT8, VoteStrategy.ALLGATHER_1BIT):
        for name, kw in drills:
            spec = ScenarioSpec(f"{name}/{strategy.value}", n_workers=8,
                                n_steps=8, dim=128, strategy=strategy, **kw)
            tv = ScenarioRunner(spec, backend="virtual").run()
            tm = ScenarioRunner(spec, backend="mesh").run()
            assert tv.digest == tm.digest, (
                f"{spec.name}: virtual and mesh wire paths diverged "
                f"({tv.digest[:12]} != {tm.digest[:12]})")
            s = tv.summary()
            out.append((f"fig4-smoke/{spec.name}", s["loss_drop"],
                        f"mesh==virtual digest {tv.digest[:12]} "
                        f"flip={s['mean_flip_fraction']:.3f}"))
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--scenario-grid", action="store_true",
                    help="Fig. 4 sweep from ScenarioRunner traces")
    ap.add_argument("--scenario-smoke", action="store_true",
                    help="CI smoke: 3 scenarios x 2 strategies, "
                         "mesh-vs-virtual bit-identity on 8 devices")
    ap.add_argument("--breaking-point", action="store_true",
                    help="adaptive-attack breaking-point curves vs the "
                         "Thm 2 bound; writes BENCH_robustness.json")
    ap.add_argument("--config", default=_CONFIG,
                    help="scenario config file (default: "
                         "benchmarks/configs/fig4_grid.json)")
    ap.add_argument("--backend", default="virtual",
                    choices=("virtual", "mesh"),
                    help="--scenario-grid backend")
    ap.add_argument("--json", dest="json_out", default=None,
                    help="also dump full per-step traces to this file")
    from repro.obs import recorder as obs
    obs.add_trace_arg(ap)
    args = ap.parse_args()

    if sum((args.scenario_smoke, args.scenario_grid,
            args.breaking_point)) > 1:
        ap.error("--scenario-smoke/--scenario-grid/--breaking-point are "
                 "exclusive")
    if not args.scenario_grid and (args.json_out or args.config != _CONFIG
                                   or args.backend != "virtual"):
        ap.error("--json/--config/--backend apply to --scenario-grid only")

    if args.breaking_point:
        # identity rows replay every adaptive mode on the mesh backend:
        # force the 8-virtual-device platform before jax initialises,
        # APPENDING so a caller's unrelated XLA_FLAGS survive
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
        rec = obs.activate_trace(args)
        from repro.core.attacks import breaking_point as bp
        rs = bp.breaking_point_rows()
        obs.emit_bench_json(rs, os.path.normpath(_BP_JSON))
    elif args.scenario_smoke:
        # the smoke lane *is* the 8-virtual-device platform; force the
        # device count before jax initialises, APPENDING so a caller's
        # unrelated XLA_FLAGS (dump dirs etc.) survive
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
        rec = obs.activate_trace(args)
        rs = smoke_rows()
    elif args.scenario_grid:
        rec = obs.activate_trace(args)
        traces = scenario_traces(args.config, args.backend)
        rs = scenario_rows(traces=traces)
        if args.json_out:
            with open(args.json_out, "w") as f:
                json.dump([t.to_dict() for t in traces], f, indent=1)
    else:
        rec = obs.activate_trace(args)
        rs = rows()
    print("name,value,derived")
    for name, value, derived in rs:
        print(f"{name},{value:.6g},{derived}", flush=True)
    obs.finish_trace(rec)


if __name__ == "__main__":
    main()
