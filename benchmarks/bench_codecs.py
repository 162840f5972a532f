"""Codec frontier: convergence vs bits/param across the gradient codecs.

The Gradient Codec subsystem (DESIGN.md §8) makes the paper's 1-bit wire
one point on a compression/robustness frontier; this benchmark sweeps
that frontier two ways:

* ``rows()`` (the ``benchmarks.run`` driver path) — trains the reduced
  quickstart model (glm4 family, the model every example uses) through
  the REAL distributed train step on 8 virtual devices in a subprocess,
  once per codec, and reports loss drop against the codec's wire width.
* ``--smoke`` — the CI lane (scripts/ci.sh codec-smoke stage, <10 s):
  a ScenarioRunner drill per codec x strategy on the 8-virtual-device
  platform, each *new* codec additionally replayed on the mesh backend
  and asserted bit-identical to the virtual wire path; writes the
  machine-readable baseline ``BENCH_codecs.json`` (also reachable via
  ``python -m benchmarks.run --only codecs --emit-json ...``).

Usage:
    python -m benchmarks.bench_codecs            # LM sweep (subprocess)
    python -m benchmarks.bench_codecs --smoke    # CI smoke + JSON
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import textwrap

CODEC_STRATEGIES = [
    # (codec, wire strategy) — each codec on its natural transport
    ("sign1bit", "psum_int8"),
    ("sign1bit", "allgather_1bit"),
    ("ef_sign", "allgather_1bit"),
    ("ternary2bit", "allgather_1bit"),
    ("weighted_vote", "allgather_1bit"),
]

_JSON_DEFAULT = "BENCH_codecs.json"

_WORKER = textwrap.dedent("""
    import os
    # append, so a caller's unrelated XLA_FLAGS (dump dirs etc.) survive
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8").strip()
    import json, sys
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import compat
    from repro.configs.base import (OptimizerConfig, TrainConfig,
                                    VoteStrategy, get_config,
                                    reduced_config)
    from repro.core import codecs
    from repro.models import model as M
    from repro.train import train_step as TS

    cells = json.loads(sys.argv[1])
    mesh = compat.make_mesh((8, 1), ("data", "model"),
                            axis_types=(compat.AxisType.Auto,) * 2)
    out = {}
    for codec, strategy in cells:
        cfg = reduced_config(get_config("glm4-9b"), num_layers=2)
        tcfg = TrainConfig(
            global_batch=8, seq_len=32,
            optimizer=OptimizerConfig(
                kind="signum_vote", learning_rate=3e-3, codec=codec,
                vote_strategy=VoteStrategy(strategy)))
        art = TS.make_train_step(cfg, tcfg, mesh=mesh)
        params, opt = TS.materialize_state(cfg, tcfg, art,
                                           jax.random.PRNGKey(0), mesh)
        batch = M.make_batch(cfg, 8, 32, jax.random.PRNGKey(1))
        batch = jax.tree.map(lambda a: jax.device_put(
            np.asarray(a), NamedSharding(mesh, P("data"))), batch)
        losses = []
        for i in range(30):
            params, opt, met = art.step_fn(params, opt, batch,
                                           jnp.int32(i))
            losses.append(float(met["loss"]))
        bits = codecs.get_codec(codec).wire_bits(art.vote_strategy)
        out[f"{codec}/{strategy}"] = {
            "first": losses[0], "last": losses[-1],
            "bits_per_param": bits}
    print("RESULT " + json.dumps(out))
""")


def rows():
    """Loss drop per (codec, strategy) on the quickstart LM, 8 voters."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    # a CPU semantics harness (8 forced host devices): it must not take
    # the accelerator this process may hold
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-c", _WORKER, json.dumps(CODEC_STRATEGIES)],
        env=env, capture_output=True, text=True, timeout=1800)
    if proc.returncode != 0:
        raise RuntimeError(f"codecs worker failed (rc {proc.returncode}):\n"
                           f"{proc.stderr[-2000:]}")
    line = [l for l in proc.stdout.splitlines()
            if l.startswith("RESULT ")][0]
    res = json.loads(line[len("RESULT "):])
    out = []
    for cell, r in res.items():
        out.append((
            f"codecs/{cell}", r["first"] - r["last"],
            f"loss {r['first']:.2f}->{r['last']:.2f} at "
            f"{r['bits_per_param']:g} bits/param (8 voters, quickstart "
            "model)"))
    return out


# ---------------------------------------------------------------------------
# smoke mode (scripts/ci.sh codec-smoke stage)
# ---------------------------------------------------------------------------


def smoke_rows():
    """One drill per (codec, strategy) cell through ScenarioRunner on the
    8-virtual-device platform; every non-default codec is replayed on the
    mesh backend and asserted bit-identical (the §8 acceptance bar)."""
    from repro.configs.base import VoteStrategy
    from repro.core import codecs
    from repro.sim import AdversarySpec, ScenarioRunner, ScenarioSpec

    out = []
    for codec, strategy in CODEC_STRATEGIES:
        spec = ScenarioSpec(
            f"codec-smoke/{codec}/{strategy}", n_workers=8, n_steps=6,
            dim=128, strategy=VoteStrategy(strategy), codec=codec,
            adversary=AdversarySpec("sign_flip", 0.25))
        tv = ScenarioRunner(spec, backend="virtual").run()
        note = ""
        if codec != "sign1bit":
            tm = ScenarioRunner(spec, backend="mesh").run()
            # RuntimeError, not assert: the acceptance bar must survive
            # `python -O` (the defect class pack_signs just shed)
            if tv.digest != tm.digest:
                raise RuntimeError(
                    f"{spec.name}: codec wire diverged between mesh and "
                    f"virtual ({tv.digest[:12]} != {tm.digest[:12]})")
            note = f" mesh==virtual {tv.digest[:12]}"
        s = tv.summary()
        out.append((
            f"codecs-smoke/{codec}/{strategy}", s["loss_drop"],
            f"{s['bits_per_param']:g} bits/param "
            f"flip={s['mean_flip_fraction']:.3f} "
            f"ties->{s['tie_policy']}{note}"))
    # the codec layer's no-op proof belongs in the smoke lane too:
    # sign1bit and ternary2bit share the psum wire bit for bit
    a = ScenarioRunner(ScenarioSpec(
        "codec-smoke/psum-fixed-point", n_workers=8, n_steps=5,
        dim=96)).run()
    b = ScenarioRunner(ScenarioSpec(
        "codec-smoke/psum-fixed-point", n_workers=8, n_steps=5,
        dim=96, codec="ternary2bit")).run()
    if a.digest != b.digest:
        raise RuntimeError("ternary over psum drifted from sign1bit "
                           f"({a.digest[:12]} != {b.digest[:12]})")
    out.append(("codecs-smoke/ternary_psum_fixed_point", 1.0,
                f"bit-identical to sign1bit over psum ({a.digest[:12]})"))
    return out


def emit_json(rs, path: str) -> None:
    """Machine-readable benchmark baseline (the bench trajectory's seed);
    delegates to :func:`repro.obs.emit_bench_json` — ONE writer, one
    schema, shared with every bench and ``benchmarks.run``."""
    from repro.obs import emit_bench_json
    emit_bench_json(rs, path)


def main() -> None:
    from repro.obs import recorder as obs
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="fast codec drill sweep + mesh==virtual asserts "
                         "(CI lane, <10 s)")
    ap.add_argument("--emit-json", dest="json_out", nargs="?",
                    const=_JSON_DEFAULT, default=None,
                    help=f"write rows as JSON (default {_JSON_DEFAULT})")
    obs.add_trace_arg(ap)
    args = ap.parse_args()

    if args.smoke:
        # force the 8-virtual-device platform before jax initialises,
        # APPENDING so a caller's unrelated XLA_FLAGS survive
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
    rec = obs.activate_trace(args)
    if args.smoke:
        rs = smoke_rows()
        if args.json_out is None:        # CI smoke always seeds the JSON
            args.json_out = _JSON_DEFAULT
    else:
        rs = rows()
    print("name,value,derived")
    for name, value, derived in rs:
        print(f"{name},{value:.6g},{derived}", flush=True)
    if args.json_out:
        emit_json(rs, args.json_out)
        print(f"# wrote {args.json_out}", flush=True)
    obs.finish_trace(rec)


if __name__ == "__main__":
    main()
