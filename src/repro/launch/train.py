"""Single-device training launcher with checkpoint/restart and a watchdog.

Trains one replica (M=1: the vote degenerates to sign) on the default
device, starting from the arch's preset (``configs.presets``: optimizer
mode, momentum dtype, remat, microbatches); a flag overrides the preset
only when it is given. ``--reduced`` runs a smoke-scale config of the
same family, e.g. on the CPU:

  PYTHONPATH=src JAX_PLATFORMS=cpu python -m repro.launch.train \\
      --arch zamba2-1.2b --reduced --steps 200 --batch 8 --seq 128 \\
      --ckpt-dir /tmp/ckpt

Without ``--reduced`` it trains the published widths on one TPU chip.
Multi-chip meshes are built by ``launch.mesh``; ``chip_smoke.py
--four-chip`` drives the mesh step.

Every step runs under a Watchdog, which raises on timeout; a rerun with
the same ``--ckpt-dir`` resumes from the latest checkpoint.
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.checkpoint import AsyncCheckpointer, latest_step_dir, restore
from repro.configs.base import (MomentumMode, ShapeCell, get_config,
                                reduced_config)
from repro.configs.presets import default_train_config
from repro.core import attacks
from repro.data.pipeline import SyntheticLMPipeline
from repro.distributed.fault_tolerance import Watchdog
from repro.launch.compile_cache import enable_compile_cache
from repro.obs import recorder as obs
from repro.train import train_step as TS


#: optimizer kinds ``--opt`` accepts (``OptimizerConfig.kind``)
OPT_KINDS = ("signum_vote", "signsgd_vote", "sgd", "sgdm", "adam")


def build(arch: str, *, reduced: bool, batch: int, seq: int,
          opt_kind: Optional[str] = None, lr: Optional[float] = None,
          momentum: Optional[float] = None,
          microbatches: Optional[int] = None, byz_mode: str = "none",
          byz_n: int = 0):
    """(ModelConfig, TrainConfig) for `arch`: the preset of
    ``default_train_config``, with each of `opt_kind`, `lr`, `momentum`
    and `microbatches` that is not None overriding its field. A sign
    kind other than the preset's must keep the preset's momentum mode:
    Mode B archs (global momentum) train ``signsgd_vote`` only."""
    if opt_kind is not None and opt_kind not in OPT_KINDS:
        raise ValueError(f"unknown optimizer kind {opt_kind!r}; "
                         f"one of {OPT_KINDS}")
    cfg = get_config(arch)
    if reduced:
        cfg = reduced_config(cfg)
    tcfg = default_train_config(
        arch, ShapeCell("cli", seq, batch, "train"),
        kind=opt_kind or "signum_vote",
        byzantine=attacks.build_config(byz_mode, byz_n))
    opt_over = {k: v for k, v in (("kind", opt_kind),
                                  ("learning_rate", lr),
                                  ("momentum", momentum)) if v is not None}
    if (opt_kind is not None and opt_kind != tcfg.optimizer.kind
            and tcfg.optimizer.momentum_mode != MomentumMode.PER_WORKER):
        raise ValueError(
            f"{arch} trains Mode B ({tcfg.optimizer.kind}, global "
            f"momentum); --opt {opt_kind} needs per-worker momentum")
    tcfg = dataclasses.replace(
        tcfg, optimizer=dataclasses.replace(tcfg.optimizer, **opt_over))
    if microbatches is not None:
        tcfg = dataclasses.replace(tcfg, microbatches=microbatches)
    if batch % tcfg.microbatches:
        raise ValueError(
            f"--batch {batch} does not split into {tcfg.microbatches} "
            f"microbatches ({arch}'s preset); pass --microbatches")
    return cfg, tcfg


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-scale config of the same family")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--opt", default=None, choices=OPT_KINDS,
                    help="optimizer kind (default: the arch preset's)")
    ap.add_argument("--lr", type=float, default=None,
                    help="learning rate (default: the arch preset's)")
    ap.add_argument("--momentum", type=float, default=None,
                    help="momentum beta (default: the arch preset's)")
    ap.add_argument("--microbatches", type=int, default=None,
                    help="gradient-accumulation steps (default: the arch "
                         "preset's)")
    ap.add_argument("--byzantine", default="none")
    ap.add_argument("--adversaries", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--serve-dir", default=None,
                    help="publish params-only serving checkpoints here "
                         "(repro.serve.CheckpointWatcher hot-swaps them "
                         "into a live ServeEngine)")
    ap.add_argument("--serve-every", type=int, default=50,
                    help="publish to --serve-dir every N steps")
    ap.add_argument("--watchdog-s", type=float, default=600.0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    obs.add_trace_arg(ap)
    args = ap.parse_args()
    enable_compile_cache()
    trace_rec = obs.activate_trace(args)
    rec = obs.get_recorder()

    cfg, tcfg = build(args.arch, reduced=args.reduced, batch=args.batch,
                      seq=args.seq, opt_kind=args.opt, lr=args.lr,
                      momentum=args.momentum,
                      microbatches=args.microbatches,
                      byz_mode=args.byzantine, byz_n=args.adversaries)
    art = TS.make_train_step(cfg, tcfg, mesh=None)
    params, opt_state = TS.materialize_state(
        cfg, tcfg, art, jax.random.PRNGKey(args.seed))
    pipe = SyntheticLMPipeline(cfg, args.batch, args.seq, seed=args.seed)

    emitter = None
    if args.serve_dir:
        from repro.serve import CheckpointEmitter
        emitter = CheckpointEmitter(args.serve_dir)

    ckpt: Optional[AsyncCheckpointer] = None
    start_step = 0
    if args.ckpt_dir:
        ckpt = AsyncCheckpointer(args.ckpt_dir)
        if latest_step_dir(args.ckpt_dir):
            params, opt_state, data_state, meta = restore(
                args.ckpt_dir, like_params=params, like_opt=opt_state)
            pipe.restore(data_state)
            start_step = int(meta["step"]) + 1
            print(f"restored checkpoint at step {meta['step']}")

    pipe.state.step = start_step
    t0 = time.time()
    for step in range(start_step, args.steps):
        # the host's three phases of a step, named as the chip benchmark
        # names its own (batch, dispatch, sync) in a profiler trace
        with rec.span("train.batch", step=step) as sb:
            batch = {k: jnp.asarray(v) for k, v in next(pipe).items()}
        with Watchdog(args.watchdog_s) as wd:
            with rec.span("train.dispatch", step=step) as sd:
                params, opt_state, metrics = art.step_fn(
                    params, opt_state, batch, jnp.int32(step))
            with rec.span("train.sync", step=step) as ss:
                loss = float(metrics["loss"])
        if rec.enabled:
            rec.step(kind_detail="train", step=step, loss=loss,
                     arch=args.arch, opt=tcfg.optimizer.kind,
                     phase_s={"batch": sb.dur_s, "dispatch": sd.dur_s,
                              "sync": ss.dur_s})
        if wd.fired:
            raise TimeoutError(f"step {step} exceeded {args.watchdog_s}s")
        if step % args.log_every == 0 or step == args.steps - 1:
            dt = time.time() - t0
            print(f"step {step:5d}  loss {loss:8.4f}  "
                  f"({dt / max(step - start_step + 1, 1):.3f}s/step)",
                  flush=True)
        if ckpt and (step + 1) % args.ckpt_every == 0:
            ckpt.save(step, params, opt_state, pipe.checkpoint(),
                      meta={"arch": args.arch, "step": step})
        if emitter and (step + 1) % args.serve_every == 0:
            with rec.span("serve.emit", step=step):
                emitter.emit(step, params, meta={"arch": args.arch})
    if ckpt:
        ckpt.save(args.steps - 1, params, opt_state, pipe.checkpoint(),
                  meta={"arch": args.arch, "step": args.steps - 1})
        ckpt.wait()
    if emitter and args.steps % args.serve_every != 0:
        emitter.emit(args.steps - 1, params, meta={"arch": args.arch})
    obs.finish_trace(trace_rec)
    print("done.")


if __name__ == "__main__":
    main()
