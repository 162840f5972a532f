"""End-to-end driver: train a ~100M-parameter LM for a few hundred steps
with SIGNUM + majority vote, with checkpointing every 100 steps.

The model is a glm4-family transformer scaled to ~100M params
(12 layers, d_model=512, vocab 32k). On CPU this takes a few minutes; on
one TPU chip the same step runs under launch/train.py.

    PYTHONPATH=src python examples/train_lm.py [--steps 300]
"""
import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.checkpoint.checkpoint import AsyncCheckpointer
from repro.configs.base import OptimizerConfig, TrainConfig, get_config
from repro.data.pipeline import SyntheticLMPipeline
from repro.models import model as M
from repro.train import train_step as TS


def config_100m():
    base = get_config("glm4-9b")
    return dataclasses.replace(
        base, name="glm4-100m", num_layers=12, d_model=512, num_heads=8,
        num_kv_heads=2, head_dim=64, d_ff=1536, vocab_size=32_000)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_100m_ckpt")
    args = ap.parse_args()

    cfg = config_100m()
    print(f"model: {cfg.name}  params={cfg.param_count() / 1e6:.1f}M")
    tcfg = TrainConfig(
        global_batch=args.batch, seq_len=args.seq,
        optimizer=OptimizerConfig(kind="signum_vote", learning_rate=3e-4,
                                  momentum=0.9, warmup_steps=20,
                                  total_steps=args.steps))
    art = TS.make_train_step(cfg, tcfg, mesh=None)
    params, opt_state = TS.materialize_state(cfg, tcfg, art,
                                             jax.random.PRNGKey(0))
    pipe = SyntheticLMPipeline(cfg, args.batch, args.seq, seed=0)
    ckpt = AsyncCheckpointer(args.ckpt_dir)

    t0 = time.time()
    for step in range(args.steps):
        batch = {k: jnp.asarray(v) for k, v in next(pipe).items()}
        params, opt_state, met = art.step_fn(params, opt_state, batch,
                                             jnp.int32(step))
        if step % 20 == 0 or step == args.steps - 1:
            dt = (time.time() - t0) / (step + 1)
            print(f"step {step:4d}  loss {float(met['loss']):8.4f}  "
                  f"ce {float(met['ce']):8.4f}  {dt:.2f}s/step", flush=True)
        if (step + 1) % 100 == 0:
            ckpt.save(step, params, opt_state, pipe.checkpoint(),
                      meta={"arch": cfg.name, "step": step})
    ckpt.wait()
    print(f"done in {time.time() - t0:.0f}s; checkpoints in {args.ckpt_dir}")


if __name__ == "__main__":
    main()
