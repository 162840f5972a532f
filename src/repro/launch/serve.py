"""Batched serving driver: prefill a batch of prompts, then decode tokens.

CPU-scale entry point (the same decode/prefill steps lower on the
production mesh in the dry-run):

  PYTHONPATH=src python -m repro.launch.serve --arch glm4-9b --reduced \\
      --batch 4 --prompt-len 32 --gen 16

``--engine`` switches from the fixed-batch loop to the continuous-
batching :class:`repro.serve.ServeEngine` (in-flight admission over a
recycled slot pool) fed by the deterministic Poisson generator:

  PYTHONPATH=src python -m repro.launch.serve --arch glm4-9b --reduced \\
      --engine --requests 16 --rate 0.5

``--trace FILE`` records obs spans/counters either way (render with
scripts/trace_report.py).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_config, reduced_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as M
from repro.obs import recorder as obs
from repro.train.serve_step import make_cache_rehome, make_decode_step


def _run_engine(cfg, params, args) -> None:
    from repro.serve import ServeConfig, ServeEngine, poisson_requests

    max_len = args.prompt_len + args.gen
    sc = ServeConfig(n_slots=args.batch, max_len=max_len,
                     prompt_pad=args.prompt_len,
                     temperature=args.temperature, seed=args.seed)
    eng = ServeEngine(cfg, params, sc)
    reqs = poisson_requests(
        n_requests=args.requests, rate=args.rate,
        vocab_size=cfg.vocab_size, prompt_lens=(args.prompt_len,),
        gen_range=(args.gen, args.gen), seed=args.seed)
    t0 = time.time()
    rep = eng.run(reqs)
    dt = time.time() - t0
    print(f"engine: {rep.completed}/{rep.n_requests} requests, "
          f"{rep.total_tokens} tokens in {rep.ticks} ticks "
          f"({dt:.2f}s, goodput {rep.goodput_tokens_per_tick:.2f} "
          f"tok/tick, occupancy {rep.occupancy_mean:.2f})")
    print(f"latency ticks p50/p95/p99: {rep.latency_p50:.1f}/"
          f"{rep.latency_p95:.1f}/{rep.latency_p99:.1f}  "
          f"ttft p50: {rep.ttft_p50:.1f}")
    first = min(rep.records)
    print("sampled token ids (first request):",
          rep.records[first].tokens)


def _run_batch(cfg, params, args) -> None:
    rec = obs.get_recorder()
    key = jax.random.PRNGKey(args.seed)
    batch = M.make_batch(cfg, args.batch, args.prompt_len, key)

    max_len = args.prompt_len + args.gen
    # prefill token-by-token through the decode path for recurrent archs;
    # transformer archs use the batched prefill
    t0 = time.time()
    with rec.span("serve.prefill", batch=args.batch,
                  prompt_len=args.prompt_len):
        logits, cache = jax.jit(
            lambda p, b: M.prefill(cfg, p, b))(params, batch)
        # one jitted re-home into the max_len decode cache (recurrent
        # state passes through, seq leaves land at the origin)
        cache = make_cache_rehome(cfg, args.batch, max_len)(cache)
    prefill_s = time.time() - t0
    print(f"prefill: {args.batch}x{args.prompt_len} in {prefill_s:.2f}s")

    decode = make_decode_step(cfg)
    tokens = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
    out = [tokens]
    t0 = time.time()
    with rec.span("serve.decode", steps=args.gen):
        for i in range(args.gen):
            pos = jnp.int32(args.prompt_len + i)
            logits_t, cache = decode(params, tokens, cache, pos)
            if args.temperature > 0:
                key, sub = jax.random.split(key)
                tokens = jax.random.categorical(
                    sub, logits_t / args.temperature, axis=-1
                ).astype(jnp.int32)[:, None]
            else:
                tokens = jnp.argmax(logits_t, axis=-1
                                    ).astype(jnp.int32)[:, None]
            out.append(tokens)
    gen_s = time.time() - t0
    toks = jnp.concatenate(out, axis=1)
    print(f"decode: {args.gen} steps x batch {args.batch} in {gen_s:.2f}s "
          f"({args.gen * args.batch / max(gen_s, 1e-9):.1f} tok/s)")
    print("sampled token ids (first row):", np.asarray(toks)[0].tolist())


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--engine", action="store_true",
                    help="continuous batching via repro.serve.ServeEngine "
                         "(slot pool of --batch, in-flight admission)")
    ap.add_argument("--requests", type=int, default=16,
                    help="--engine: number of Poisson requests")
    ap.add_argument("--rate", type=float, default=0.5,
                    help="--engine: offered load in requests/tick")
    obs.add_trace_arg(ap)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced_config(cfg)
    params = M.init_params(cfg, jax.random.PRNGKey(args.seed))

    rec = obs.activate_trace(args)
    try:
        if args.engine:
            _run_engine(cfg, params, args)
        else:
            _run_batch(cfg, params, args)
    finally:
        obs.finish_trace(rec)


if __name__ == "__main__":
    main()
