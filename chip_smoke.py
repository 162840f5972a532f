#!/usr/bin/env python3
"""Proof that the signSGD majority-vote trainer runs on a TPU.

    python chip_smoke.py              # one chip: kernels, then the trainer
    python chip_smoke.py --four-chip  # four chips: the vote over the mesh

One chip, in one process:

* kernel phase: each Pallas entry point runs at 2^24 elements (M=4 voters)
  and must equal its ``kernels/ref.py`` oracle bit for bit; its lowered
  program must hold the kernel (``tpu_custom_call``). The voting kernels
  run again at 2^22 elements with the M of their callers (8, 15), 32 and,
  for the fused vote, 64. The oracles of the two float kernels run on the
  chip, since XLA's CPU backend may round a*b+c differently (the
  distance to the CPU oracle is printed, not checked); the bit-only
  oracles run on the host's CPU device, since the jnp packers pad a
  32-wide minor dim to the TPU's 128 lanes and would not fit the chip's
  HBM at this size.
* train phase: zamba2-1.2b at its published widths and depth, Mode A
  signum + majority vote, built by ``repro.launch.train.build`` from the
  arch preset, 5 steps on ``SyntheticLMPipeline`` batches. The compiled
  step must alias (donate) the params and optimizer state, and every
  loss must be finite.

``--four-chip`` runs only the mesh ``(data=4, model=1)``:

* vote: seeded per-replica int8 signs voted by ``MeshBackend`` under
  ``psum_int8`` and ``allgather_1bit`` equal a numpy majority with each
  wire's tie rule (DESIGN.md §5), bit for bit, on every replica.
* train: 3 zamba2-1.2b Mode A steps under ``allgather_1bit``; leaf by
  leaf the parameter update equals -lr times the majority of the four
  replicas' new momentum signs, exactly in the params' dtype. Where
  |p| is large, p - lr and p + lr both round back to p in bf16, so the
  check cannot see the vote there: each leaf's count of such elements
  and of elements that moved is printed, and every leaf that can move
  must have moved.

There is no CPU fallback: without a TPU the script exits non-zero before
any phase. Any failure raises. The last line of standard output is one
JSON object naming the device.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

N_KERNEL = 1 << 24     # elements per kernel call
M_VOTERS = 4
N_WIDE = 1 << 22       # elements per call of the many-voter kernel cases
M_WIDE = (8, 15, 32)   # bench_comm's voter counts, and 32
ARCH = "zamba2-1.2b"
BATCH_ONE = 4          # global batch on one chip
BATCH_FOUR = 16        # global batch on four chips (4 per replica)
SEQ = 2048


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------


def kernel_phase() -> None:
    import jax
    import jax.numpy as jnp
    from repro.kernels import (bitpack as bp, fused_vote as fv, ref,
                               signum_update as su, ternary_pack as tp,
                               vote as vt)

    n, m = N_KERNEL, M_VOTERS
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    x = jax.random.normal(ks[0], (m, n), jnp.float32)
    words = jax.random.bits(ks[1], (m, n // 32), jnp.uint32)
    tern = jax.random.randint(ks[2], (m, n), -1, 2, jnp.int32)
    g = jax.random.normal(ks[3], (n // 4096, 4096), jnp.float32)
    mom = jax.random.normal(ks[4], (n // 4096, 4096), jnp.float32)
    beta, eta, wd = 0.9, 1e-3, 1e-2
    cpu = jax.devices("cpu")[0]
    tern_words = jax.device_put(
        jax.jit(ref.ternary_pack)(jax.device_put(tern, cpu)), x.sharding)

    # (name, kernel, static args, inputs, oracle, oracle on the chip?)
    cases = [
        ("fused_majority_2d_f32", fv.fused_majority_2d, (), (x,),
         ref.fused_majority, False),
        ("fused_majority_2d_bf16", fv.fused_majority_2d, (),
         (x.astype(jnp.bfloat16),), ref.fused_majority, False),
        ("bitpack_2d", bp.bitpack_2d, (), (x[0].reshape(-1, 4096),),
         ref.bitpack, False),
        ("bitunpack_2d", bp.bitunpack_2d, (),
         (words[0].reshape(-1, 128),), ref.bitunpack, False),
        ("majority_packed", vt.majority_packed, (), (words,), ref.majority,
         False),
        ("ternary_pack_2d", tp.ternary_pack_2d, (),
         (tern[0].reshape(-1, 2048),), ref.ternary_pack, False),
        ("ternary_tally_packed", tp.ternary_tally_packed, (),
         (tern_words,), ref.ternary_majority, False),
        ("momentum_sign_pack", su.momentum_sign_pack, (beta,), (g, mom),
         lambda g_, m_: ref.momentum_sign_pack(g_, m_, beta), True),
        ("apply_vote", su.apply_vote, (eta, wd),
         (mom, words[0].reshape(-1, 128)),
         lambda p_, v_: ref.apply_vote(p_, v_, eta, wd), True),
    ]
    for mv in M_WIDE + (64,):
        kx, kw, kt = jax.random.split(jax.random.fold_in(ks[5], mv), 3)
        xm = jax.random.normal(kx, (mv, N_WIDE), jnp.float32)
        cases += [(f"fused_majority_2d_f32_m{mv}", fv.fused_majority_2d, (),
                   (xm,), ref.fused_majority, False)]
        if mv == 64:
            continue
        wm = jax.random.bits(kw, (mv, N_WIDE // 32), jnp.uint32)
        tm = jax.device_put(jax.jit(ref.ternary_pack)(jax.device_put(
            jax.random.randint(kt, (mv, N_WIDE), -1, 2, jnp.int32), cpu)),
            x.sharding)
        cases += [
            (f"fused_majority_2d_bf16_m{mv}", fv.fused_majority_2d, (),
             (xm.astype(jnp.bfloat16),), ref.fused_majority, False),
            (f"majority_packed_m{mv}", vt.majority_packed, (), (wm,),
             ref.majority, False),
            (f"ternary_tally_packed_m{mv}", tp.ternary_tally_packed, (),
             (tm,), ref.ternary_majority, False)]
    bad = []
    for name, fn, static, args, oracle, on_chip in cases:
        hlo = fn.lower(*args, *static).as_text()
        has_kernel = "tpu_custom_call" in hlo
        got = jax.block_until_ready(fn(*args, *static))     # compile + run
        t0 = time.perf_counter()
        got = jax.block_until_ready(fn(*args, *static))
        dt = time.perf_counter() - t0
        want = jax.jit(oracle)(*(args if on_chip
                                 else jax.device_put(args, cpu)))
        pairs = [(np.asarray(a), np.asarray(b)) for a, b in
                 zip(jax.tree.leaves(got), jax.tree.leaves(want))]
        exact = all(np.array_equal(a, b) for a, b in pairs)
        extra = "" if exact else f" mismatch={_mismatch(pairs)}"
        if on_chip:
            want_cpu = jax.jit(oracle)(*jax.device_put(args, cpu))
            extra += " cpu_oracle_mismatch=" + str(_mismatch(
                [(c, k) for c, (k, _) in
                 zip(jax.tree.leaves(want_cpu), pairs)]))
        log(f"kernel {name:28s} bit_exact={exact} "
            f"tpu_custom_call={has_kernel} wall_ms={dt * 1e3:.3f}{extra}")
        if not (exact and has_kernel):
            bad.append(name)
    if bad:
        raise AssertionError(f"kernels not bit-exact or not compiled as "
                             f"kernels: {bad}")


def _mismatch(pairs):
    """Per output: (differing elements, largest distance), the distance
    in units of the last place for floats and in differing bits for
    packed uint32 words."""
    out = []
    for a, b in pairs:
        a, b = np.asarray(a), np.asarray(b)
        if a.dtype.kind == "f":
            it = np.dtype(f"int{8 * a.dtype.itemsize}")
            d = np.abs(a.view(it).astype(np.int64) - b.view(it))
        else:
            d = np.bitwise_count(a ^ b)
        out.append((int(np.count_nonzero(d)), int(d.max())))
    return out


def _state_bytes(tree) -> int:
    import jax
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def train_phase(batch: int, seq: int, steps: int) -> None:
    import jax
    import jax.numpy as jnp
    from repro.data.pipeline import SyntheticLMPipeline
    from repro.launch import train as LT
    from repro.train import train_step as TS

    cfg, tcfg = LT.build(ARCH, reduced=False, batch=batch, seq=seq)
    log(f"train {ARCH}: params={cfg.param_count()} batch={batch} seq={seq} "
        f"microbatches={tcfg.microbatches} remat={tcfg.remat} "
        f"momentum_dtype={tcfg.optimizer.momentum_dtype} "
        f"vote={tcfg.optimizer.vote_strategy.value}")
    art = TS.make_train_step(cfg, tcfg, mesh=None)
    params, opt_state = TS.materialize_state(cfg, tcfg, art,
                                             jax.random.PRNGKey(0))
    pipe = SyntheticLMPipeline(cfg, batch, seq, seed=0)
    batches = [{k: jnp.asarray(v) for k, v in next(pipe).items()}
               for _ in range(steps)]

    t0 = time.perf_counter()
    compiled = art.step_fn.lower(params, opt_state, batches[0],
                                 jnp.int32(0)).compile()
    log(f"train compile_s={time.perf_counter() - t0:.3f}")
    ma = compiled.memory_analysis()
    need = _state_bytes((params, opt_state))
    log(f"train memory_analysis: argument={ma.argument_size_in_bytes} "
        f"output={ma.output_size_in_bytes} alias={ma.alias_size_in_bytes} "
        f"temp={ma.temp_size_in_bytes} params+opt_state={need}")
    if ma.alias_size_in_bytes < need:
        raise AssertionError(
            f"the step aliases {ma.alias_size_in_bytes} B, less than the "
            f"{need} B of params and optimizer state: not donated")

    for step, b in enumerate(batches):
        t0 = time.perf_counter()
        params, opt_state, metrics = compiled(params, opt_state, b,
                                              jnp.int32(step))
        jax.block_until_ready((params, opt_state, metrics))
        dt = time.perf_counter() - t0
        loss = float(metrics["loss"])
        log(f"train step {step} wall_s={dt:.4f} loss={loss!r}")
        if not np.isfinite(loss):
            raise AssertionError(f"non-finite loss {loss} at step {step}")
    stats = jax.devices()[0].memory_stats() or {}
    log(f"train peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
        f"bytes_limit={stats.get('bytes_limit')}")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def _np_majority(signs: np.ndarray, strategy: str) -> np.ndarray:
    """(M, n) int8 signs -> (n,) int8 majority with the wire's tie rule:
    psum_int8 sums ternary signs (abstain on 0, tie -> 0); allgather_1bit
    binarises (0 -> +1) and breaks ties to +1."""
    if strategy == "psum_int8":
        return np.sign(signs.sum(0, dtype=np.int32)).astype(np.int8)
    pos = (signs >= 0).sum(0, dtype=np.int32)
    return np.where(2 * pos >= signs.shape[0], 1, -1).astype(np.int8)


def vote_phase(mesh) -> None:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import compat
    from repro.configs.base import VoteStrategy
    from repro.core import vote_api as va

    m = mesh.shape["data"]
    rng = np.random.default_rng(0)
    signs = rng.integers(-1, 2, size=(m, N_KERNEL), dtype=np.int8)
    x = jax.device_put(signs, NamedSharding(mesh, P("data")))
    backend = va.MeshBackend(axes=("data",))
    for strategy in (VoteStrategy.PSUM_INT8, VoteStrategy.ALLGATHER_1BIT):
        def body(v, strategy=strategy):
            out = backend.execute(va.VoteRequest(
                payload=v[0], form="leaf", strategy=strategy))
            return out.votes[None]
        fn = jax.jit(compat.shard_map(body, mesh=mesh, in_specs=P("data"),
                                      out_specs=P("data"),
                                      axis_names={"data"}))
        got = np.asarray(fn(x))
        want = _np_majority(signs, strategy.value)
        tie = float(np.mean(signs.sum(0, dtype=np.int32) == 0))
        exact = all(np.array_equal(got[r], want) for r in range(m))
        log(f"vote {strategy.value:15s} replicas={m} n={N_KERNEL} "
            f"bit_exact={exact} zero_sum_share={tie:.6f}")
        if not exact:
            raise AssertionError(f"{strategy.value} vote != numpy majority")


def _replica_majority(m: int):
    """jit: per leaf, the majority of the M replicas' momentum signs
    (ties -> +1) as int8, in plain jnp and apart from the vote code.
    Integer work only: the device rounds nothing here. The float side
    of the check runs on the host, since the chip may keep a bf16
    round trip inside a fusion at f32 and so never round it."""
    import jax
    import jax.numpy as jnp

    def leaf(mom):
        pos = jnp.sum(mom >= 0, axis=0, dtype=jnp.int32)
        return jnp.where(2 * pos >= m, 1, -1).astype(jnp.int8)

    return jax.jit(lambda mom: jax.tree.map(leaf, mom))


def _leaf_check(p0: np.ndarray, p1: np.ndarray, vote: np.ndarray,
                lr: np.float32):
    """(elements of p1 that differ from p0 - lr * vote in p0's dtype,
    elements where p0 - lr or p0 + lr rounds to other than p0, elements
    that moved)."""
    p32 = p0.astype(np.float32)
    want = (p32 - lr * vote.astype(np.float32)).astype(p0.dtype)
    movable = (((p32 - lr).astype(p0.dtype) != p0)
               | ((p32 + lr).astype(p0.dtype) != p0))
    return (int(np.count_nonzero(p1 != want)), int(np.count_nonzero(movable)),
            int(np.count_nonzero(p1 != p0)))


def mesh_train_phase(mesh, batch: int, seq: int, steps: int) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs.base import VoteStrategy
    from repro.core.signum import lr_at
    from repro.data.pipeline import SyntheticLMPipeline
    from repro.launch import train as LT
    from repro.train import train_step as TS

    cfg, tcfg = LT.build(ARCH, reduced=False, batch=batch, seq=seq)
    opt_cfg = dataclasses.replace(tcfg.optimizer,
                                  vote_strategy=VoteStrategy.ALLGATHER_1BIT)
    if opt_cfg.weight_decay:
        raise AssertionError("the update check assumes weight_decay == 0")
    tcfg = dataclasses.replace(tcfg, optimizer=opt_cfg)
    art = TS.make_train_step(cfg, tcfg, mesh=mesh)
    log(f"mesh train {ARCH}: replicas={art.n_vote_replicas} "
        f"global_batch={batch} seq={seq} microbatches={tcfg.microbatches} "
        f"vote={art.vote_strategy.value}")
    params, opt_state = TS.materialize_state(cfg, tcfg, art,
                                             jax.random.PRNGKey(0), mesh)
    pipe = SyntheticLMPipeline(cfg, batch, seq, seed=0)
    shard = NamedSharding(mesh, P("data"))
    majority = _replica_majority(art.n_vote_replicas)
    for step in range(steps):
        b = {k: jax.device_put(v, shard) for k, v in next(pipe).items()}
        before = {k: np.asarray(v) for k, v in params.items()}
        t0 = time.perf_counter()
        params, opt_state, metrics = art.step_fn(params, opt_state, b,
                                                 jnp.int32(step))
        jax.block_until_ready((params, opt_state, metrics))
        dt = time.perf_counter() - t0
        loss = float(metrics["loss"])
        if not np.isfinite(loss):
            raise AssertionError(f"non-finite loss {loss} at step {step}")
        lr = np.float32(lr_at(opt_cfg, jnp.int32(step)))
        votes = majority(opt_state["momentum"])
        counts = {k: _leaf_check(p0, np.asarray(params[k]),
                                 np.asarray(votes[k]), lr)
                  for k, p0 in before.items()}
        del before, votes
        bad, still = [], []
        for k, (wrong, movable, moved) in counts.items():
            log(f"mesh check step {step} {k} elements={params[k].size} "
                f"movable={movable} moved={moved} mismatched={wrong}")
            if wrong:
                bad.append(k)
            if movable and not moved:
                still.append(k)
        blind = [k for k, c in counts.items() if not c[1]]
        log(f"mesh train step {step} wall_s={dt:.4f} loss={loss!r} "
            f"update_is_minus_lr_times_majority={not bad} "
            f"moved={sum(int(c[2]) for c in counts.values())} "
            f"blind_leaves={blind}")
        if bad or still:
            raise AssertionError(
                f"step {step}: update != -lr * majority on leaves {bad}; "
                f"movable leaves that did not move: {still}")
    for d in mesh.devices.flat:
        stats = d.memory_stats() or {}
        log(f"mesh device {d.id} peak_bytes_in_use="
            f"{stats.get('peak_bytes_in_use')}")


# ---------------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the 4-chip mesh vote and train check")
    args = ap.parse_args()

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    log(f"devices: {len(jax.devices())} x {dev.device_kind}")

    if args.four_chip:
        from repro.launch.mesh import make_replica_mesh
        mesh = make_replica_mesh()
        if mesh.shape["data"] != 4:
            raise AssertionError(f"--four-chip needs 4 chips, mesh is "
                                 f"{dict(mesh.shape)}")
        vote_phase(mesh)
        mesh_train_phase(mesh, BATCH_FOUR, SEQ, steps=3)
    else:
        kernel_phase()
        train_phase(BATCH_ONE, SEQ, steps=5)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
