"""Distributed train-step factory.

The step is ``jax.jit(shard_map(local_step))`` — **manual** over the vote
axes (``'data'``, ``'pod'``) so per-replica gradients are visible and the
majority vote's collectives are explicit, **auto** over ``'model'`` so XLA
SPMD partitions the TP/EP matmuls (DESIGN.md §4; validated against a flat
reference before the framework was built).

Paths through the step:

* Mode A (per-worker momentum, paper Algorithm 1): params replicated over
  the vote axes; explicit ``tree_vote`` inside the optimizer; per-worker
  momentum stored with a leading vote-axis dimension.
* Mode B + FSDP (scalable): ZeRO-3 param gathering via hooks whose
  backward **is** the majority vote (int8 reduce-scatter) — see
  ``core.majority_vote.make_fsdp_hooks``; only small replicated leaves
  vote explicitly.
* Dense baselines (sgd/sgdm/adam): same harness, psum-mean aggregation.

Without a mesh the factory returns a single-process step (M=1: the vote
degenerates to sign) for tests and CPU examples.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import compat
from repro.configs.base import (ModelConfig, MomentumMode, TrainConfig,
                                VoteStrategy)
from repro.core import vote_plan as vp
from repro.core.majority_vote import make_fsdp_hooks
from repro.core.signum import build_optimizer
from repro.core.vote_engine import resolve_strategy
from repro.distributed import sharding as shd
from repro.models import model as M
from repro.obs import recorder as obs


# ---------------------------------------------------------------------------
# spec helpers
# ---------------------------------------------------------------------------


def _manual_only(spec: P, manual: Tuple[str, ...]) -> P:
    """Strip non-manual axes from a PartitionSpec (for shard_map in_specs)."""
    def fix(e):
        if e is None:
            return None
        if isinstance(e, (tuple, list)):
            kept = tuple(x for x in e if x in manual)
            return kept if kept else None
        return e if e in manual else None

    return P(*(fix(e) for e in spec))


def _auto_only(spec: P, manual: Tuple[str, ...]) -> P:
    """Strip manual axes from a PartitionSpec (constraints inside shard_map)."""
    def fix(e):
        if e is None:
            return None
        if isinstance(e, (tuple, list)):
            kept = tuple(x for x in e if x not in manual)
            return kept if kept else None
        return None if e in manual else e

    return P(*(fix(e) for e in spec))


def _constrain_grads(grads: Dict[str, jax.Array], specs: Dict[str, P],
                     manual: Tuple[str, ...]) -> Dict[str, jax.Array]:
    """Pin each gradient leaf to its parameter's auto-axis sharding.

    Without this the SPMD partitioner is free to choose any sharding for
    the weight-gradient dots and routinely picks one that forces a
    full-size cotangent all-gather (measured: 6 x 2 GiB fp32 gathers on
    zamba2's shared block)."""
    out = {}
    for k, g in grads.items():
        spec = _auto_only(specs[k], manual)
        out[k] = compat.with_sharding_constraint(g, spec)
    return out


def _per_worker(state: Dict[str, Any], fn: Callable) -> Dict[str, Any]:
    """`fn` over each leaf of the per-worker momentum and error trees.
    The unwrap and re-wrap are the momentum's layout, so they run under
    ``sign_momentum`` like its update: XLA fuses the re-wrap into the
    update and names the fusion after it."""
    state = {**state}
    with jax.named_scope("sign_momentum"):
        for key in ("momentum", "error"):
            if key in state:
                state[key] = jax.tree.map(fn, state[key])
    return state


def _mesh_axis_sizes(mesh) -> Dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


@dataclasses.dataclass
class StepArtifacts:
    """Everything the trainer / dry-run needs alongside the step fn."""

    step_fn: Callable
    param_specs: Dict[str, P]          # full specs (data+model)
    param_shard_specs: Dict[str, P]    # manual-only (shard_map in_specs)
    opt_specs: Any
    batch_spec: Any
    n_vote_replicas: int
    vote_axes: Tuple[str, ...]
    fused_leaves: Tuple[str, ...]
    #: resolved (never AUTO); under a plan, the schedule's strategy when
    #: unique, None for mixed-strategy schedules (see `plan`)
    vote_strategy: Optional[VoteStrategy] = None
    codec: str = "sign1bit"            # resolved gradient codec (§8)
    plan: Optional[vp.VotePlan] = None  # bucketed wire schedule (§9)


# ---------------------------------------------------------------------------
# factory
# ---------------------------------------------------------------------------


def make_train_step(cfg: ModelConfig, tcfg: TrainConfig,
                    mesh=None) -> StepArtifacts:
    obs.install_compile_watch()     # set-up phase counters (jit.*)
    opt_cfg = tcfg.optimizer
    byz = tcfg.byzantine if tcfg.byzantine.mode != "none" else None
    is_sign = opt_cfg.kind in ("signum_vote", "signsgd_vote")
    per_worker = (is_sign and opt_cfg.momentum_mode == MomentumMode.PER_WORKER
                  and opt_cfg.momentum > 0)

    shapes = cfg.param_shapes()
    axis_names = tuple(mesh.axis_names) if mesh is not None else ()
    vote_axes = tuple(a for a in ("pod", "data") if a in axis_names)
    sizes = _mesh_axis_sizes(mesh) if mesh is not None else {}
    n_votes = int(np.prod([sizes.get(a, 1) for a in vote_axes])) if mesh else 1

    # AUTO resolves here, once, against the comm cost model — mesh shape,
    # param count and codec are static, so the whole step compiles against
    # one wire protocol and the dry-run records which one won. The codec
    # restricts the candidate set and prices the gathered exchange at its
    # symbol width (DESIGN.md §8).
    codec_name = opt_cfg.resolved_codec
    resolved = resolve_strategy(opt_cfg.vote_strategy, cfg.param_count(),
                                sizes.get("data", 1), sizes.get("pod", 1),
                                codec=codec_name)
    if resolved != opt_cfg.vote_strategy:
        opt_cfg = dataclasses.replace(opt_cfg, vote_strategy=resolved)
    if is_sign:
        from repro.core import codecs as codecs_mod
        codecs_mod.get_codec(codec_name).validate_strategy(resolved)

    specs = shd.param_specs(shapes, fsdp=tcfg.fsdp, mesh_shape=sizes or None)
    fused = tcfg.fsdp and mesh is not None
    hook = (make_fsdp_hooks(specs, axis_names, vote=is_sign, byz=byz)
            if fused else None)
    fused_leaves = tuple(
        k for k, s in specs.items()
        if any("data" in (e if isinstance(e, tuple) else (e,))
               for e in s if e is not None)) if fused else ()

    # VotePlan (§9): flatten the explicitly-voted leaves (everything the
    # fused ZeRO backward does NOT already vote) into one bucketed wire
    # buffer. Built here, once — shapes, mesh sizes and codec map are all
    # static — with the ORIGINAL strategy so AUTO prices the whole bucket
    # schedule per codec group instead of one tree-sized message.
    plan = None
    if is_sign and opt_cfg.bucket_bytes != 0:
        # Mode B consults voted_leaves and votes only the raw remainder
        # explicitly; Mode A votes the FULL momentum tree regardless of
        # FSDP hooks, so its plan must cover every leaf
        explicit = ({k: v for k, v in shapes.items()
                     if k not in fused_leaves}
                    if opt_cfg.momentum_mode == MomentumMode.GLOBAL
                    else dict(shapes))
        if explicit:
            plan = vp.build_plan(
                explicit, bucket_bytes=opt_cfg.bucket_bytes,
                codec_map=opt_cfg.codec_map, default_codec=codec_name,
                strategy=tcfg.optimizer.vote_strategy,
                data_size=sizes.get("data", 1),
                pod_size=sizes.get("pod", 1),
                dtypes={k: cfg.dtype for k in explicit},
                overlap=opt_cfg.overlap)
            # the plan's schedule is the wire that actually compiles:
            # report ITS resolution (None when a mixed map resolved
            # different strategies per group — art.plan has the detail),
            # not the leaf-wise single-message pricing
            group_strats = {g.strategy for g in plan.groups}
            resolved = (group_strats.pop() if len(group_strats) == 1
                        else None)

    # byz also passes to the optimizer: non-FSDP leaves vote explicitly and
    # the same replicas must act adversarially on them.
    opt = build_optimizer(opt_cfg, vote_axes, byz=byz,
                          fused_leaves=fused_leaves,
                          diagnostics=tcfg.diagnostics,
                          n_vote_replicas=n_votes, plan=plan)

    def loss_of(p, b):
        return M.loss_fn(cfg, p, b, hook=hook, remat=tcfg.remat)

    def local_step(params, opt_state, batch, step):
        # ---- unwrap per-worker momentum (leading vote axis, local = 1) ----
        if per_worker:
            opt_state = _per_worker(opt_state, lambda v: v[0])
        # ---- local gradients (manual over vote axes => no auto psum) ----
        if tcfg.microbatches > 1:
            # Sign optimizers accumulate in bf16: only the sign of the sum
            # survives, and an fp32 accumulator's dtype demand propagates
            # back through the scan transpose, doubling every stacked
            # gradient buffer (measured on qwen2-moe). Dense baselines keep
            # fp32.
            acc_dt = (jnp.bfloat16 if is_sign else jnp.float32)

            def split(x):
                return x.reshape((tcfg.microbatches,
                                  x.shape[0] // tcfg.microbatches)
                                 + x.shape[1:])
            micro = jax.tree.map(split, batch)

            def acc_body(carry, mb):
                (loss, met), g = jax.value_and_grad(
                    loss_of, has_aux=True)(params, mb)
                with jax.named_scope("grad_accum"):
                    carry = jax.tree.map(
                        lambda a, b: a + b.astype(a.dtype), carry, g)
                return carry, (loss, met)

            with jax.named_scope("grad_accum"):
                zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, acc_dt),
                                     params)
            grads, (losses, mets) = jax.lax.scan(acc_body, zeros, micro)
            with jax.named_scope("grad_accum"):
                grads = jax.tree.map(lambda g: g / tcfg.microbatches, grads)
            loss = jnp.mean(losses)
            metrics = jax.tree.map(jnp.mean, mets)
        else:
            (loss, metrics), grads = jax.value_and_grad(
                loss_of, has_aux=True)(params, batch)
        if mesh is not None:
            grads = _constrain_grads(grads, specs, vote_axes)

        # ---- optimizer (vote happens inside) ----
        new_params, new_state, diag = opt.update(grads, opt_state, params,
                                                 step)
        # ---- re-wrap per-worker momentum ----
        if per_worker:
            new_state = _per_worker(new_state, lambda v: v[None])
        # ---- metrics: average over replicas ----
        if vote_axes:
            loss = jax.lax.pmean(loss, vote_axes)
            metrics = jax.tree.map(
                lambda x: jax.lax.pmean(x, vote_axes), metrics)
        metrics = {**metrics, "loss": loss, **diag}
        return new_params, new_state, metrics

    # ------------------------------------------------------------------
    if mesh is None:
        return StepArtifacts(
            step_fn=jax.jit(local_step, donate_argnums=(0, 1)),
            param_specs=specs,
            param_shard_specs={k: P() for k in specs}, opt_specs=None,
            batch_spec=None, n_vote_replicas=1, vote_axes=(),
            fused_leaves=fused_leaves, vote_strategy=resolved,
            codec=codec_name, plan=plan)

    manual = vote_axes
    p_manual = {k: _manual_only(s, manual) for k, s in specs.items()}

    # opt-state manual specs mirror param layout; per-worker momentum gets
    # the leading vote-axis spec.
    state_shape = jax.eval_shape(
        opt.init, {k: jax.ShapeDtypeStruct(v, jnp.float32)
                   for k, v in shapes.items()})
    opt_manual: Dict[str, Any] = {}
    for key in state_shape:
        if key in ("momentum", "error"):
            # "error" may be a subset of the params under a plan codec
            # map (only the EF-mapped leaves carry a residual)
            names = tuple(state_shape[key])
            if per_worker:
                opt_manual[key] = {
                    k: P(manual, *_manual_only(specs[k], manual))
                    for k in names}
            else:
                opt_manual[key] = {k: p_manual[k] for k in names}
        elif key in ("m", "v"):  # dense-baseline moments follow params
            opt_manual[key] = dict(p_manual)
        elif key == "delayed":   # one-round vote buffer: param layout,
            opt_manual[key] = dict(p_manual)   # replicated over the vote
        else:
            opt_manual[key] = P()

    batch_struct = M.input_specs(
        cfg, type("C", (), {"global_batch": tcfg.global_batch,
                            "seq_len": tcfg.seq_len, "kind": "train",
                            "name": "train"})())["batch"]
    batch_spec = jax.tree.map(lambda _: P(manual), batch_struct)

    step_fn = jax.jit(compat.shard_map(
        local_step, mesh=mesh,
        in_specs=(p_manual, opt_manual, batch_spec, P()),
        out_specs=(p_manual, opt_manual, P()),
        axis_names=set(manual), check_vma=False),
        donate_argnums=(0, 1))  # params/opt update in place

    return StepArtifacts(
        step_fn=step_fn, param_specs=specs, param_shard_specs=p_manual,
        opt_specs=opt_manual, batch_spec=batch_spec,
        n_vote_replicas=n_votes, vote_axes=vote_axes,
        fused_leaves=fused_leaves, vote_strategy=resolved,
        codec=codec_name, plan=plan)


# ---------------------------------------------------------------------------
# state construction
# ---------------------------------------------------------------------------


def abstract_state(cfg: ModelConfig, tcfg: TrainConfig, art: StepArtifacts,
                   mesh=None) -> Tuple[Any, Any]:
    """ShapeDtypeStructs of (params, opt_state) with full shardings attached
    (for the dry-run lowering: no allocation ever happens)."""
    opt_cfg = tcfg.optimizer
    per_worker = (opt_cfg.kind in ("signum_vote", "signsgd_vote")
                  and opt_cfg.momentum_mode == MomentumMode.PER_WORKER
                  and opt_cfg.momentum > 0)
    dt = jnp.dtype(cfg.dtype)
    shapes = cfg.param_shapes()

    def mk(shape, dtype, spec):
        if mesh is None:
            return jax.ShapeDtypeStruct(shape, dtype)
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, spec))

    params = {k: mk(v, dt, art.param_specs[k]) for k, v in shapes.items()}

    mom_dt = jnp.dtype(opt_cfg.momentum_dtype)
    opt_state: Dict[str, Any] = {"count": mk((), jnp.int32, P())}
    is_sign = opt_cfg.kind in ("signum_vote", "signsgd_vote")
    needs_mom = (opt_cfg.momentum > 0
                 and opt_cfg.kind in ("signum_vote", "signsgd_vote", "sgdm",
                                      "adam"))

    def momentum_like(names=None):
        keep = shapes if names is None else {k: shapes[k] for k in names}
        if per_worker:
            return {k: mk((art.n_vote_replicas,) + v, mom_dt,
                          P(art.vote_axes or None, *art.param_specs[k]))
                    for k, v in keep.items()}
        return {k: mk(v, mom_dt, art.param_specs[k])
                for k, v in keep.items()}

    if is_sign and needs_mom:
        opt_state["momentum"] = momentum_like()
    if is_sign and opt_cfg.delayed_vote:
        # one-round vote buffer (§11): leaf-shaped int8, param sharding
        # (replicated over the vote axes — every replica applies the
        # same previous decision); refit_tree_leading_axis passes it
        # through unchanged at elastic events (no leading voter axis)
        opt_state["delayed"] = {k: mk(v, jnp.int8, art.param_specs[k])
                                for k, v in shapes.items()}
    if is_sign:
        from repro.core import codecs as codecs_mod
        codec = codecs_mod.get_codec(opt_cfg.resolved_codec)
        if art.plan is not None:   # per-leaf codecs come from the plan (§9)
            ef_names = art.plan.worker_state_leaves
            if ef_names:   # EF residual: momentum-shaped, mapped leaves only
                opt_state["error"] = momentum_like(ef_names)
            if art.plan.has_server_state:
                opt_state["codec"] = {
                    "flip_ema": mk((art.n_vote_replicas,), jnp.float32, P())}
        else:
            if codec.worker_state:   # EF residual: momentum-shaped (§8)
                opt_state["error"] = momentum_like()
            if codec.server_state:   # decode memory: replicated (M,) vector
                opt_state["codec"] = {
                    "flip_ema": mk((art.n_vote_replicas,), jnp.float32, P())}
    if opt_cfg.kind in ("sgdm", "adam"):
        opt_state["m"] = {k: mk(v, jnp.float32, art.param_specs[k])
                          for k, v in shapes.items()}
        if opt_cfg.kind == "adam":
            opt_state["v"] = dict(opt_state["m"])
    return params, opt_state


def materialize_state(cfg: ModelConfig, tcfg: TrainConfig,
                      art: StepArtifacts, key: jax.Array, mesh=None
                      ) -> Tuple[Any, Any]:
    """Concrete (params, opt_state) placed per the full shardings."""
    p_abs, o_abs = abstract_state(cfg, tcfg, art, mesh)

    def init_fn(k):
        params = M.init_params(cfg, k)
        opt = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), o_abs)
        return params, opt

    if mesh is None:
        return jax.jit(init_fn)(key)
    shardings = jax.tree.map(lambda s: s.sharding, (p_abs, o_abs))
    return jax.jit(init_fn, out_shardings=shardings)(key)
