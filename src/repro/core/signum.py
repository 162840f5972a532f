"""SIGNUM / signSGD with majority vote — the paper's Algorithm 1 — plus the
dense baselines it is benchmarked against (distributed SGD/SGDM/Adam).

Optimizers are (init, update) pairs operating on *replica-local* trees;
they are called inside the manual-axes shard_map built by
``train/train_step.py``. Cross-replica aggregation is explicit:

* Mode A (``signum_vote``, paper-faithful): each replica keeps its own
  momentum ``v_m = beta*v_m + (1-beta)*g_m``; the vote aggregates
  ``sign(v_m)`` (Algorithm 1 line-for-line). The trainer stores the
  momentum with a leading vote-axis so every replica owns a distinct
  buffer.
* Mode B (``signsgd_vote``, DESIGN.md §3): replicas vote on ``sign(g_m)``
  (= Algorithm 1 with beta=0); momentum applies to the *voted* sign and is
  shardable like the params. When the fused ZeRO path is active the FSDP
  leaves arrive **already voted** by the backward reduce-scatter
  (``voted_leaves``), so only the small replicated leaves vote here.

Update rule (both modes): ``x <- x - eta * (vote + weight_decay * x)``.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ByzantineConfig, MomentumMode, OptimizerConfig
from repro.core import codecs as codecs_mod
from repro.core import sign_compress as sc
from repro.core import vote_api as va
from repro.core import vote_plan as vp
from repro.core.majority_vote import tree_mean


@dataclasses.dataclass(frozen=True)
class Optimizer:
    init: Callable
    update: Callable  # (grads, state, params, step) -> (params, state, diag)


def lr_at(cfg: OptimizerConfig, step: jax.Array) -> jax.Array:
    lr = jnp.float32(cfg.learning_rate)
    if cfg.warmup_steps:
        warm = jnp.minimum(step / cfg.warmup_steps, 1.0)
        lr = lr * warm
    if cfg.total_steps:
        frac = jnp.clip((step - cfg.warmup_steps)
                        / max(cfg.total_steps - cfg.warmup_steps, 1), 0.0, 1.0)
        lr = lr * 0.5 * (1 + jnp.cos(jnp.pi * frac))
    return lr


def _split(tree: Dict, names: Sequence[str]) -> Tuple[Dict, Dict]:
    a = {k: v for k, v in tree.items() if k in names}
    b = {k: v for k, v in tree.items() if k not in names}
    return a, b


# (The vote_margin / vote_agreement diagnostics moved into the vote API:
# they arrive on the VoteOutcome's WireReport, computed once per vote —
# DESIGN.md §10.)


# ---------------------------------------------------------------------------
# the paper's optimizer family
# ---------------------------------------------------------------------------


def make_sign_optimizer(cfg: OptimizerConfig, axes: Sequence[str],
                        byz: Optional[ByzantineConfig] = None,
                        voted_leaves: Sequence[str] = (),
                        diagnostics: bool = False,
                        n_vote_replicas: int = 1,
                        plan: Optional[vp.VotePlan] = None) -> Optimizer:
    """SIGNUM/signSGD with majority vote.

    `axes`: manual mesh axes the vote runs over.
    `voted_leaves`: param names whose gradients arrive pre-voted via the
    fused ZeRO backward (Mode B only).
    `n_vote_replicas`: static voter count (sizes the server-stateful
    codecs' decode memory; 1 in the single-process degenerate case).
    `plan`: optional :class:`~repro.core.vote_plan.VotePlan` (§9) — the
    explicitly-voted leaves go to the wire as one flat bucketed buffer
    instead of leaf by leaf; per-leaf codecs come from the plan's map.

    The wire is codec-parametric (DESIGN.md §8): `cfg.resolved_codec`
    selects what goes on it. Worker-side codec memory (the EF residual)
    lives under ``state["error"]`` — per-worker under Mode A, so it
    refits across elastic rescale like the momentum (§6); server-side
    decode memory (the weighted vote's reliability estimates) lives under
    ``state["codec"]``, replicated. Under a plan with a codec map the
    residual tree holds ONLY the leaves mapped to a worker-state codec.
    """
    beta = cfg.momentum
    mode = cfg.momentum_mode
    mom_dtype = jnp.dtype(cfg.momentum_dtype)
    codec = codecs_mod.get_codec(cfg.resolved_codec)
    ef_leaves = (plan.worker_state_leaves if plan is not None
                 else None)   # None = legacy single-codec rule
    ef = (bool(ef_leaves) if plan is not None else codec.worker_state)
    server_state = (plan.has_server_state if plan is not None
                    else codec.server_state)
    if ef and mode != MomentumMode.PER_WORKER:
        # Mode B votes on raw gradient signs and keeps momentum on the
        # vote — there is no per-worker encode input for a residual to
        # fold into. Rejecting the combination beats silently training
        # as sign1bit with a dead momentum-sized error tree.
        raise ValueError(
            f"codec {codec.name if plan is None else ef_leaves!r} carries "
            "a per-worker EF residual and requires "
            "momentum_mode=per_worker (Mode A); Mode B has no "
            "worker-side encode input (DESIGN.md §3/§8)")

    leaf_codec_names = plan.leaf_codecs() if plan is not None else None

    def _leaf_codec(name: str):
        if leaf_codec_names is None:
            return codec
        return codecs_mod.get_codec(leaf_codec_names[name])

    def init(params):
        state = {"count": jnp.zeros((), jnp.int32)}
        if beta > 0 or mode == MomentumMode.GLOBAL:
            state["momentum"] = jax.tree.map(
                lambda p: jnp.zeros(p.shape, mom_dtype), params)
        if cfg.delayed_vote:
            # one-round vote buffer (DESIGN.md §11): step t applies the
            # majority voted at t-1. int8 ternary signs, replicated
            # (every replica applies the same previous decision); zeros
            # at step 0, so the first update is weight decay only.
            state["delayed"] = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.int8), params)
        if ef:
            state["error"] = {
                k: jnp.zeros(p.shape, mom_dtype) for k, p in params.items()
                if ef_leaves is None or k in ef_leaves}
        if server_state:
            state["codec"] = (plan.init_server_state(n_vote_replicas)
                              if plan is not None
                              else codec.init_server_state(n_vote_replicas))
        return state

    def encode(tree, err):
        # codec encode: fold each EF leaf's residual into the vote input
        # (identity for residual-free leaves/codecs)
        return {k: _leaf_codec(k).encode_leaf(v, err.get(k))
                for k, v in tree.items()}

    def feedback(encoded, votes, err):
        # codec feedback: residual vs the APPLIED vote, EF leaves only
        return {k: _leaf_codec(k).feedback_leaf(encoded[k], votes[k], e)
                for k, e in err.items()}

    backend = va.MeshBackend(axes=tuple(axes))

    def _vote(tree, step, cstate):
        """Dispatch the explicit vote through the declarative API: one
        VoteRequest whether the wire is the bucketed plan schedule or
        leaf-wise — margin/agreement come back on the WireReport,
        computed once (DESIGN.md §10)."""
        out = backend.execute(va.VoteRequest(
            payload=tree, form="tree", strategy=cfg.vote_strategy,
            codec=codec.name, plan=plan, failures=va.FailureSpec(byz=byz),
            step=step, server_state=cstate, diagnostics=diagnostics,
            overlap=cfg.overlap))
        diag = {}
        if diagnostics:
            diag["vote_agreement"] = out.wire.agreement
            diag["vote_margin"] = out.wire.margin
        return out.votes, out.server_state, diag

    def update(grads, state, params, step):
        eta = lr_at(cfg, step)
        diag = {}
        cstate = state.get("codec")
        if mode == MomentumMode.PER_WORKER:
            # --- Algorithm 1 verbatim ---
            if beta > 0:
                with jax.named_scope("sign_momentum"):
                    v = jax.tree.map(
                        lambda m, g: beta * m + (1 - beta)
                        * g.astype(mom_dtype), state["momentum"], grads)
                state = {**state, "momentum": v}
            else:
                v = grads
            if ef:
                v = encode(v, state["error"])
            votes, new_cstate, diag = _vote(v, step, cstate)
            if ef:
                state = {**state, "error": feedback(v, votes,
                                                    state["error"])}
            if server_state:
                state = {**state, "codec": new_cstate}
        else:
            # --- Mode B: vote on sign(g), momentum on the vote ---
            pre, raw = _split(grads, voted_leaves)
            if raw:
                raw_votes, new_cstate, diag = _vote(raw, step, cstate)
                if server_state:
                    state = {**state, "codec": new_cstate}
            else:
                raw_votes = {}
            votes = {**pre, **raw_votes}
            if diagnostics and not raw:
                # every leaf took the fused vote-in-backward path: the
                # wire is not observable here, but the metric keys are
                # a contract when diagnostics=True
                diag["vote_agreement"] = jnp.float32(jnp.nan)
                diag["vote_margin"] = jnp.float32(jnp.nan)
            if beta > 0:
                with jax.named_scope("sign_momentum"):
                    u = jax.tree.map(
                        lambda m, vt: beta * m + (1 - beta)
                        * vt.astype(mom_dtype), state["momentum"], votes)
                    votes = jax.tree.map(lambda x: jnp.sign(x), u)
                state = {**state, "momentum": u}
        if cfg.delayed_vote:
            # apply the PREVIOUS step's majority; bank this step's fresh
            # decision for t+1. EF feedback and the diagnostics above
            # observed the FRESH vote — only the parameter update lags.
            applied = state["delayed"]
            state = {**state, "delayed": jax.tree.map(sc.sign_ternary,
                                                      votes)}
        else:
            applied = votes

        def apply(p, vt):
            # barrier: without it XLA CSEs this f32 cast with the ZeRO
            # hook's gather operand and all-gathers params in fp32
            # (measured 2x wire + expert replication on qwen3-moe)
            p32 = jax.lax.optimization_barrier(p).astype(jnp.float32)
            upd = vt.astype(jnp.float32) + cfg.weight_decay * p32
            return (p32 - eta * upd).astype(p.dtype)

        with jax.named_scope("sign_update"):
            new_params = jax.tree.map(apply, params, applied)
        state = {**state, "count": state["count"] + 1}
        return new_params, state, diag

    return Optimizer(init, update)


# ---------------------------------------------------------------------------
# dense baselines (the paper's comparison arm)
# ---------------------------------------------------------------------------


def make_dense_optimizer(cfg: OptimizerConfig, axes: Sequence[str],
                         mean_leaves: Sequence[str] = ()) -> Optimizer:
    """Distributed SGD / SGDM / Adam with psum-mean gradient aggregation.

    `mean_leaves`: names already mean-reduced by the fused ZeRO backward.
    """
    kind = cfg.kind

    def init(params):
        state = {"count": jnp.zeros((), jnp.int32)}
        if kind in ("sgdm", "adam"):
            state["m"] = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
        if kind == "adam":
            state["v"] = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
        return state

    def update(grads, state, params, step):
        eta = lr_at(cfg, step)
        pre, raw = _split(grads, mean_leaves)
        raw = tree_mean(raw, axes) if raw else {}
        g = {**pre, **raw}
        g = jax.tree.map(lambda x: x.astype(jnp.float32), g)
        cnt = state["count"] + 1
        if kind == "sgd":
            upd = g
        elif kind == "sgdm":
            m = jax.tree.map(lambda m_, g_: cfg.momentum * m_ + g_,
                             state["m"], g)
            state = {**state, "m": m}
            upd = m
        elif kind == "adam":
            b1, b2 = cfg.momentum, cfg.beta2
            m = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_,
                             state["m"], g)
            v = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_,
                             state["v"], g)
            state = {**state, "m": m, "v": v}
            t = cnt.astype(jnp.float32)
            upd = jax.tree.map(
                lambda m_, v_: (m_ / (1 - b1 ** t))
                / (jnp.sqrt(v_ / (1 - b2 ** t)) + cfg.eps), m, v)
        else:
            raise ValueError(kind)
        new_params = jax.tree.map(
            lambda p, u: (p.astype(jnp.float32)
                          - eta * (u + cfg.weight_decay
                                   * p.astype(jnp.float32))).astype(p.dtype),
            params, upd)
        return new_params, {**state, "count": cnt}, {}

    return Optimizer(init, update)


def build_optimizer(cfg: OptimizerConfig, axes: Sequence[str],
                    byz: Optional[ByzantineConfig] = None,
                    fused_leaves: Sequence[str] = (),
                    diagnostics: bool = False,
                    n_vote_replicas: int = 1,
                    plan: Optional[vp.VotePlan] = None) -> Optimizer:
    if cfg.kind in ("signum_vote", "signsgd_vote"):
        return make_sign_optimizer(cfg, axes, byz, voted_leaves=fused_leaves,
                                   diagnostics=diagnostics,
                                   n_vote_replicas=n_vote_replicas,
                                   plan=plan)
    return make_dense_optimizer(cfg, axes, mean_leaves=fused_leaves)
