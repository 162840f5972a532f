"""Byzantine adversary models (paper §3.4, Fig. 4; DESIGN.md §7).

Transforms are jit-compatible and applied between local sign computation
and the vote, so they compose with every vote strategy — including the
fused vote-in-backward path — and with stale-vote straggler substitution
(``distributed.fault_tolerance``): a straggling adversary perturbs its
*stale* vector, not a fresh one.

Modes
  sign_flip  — send the negation (the paper's strongest non-cooperating
               adversary)
  random     — send random ±1 (corrupted-worker model)
  zero       — abstain every step (crashed/mute worker)
  colluding  — every adversary sends the SAME pseudo-random target
               direction (coordinated attack: a colluding coalition gets
               its full weight behind one direction instead of cancelling
               itself; Mengoli et al. 2025's coordinated model)
  blind      — flip each honest coordinate independently with probability
               ``flip_prob`` per step (Akoun & Meyer 2022's stochastic
               blind adversary; ``flip_prob=1`` degenerates to sign_flip,
               ``flip_prob=0.5`` to random)
  none       — honest

The per-replica transform lives in :func:`evil_signs`, keyed on an
*explicit* replica index — the mesh path (:func:`apply_adversary`) derives
that index from the vote axes via ``compat.axis_index``, while the
Scenario Lab's virtual mesh (:func:`apply_adversary_stacked`) vmaps it
over a stacked voter dimension. Both paths derive PRNG keys through
:func:`adversary_key` (seed + salt, folded with replica index and step),
so a ``random``/``blind``/``colluding`` adversary sends bit-identical
vectors no matter how many hosts or devices replay the scenario.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from repro import compat
from repro.configs.base import ByzantineConfig

#: modes where the adversary's vector depends on PRNG draws (and therefore
#: on the seed/salt/step key discipline)
STOCHASTIC_MODES = ("random", "colluding", "blind")
MODES = ("none", "sign_flip", "random", "zero", "colluding", "blind")


def replica_index(axis_names: Sequence[str], like=None) -> jax.Array:
    """Linear index of this replica over the (manual) vote axes.
    `like` is accepted and unused."""
    idx = jnp.int32(0)
    for name in axis_names:
        idx = idx * compat.axis_size(name) + compat.axis_index(name,
                                                               like=like)
    return idx


def adversary_key(cfg: ByzantineConfig, idx: Optional[jax.Array] = None, *,
                  step: Optional[jax.Array] = None, salt: int = 0
                  ) -> jax.Array:
    """The PRNG key a stochastic adversary draws from.

    ``PRNGKey(seed + salt)`` folded with the replica index (omitted for
    colluding adversaries, whose draw must be shared) and the step. The
    key depends only on *logical* identifiers — replica index within the
    vote, scenario salt, step — never on device placement, which is what
    makes adversarial runs reproducible across host counts (DESIGN.md §7).
    """
    key = jax.random.PRNGKey(cfg.seed + salt)
    if idx is not None:
        key = jax.random.fold_in(key, idx)
    if step is not None:
        key = jax.random.fold_in(key, step)
    return key


def evil_signs(signs: jax.Array, cfg: ByzantineConfig, idx: jax.Array, *,
               step: Optional[jax.Array] = None, salt: int = 0,
               obs=None) -> jax.Array:
    """What replica `idx` would send if it were adversarial.

    `signs` is the replica's honest int8 sign tensor; the result has the
    same shape/dtype. Pure function of (signs, cfg, idx, step, salt) —
    plus, for the adaptive modes dispatched to ``repro.core.attacks``,
    the observation dict ``obs`` (DESIGN.md §15).
    """
    if cfg.mode in ("adaptive_flip", "low_margin", "reputation"):
        # lazy: evil_signs is called at trace time only, and attacks
        # imports this module at top level
        from repro.core.attacks import engine as _attacks
        return _attacks.adaptive_evil_signs(signs, cfg, idx, obs,
                                            step=step, salt=salt)
    if cfg.mode == "sign_flip":
        return -signs
    if cfg.mode == "zero":
        return jnp.zeros_like(signs)
    if cfg.mode == "random":
        rnd = jax.random.bernoulli(
            adversary_key(cfg, idx, step=step, salt=salt), 0.5, signs.shape)
        return jnp.where(rnd, jnp.int8(1), jnp.int8(-1))
    if cfg.mode == "colluding":
        # one shared target direction: the key folds step but NOT idx, so
        # every adversary draws the same vector and the coalition's full
        # weight lands on one direction instead of cancelling itself
        rnd = jax.random.bernoulli(
            adversary_key(cfg, None, step=step, salt=salt), 0.5, signs.shape)
        return jnp.where(rnd, jnp.int8(1), jnp.int8(-1))
    if cfg.mode == "blind":
        # flip each honest coordinate with prob flip_prob; abstentions
        # (sign 0) stay abstentions — a blind adversary corrupts what it
        # sends, it cannot invent votes it does not have
        flip = jax.random.bernoulli(
            adversary_key(cfg, idx, step=step, salt=salt),
            cfg.flip_prob, signs.shape)
        return jnp.where(flip, -signs, signs)
    raise ValueError(f"unknown byzantine mode {cfg.mode!r}")


def apply_adversary(signs: jax.Array, cfg: ByzantineConfig,
                    axis_names: Sequence[str], *,
                    step: jax.Array | None = None,
                    salt: int = 0, obs=None) -> jax.Array:
    """Transform this replica's int8 sign tensor per the adversary model
    (mesh path: the replica index comes from the vote axes).

    Replicas with linear index < cfg.num_adversaries act adversarially
    (which replicas are adversarial is immaterial to the vote — only the
    count matters, Theorem 2).
    """
    if cfg.mode == "none" or cfg.num_adversaries == 0:
        return signs
    idx = replica_index(axis_names, like=signs)
    evil = evil_signs(signs, cfg, idx, step=step, salt=salt, obs=obs)
    return jnp.where(idx < cfg.num_adversaries, evil, signs)


def apply_adversary_stacked(stacked: jax.Array, cfg: ByzantineConfig, *,
                            step: Optional[jax.Array] = None,
                            salt: int = 0,
                            ids: Optional[jax.Array] = None,
                            obs=None) -> jax.Array:
    """The same transform over a stacked (M, ...) voter tensor (virtual
    mesh path: replica index = position along the leading dim).
    Bit-identical to `apply_adversary` run on M mesh replicas (asserted
    by tests/tier2/scenario_harness.py).

    ``ids`` overrides the per-row replica index with *logical* voter
    identities (int32, shape (M,)): a client-sampled or chunk-streamed
    round materializes only some rows of the population, but each row's
    adversary predicate (`id < num_adversaries`) and PRNG stream
    (:func:`adversary_key` folds the id) must depend on who the voter
    IS, not where its row landed — the same client draws the same evil
    vector regardless of sampling or chunking. Default (`None`) keeps
    the historical row-position indexing.
    """
    if cfg.mode == "none" or cfg.num_adversaries == 0:
        return stacked
    m = stacked.shape[0]
    idx = (jnp.arange(m, dtype=jnp.int32) if ids is None
           else jnp.asarray(ids).astype(jnp.int32))
    evil = jax.vmap(
        lambda s, i: evil_signs(s, cfg, i, step=step, salt=salt,
                                obs=obs))(stacked, idx)
    is_adv = (idx < cfg.num_adversaries).reshape(
        (m,) + (1,) * (stacked.ndim - 1))
    return jnp.where(is_adv, evil, stacked)
