"""Fault-tolerance machinery: stragglers, elastic rescale, watchdog.

The paper's claim (§3.4) is that majority vote *is* the fault-tolerance
mechanism: any bounded-influence failure (stale vote, random bits, crash,
adversary) is just another ≤1-vote perturbation, covered by Theorem 2 up
to 50% bad replicas. This module supplies the runtime plumbing around
that property:

* ``simulate_stragglers`` — stale-vote substitution: a replica that misses
  the step deadline contributes its *previous* sign vector instead of
  blocking the step (synchronous step, no tail latency). In-JAX, used by
  tests/benchmarks to quantify convergence vs fraction-stale.
* ``ElasticPlan`` — host-side logic mapping a surviving device set to a
  new mesh and instructing the checkpoint reshard (vote semantics depend
  only on the replica *count*, so DP rescale is transparent; Mode A
  momenta are truncated / zero-padded by checkpoint.restore).
* ``Watchdog`` — wall-clock supervision of the train loop; on a stuck
  step (collective hang after a node failure) it triggers the
  restore-and-rescale path in launch/train.py.
* ``vote_with_failures`` (+ the codec/plan variants) — DEPRECATED shims
  over the vote API (DESIGN.md §10): the failure composition is now DATA
  on a :class:`~repro.core.vote_api.VoteRequest`
  (:class:`~repro.core.vote_api.FailureSpec`), executed by the same
  backend the trainer steps through — robustness experiments measure the
  production wire protocol, not a lookalike.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from fractions import Fraction
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro import compat


# ---------------------------------------------------------------------------
# straggler mitigation (stale-vote substitution)
# ---------------------------------------------------------------------------


def simulate_stragglers(signs: jax.Array, prev_signs: jax.Array,
                        straggler_mask: jax.Array) -> jax.Array:
    """Elementwise: replicas flagged in `straggler_mask` (scalar bool per
    replica, e.g. from axis_index comparisons) vote with last step's signs."""
    return jnp.where(straggler_mask, prev_signs, signs)


def straggler_mask_for(axis_names: Sequence[str], n_stale: int,
                       like=None) -> jax.Array:
    """First `n_stale` replicas along the vote axes are stale this step.
    `like` is accepted and unused."""
    from repro.core.byzantine import replica_index
    return replica_index(axis_names, like=like) < n_stale


def count_for_fraction(fraction: float, n_replicas: int) -> int:
    """Replicas a fraction maps to, with explicit half-up rounding so the
    boundary regimes land where the paper's figures put them (0.5 of 16
    -> 8, i.e. *exactly* 50% — the tie regime DESIGN.md §7 pins).

    The product is taken in exact rational arithmetic (the float value
    of ``fraction`` is honored bit-for-bit): at federated-scale
    populations ``int(fraction * n + 0.5)`` accumulates float error and
    can land one replica off the half-up boundary.
    """
    if not 0.0 <= fraction <= 1.0:
        raise ValueError(f"fraction {fraction} outside [0, 1]")
    half_up = int(Fraction(fraction) * n_replicas + Fraction(1, 2))
    return min(n_replicas, half_up)


def _failure_request(engine, payload, prev_signs, n_stale, step,
                     server_state=None, plan=None):
    """The legacy (engine, stale, adversary) triple as one declarative
    :class:`~repro.core.vote_api.VoteRequest` (prev-less calls keep the
    historical no-substitution semantics)."""
    from repro.core import vote_api as va
    return va.VoteRequest(
        payload=payload, form="leaf", strategy=engine.strategy,
        codec=engine.codec, plan=plan,
        failures=va.FailureSpec(
            n_stale=n_stale if prev_signs is not None else 0,
            byz=engine.byz),
        prev=prev_signs, step=step, salt=engine.salt,
        server_state=server_state)


def vote_with_failures(engine, signs: jax.Array,
                       prev_signs: Optional[jax.Array] = None,
                       n_stale: int = 0, step=None) -> jax.Array:
    """DEPRECATED shim: one aggregation under failures — stale-vote
    substitution, then the engine's compiled adversary, then the wire —
    now a :class:`~repro.core.vote_api.VoteRequest` with a
    :class:`~repro.core.vote_api.FailureSpec`, executed on the mesh
    backend."""
    from repro.core import vote_api as va
    va.warn_legacy("fault_tolerance.vote_with_failures")
    return va.MeshBackend(axes=engine.axes).execute(
        _failure_request(engine, signs, prev_signs, n_stale, step)).votes


def codec_vote_with_failures(engine, signs: jax.Array,
                             prev_signs: Optional[jax.Array] = None,
                             n_stale: int = 0, step=None,
                             server_state=None):
    """DEPRECATED shim: codec-aware :func:`vote_with_failures`; returns
    ``(vote, new_server_state)``."""
    from repro.core import vote_api as va
    va.warn_legacy("fault_tolerance.codec_vote_with_failures")
    out = va.MeshBackend(axes=engine.axes).execute(
        _failure_request(engine, signs, prev_signs, n_stale, step,
                         server_state))
    return out.votes, out.server_state


def plan_vote_with_failures(engine, plan, values: jax.Array,
                            prev_signs: Optional[jax.Array] = None,
                            n_stale: int = 0, step=None,
                            server_state=None):
    """DEPRECATED shim: bucketed :func:`vote_with_failures` (DESIGN.md
    §9) — the same failure composition applied once to the flat wire
    buffer, then the plan's bucket schedule; returns
    ``(vote, new_server_state)``."""
    from repro.core import vote_api as va
    va.warn_legacy("fault_tolerance.plan_vote_with_failures")
    out = va.MeshBackend(axes=engine.axes).execute(
        _failure_request(engine, values, prev_signs, n_stale, step,
                         server_state, plan=plan))
    return out.votes, out.server_state


# ---------------------------------------------------------------------------
# elastic rescale
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ElasticPlan:
    """Mapping from a failure event to the survivor configuration."""

    old_shape: Tuple[int, ...]
    old_axes: Tuple[str, ...]
    new_shape: Tuple[int, ...]
    new_axes: Tuple[str, ...]
    note: str

    @property
    def new_replicas(self) -> int:
        n = 1
        for a, s in zip(self.new_axes, self.new_shape):
            if a in ("pod", "data"):
                n *= s
        return n


def plan_rescale(old_shape: Tuple[int, ...], old_axes: Tuple[str, ...],
                 surviving_devices: int) -> ElasticPlan:
    """Choose the survivor mesh after losing devices.

    Policy: keep the 'model' axis intact (TP degree is baked into layouts
    and kernels); shrink 'data' (and drop 'pod' if a whole pod died) to the
    largest power-of-two fit. The majority vote is indifferent to the DP
    width — Theorem 2's M simply decreases.
    """
    sizes = dict(zip(old_axes, old_shape))
    model = sizes.get("model", 1)
    if surviving_devices < model:
        raise ValueError(
            f"cannot keep TP degree {model} with {surviving_devices} devices")
    avail_dp = surviving_devices // model
    new_dp = 1
    while new_dp * 2 <= avail_dp:
        new_dp *= 2
    if "pod" in sizes and new_dp >= sizes["data"]:
        pods = new_dp // sizes["data"]
        return ElasticPlan(old_shape, old_axes,
                           (pods, sizes["data"], model),
                           ("pod", "data", "model"),
                           f"kept {pods} pod(s), data={sizes['data']}")
    return ElasticPlan(old_shape, old_axes, (new_dp, model),
                       ("data", "model"),
                       f"flattened to data={new_dp}, model={model}")


def make_mesh_from_plan(plan: ElasticPlan):
    return compat.make_mesh(
        plan.new_shape, plan.new_axes,
        axis_types=(compat.AxisType.Auto,) * len(plan.new_shape))


# ---------------------------------------------------------------------------
# watchdog
# ---------------------------------------------------------------------------


class Watchdog:
    """Detects a stuck step (e.g. a collective hanging on a dead peer) and
    invokes `on_timeout`. Use as a context manager around blocking work."""

    def __init__(self, timeout_s: float,
                 on_timeout: Optional[Callable[[], None]] = None):
        self.timeout_s = timeout_s
        self.on_timeout = on_timeout
        self.fired = False
        self._timer: Optional[threading.Timer] = None

    def _fire(self):
        self.fired = True
        if self.on_timeout is not None:
            self.on_timeout()

    def __enter__(self):
        self._timer = threading.Timer(self.timeout_s, self._fire)
        self._timer.daemon = True
        self._timer.start()
        return self

    def __exit__(self, *exc):
        if self._timer is not None:
            self._timer.cancel()
        return False
