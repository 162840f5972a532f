"""The vote API (DESIGN.md §10): one declarative entry point for every
majority vote, on every backend.

Four PRs of growth multiplied the paper's single concept — workers send
sign vectors, the server returns a majority decision — into ~15
imperative entry points, one per point in the payload-form × codec ×
failure × backend grid. This module collapses that grid back into data:

* :class:`VoteRequest` **says what to vote on** — the payload (a
  replica-local leaf, a host-local stacked ``(M, n)`` buffer, or a tree
  of leaves), the wire (strategy or AUTO, codec, optional
  :class:`~repro.core.vote_plan.VotePlan` bucket schedule), the failure
  composition (:class:`FailureSpec`: stale-vote stragglers + the
  compiled Byzantine model), the PRNG discipline (``step``/``salt``),
  and the incoming server state.
* A :class:`VoteBackend` **executes it** — :class:`MeshBackend` drives
  the real collectives (inside a manual ``shard_map`` region for
  leaf/tree payloads, or by building the ``shard_map`` itself for
  stacked payloads, exactly like the Scenario Lab's mesh path);
  :class:`VirtualBackend` runs the same stage methods over a stacked
  voter dim with the exchange virtualised (host-count independent).
* :class:`VoteOutcome` **returns the decision** — votes in the
  payload's original form, the updated server state, and a
  :class:`WireReport` (bytes/messages/margin/agreement) computed once.

Requests are *validated at build time*: unsupported codec × strategy
combinations, missing server state, stale substitution without a
previous-signs source, or a payload that does not match its plan's
manifest are all rejected with actionable messages before any tracing
happens, and both backends see the identical request — which is how the
mesh == virtual bit-identity invariants are proven once instead of
per-variant.

Every legacy entry point (``VoteEngine.vote*``,
``fault_tolerance.*_vote_with_failures``, ``virtual_mesh.virtual_*``,
``vote_plan.plan_vote_signs``/``plan_tree_vote``) is now a deprecation
shim that builds a :class:`VoteRequest` and calls ``execute`` — see the
migration table in DESIGN.md §10.

This module is also the single home of the pack-width helpers
(:func:`pad_last`, :func:`count_dtype`) that ``vote_engine``,
``vote_plan`` and the virtual mesh used to carry as near-duplicates.
"""
from __future__ import annotations

import abc
import dataclasses
import functools
import warnings
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import compat
from repro.configs.base import ByzantineConfig, VoteStrategy
from repro.core import byzantine, sign_compress as sc
from repro.obs import recorder as obs

FORMS = ("leaf", "stacked", "tree", "streamed")
MESH_STYLES = ("data_model", "data_only")


# ---------------------------------------------------------------------------
# consolidated pack-width helpers (single source of truth; DESIGN.md §10)
# ---------------------------------------------------------------------------


def count_dtype(n_voters: int):
    """Narrowest signed integer that can hold a vote count of `n_voters`."""
    if n_voters <= 127:
        return jnp.int8
    if n_voters <= 32_767:
        return jnp.int16
    return jnp.int32


def count_bytes(n_voters: int) -> int:
    return jnp.dtype(count_dtype(n_voters)).itemsize


def pad_last(x: jax.Array, multiple: int) -> Tuple[jax.Array, int]:
    """Zero-pad the LAST dim to a multiple; returns (padded, original_n).

    This is THE padding helper — `vote_engine`,
    `vote_plan`, `sign_compress` and the virtual mesh all delegate here,
    so the wire's pad semantics cannot silently diverge per module."""
    n = x.shape[-1]
    return compat.pad_trailing(x, (-n) % multiple), n


# ---------------------------------------------------------------------------
# deprecation plumbing for the legacy entry points
# ---------------------------------------------------------------------------

_WARNED: set = set()


def warn_legacy(name: str, hint: str = "") -> None:
    """Emit ONE DeprecationWarning per legacy entry point per process
    (module-level once-guard): the shims stay usable in hot loops and
    old notebooks without drowning them in repeats."""
    if name in _WARNED:
        return
    _WARNED.add(name)
    warnings.warn(
        f"{name} is deprecated: build a repro.core.vote_api.VoteRequest "
        f"and call MeshBackend/VirtualBackend.execute() instead"
        + (f" ({hint})" if hint else "") + "; see DESIGN.md §10",
        DeprecationWarning, stacklevel=3)


# ---------------------------------------------------------------------------
# the request / outcome dataclasses
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class FailureSpec:
    """The failure composition applied in front of the wire, in the
    pinned order (DESIGN.md §7): stale-vote straggler substitution first
    (the first `n_stale` replicas vote with the request's ``prev``
    signs), THEN the compiled Byzantine model (`byz`) — so a straggling
    adversary corrupts its *stale* vector. Crashed/mute workers are the
    ``zero``-mode adversary (an abstention mask on the count wires)."""

    n_stale: int = 0
    byz: Optional[ByzantineConfig] = None

    def __post_init__(self):
        from repro.core.attacks.engine import ATTACK_MODES
        if self.n_stale < 0:
            raise ValueError(f"n_stale must be >= 0, got {self.n_stale}")
        if (self.byz is not None and self.byz.mode not in byzantine.MODES
                and self.byz.mode not in ATTACK_MODES):
            raise ValueError(f"unknown adversary mode {self.byz.mode!r}; "
                             f"have {byzantine.MODES} plus adaptive "
                             f"{ATTACK_MODES}")

    @property
    def active(self) -> bool:
        return self.n_stale > 0 or (self.byz is not None
                                    and self.byz.mode != "none")

    @property
    def adaptive(self) -> bool:
        """True when the adversary is one of the ``repro.core.attacks``
        modes, which additionally consume ``VoteRequest.attack_obs``."""
        from repro.core.attacks.engine import ATTACK_MODES
        return self.byz is not None and self.byz.mode in ATTACK_MODES


@dataclasses.dataclass(frozen=True)
class WireReport:
    """What one executed vote put on the wire — computed once, here,
    instead of re-derived per caller. `payload_bytes` is one replica's
    outbound payload (the paper's "bits sent"); `n_messages` counts the
    wire rounds (1 per leaf/flat vote, one per bucket under a plan);
    `strategy` is the resolved wire (None for a mixed-strategy plan or
    the M=1 no-wire degenerate case). `margin`/`agreement` are the §7
    diagnostics (traced scalars), present when the request asked for
    them."""

    n_voters: int
    payload_bytes: float
    n_messages: int
    strategy: Optional[VoteStrategy]
    margin: Optional[jax.Array] = None
    agreement: Optional[jax.Array] = None


@dataclasses.dataclass(frozen=True)
class VoteOutcome:
    """votes in the payload's original form + updated server state + the
    wire report.

    ``wire_signs`` is the (M, n) int8 sign tensor that actually reached
    the wire (sign extraction -> stale substitution -> adversary, the
    pinned §7 order) — populated by the dense VirtualBackend path so
    trace capture observes exactly what was voted instead of recomputing
    the failure composition (and re-drawing the adversary PRNG) outside
    ``execute()``. ``None`` on the mesh path (the stack never exists on
    one host), the fused-kernel path (the kernel consumes raw values),
    and the streamed path (never materialized by design).

    ``counts`` is the per-coordinate signed tally ((n,) integer array,
    at the wire's own weight scale) — populated by the streamed path,
    where it feeds the attack engine's ``margin`` observation channel
    (DESIGN.md §15) without re-walking the stream; the stack never
    being materialized means no caller can recompute it after the
    fact. ``None`` elsewhere (dense callers tally ``wire_signs``)."""

    votes: Any
    server_state: Dict[str, Any]
    wire: WireReport
    wire_signs: Any = None
    counts: Any = None


@dataclasses.dataclass(frozen=True, eq=False, repr=False)
class PopulationStream:
    """A voter population yielded in chunks instead of materialized as
    one dense (M, n) stack — the ``"streamed"`` request form (DESIGN.md
    §12). The engine calls ``values`` (and ``prev``, when stale
    substitution is requested) with int32 chunks of **logical voter
    ids** and never holds more than ``chunk_size`` rows at once, so M
    decouples from both host memory and device count.

    * ``values``  — callable, (k,) int32 logical ids -> (k, n_coords)
      real values (the sampled clients' gradients). Must be a pure
      function of the ids so chunking cannot change the vote.
    * ``ids``     — optional (n_voters,) strictly-increasing non-negative
      logical indices (a client-sampled round); default = arange
      (full participation). Adversary/stale predicates and PRNG streams
      key on these ids, not row positions.
    * ``prev``    — optional callable, same contract as ``values``,
      returning (k, n_coords) int8 prev signs for stale substitution.
    * ``weights`` — optional (n_voters,) positive int dataset sizes
      aligned to ``ids``: each client casts weight-many votes
      (FedAvg-style dataset weighting, composing with the
      ``weighted_vote`` codec's reliability weights).
    """

    n_voters: int
    n_coords: int
    values: Any
    ids: Any = None
    prev: Any = None
    weights: Any = None

    def __post_init__(self):
        if self.n_voters < 1:
            raise ValueError(f"n_voters must be >= 1, got {self.n_voters}")
        if self.n_coords < 1:
            raise ValueError(f"n_coords must be >= 1, got {self.n_coords}")
        if not callable(self.values):
            raise ValueError("values must be a callable (ids) -> (k, n) "
                             f"chunk producer, got "
                             f"{type(self.values).__name__}")
        if self.prev is not None and not callable(self.prev):
            raise ValueError("prev must be a callable (ids) -> (k, n) "
                             "int8 chunk producer (same contract as "
                             f"values), got {type(self.prev).__name__}")
        if self.ids is not None:
            ids = np.asarray(self.ids)
            if ids.shape != (self.n_voters,):
                raise ValueError(f"ids must have shape ({self.n_voters},) "
                                 f"aligned to the stream rows, got "
                                 f"{ids.shape}")
            if not np.issubdtype(ids.dtype, np.integer):
                raise ValueError(f"ids must be integer logical indices, "
                                 f"got dtype {ids.dtype}")
            if ids.size and (int(ids.min()) < 0
                             or np.any(np.diff(ids) <= 0)):
                raise ValueError("ids must be strictly increasing "
                                 "non-negative logical voter indices "
                                 "(sort the sampled set)")
        if self.weights is not None:
            w = np.asarray(self.weights)
            if w.shape != (self.n_voters,):
                raise ValueError(f"weights must have shape "
                                 f"({self.n_voters},) aligned to the "
                                 f"stream rows, got {w.shape}")
            if not np.issubdtype(w.dtype, np.integer):
                raise ValueError("weights are integer vote counts "
                                 "(dataset sizes), got dtype "
                                 f"{w.dtype}")
            if w.size and int(w.min()) < 1:
                raise ValueError("weights must be >= 1 (a zero-data "
                                 "client does not vote; drop it from "
                                 "the sample instead)")

    def row_ids(self) -> np.ndarray:
        """The logical id of every stream row, materialized ((M,) int32)."""
        if self.ids is None:
            return np.arange(self.n_voters, dtype=np.int32)
        return np.asarray(self.ids, dtype=np.int32)


@dataclasses.dataclass(frozen=True, eq=False, repr=False)
class VoteRequest:
    """One declarative vote. Validated on construction — an invalid
    request never reaches a backend, and both backends reject the same
    requests with the same error class.

    `payload` + `form`:
      * ``"leaf"``    — one replica-local tensor ``(..., n)`` inside a
        manual mesh region (real values or int8 signs; signs are a fixed
        point of the sign extraction).
      * ``"stacked"`` — a host-local ``(M, n)`` buffer of all M voters'
        values (the Scenario Lab / benchmark form).
      * ``"tree"``    — a dict of replica-local leaves (the trainer's
        form; votes come back leaf-shaped in each leaf's dtype).
      * ``"streamed"`` — a :class:`PopulationStream` yielding voter
        chunks on demand (the federated-population form, DESIGN.md §12;
        VirtualBackend only — never materializes the (M, n) stack).

    `strategy` may be ``AUTO`` (resolved against the comm cost model,
    codec-aware); `plan` switches execution to the §9 bucket schedule
    (whose per-group codecs/strategies then supersede `codec`/
    `strategy`); `overlap` selects the double-buffered schedule walk
    (§11: bucket k's exchange issued while bucket k-1 tallies — needs a
    plan, bit-identical to the synchronous walk); `failures` composes
    stale substitution (needs `prev`) and the Byzantine model;
    `step`/`salt` feed the adversary PRNG discipline; `server_state`
    threads stateful codecs' decode memory; `diagnostics` (tree form
    only) asks for margin/agreement in the :class:`WireReport`.

    `voter_ids`/`weights` annotate a **stacked** payload with logical
    voter identities / integer dataset-size vote multiplicities — the
    dense twin of the streamed form's :class:`PopulationStream` axes
    (VirtualBackend only; the mesh's voters are physical replicas). A
    streamed request carries both on the stream instead.

    `attack_obs` is the adaptive adversary's observation dict
    (DESIGN.md §15): required exactly when ``failures.byz`` is one of
    the ``repro.core.attacks`` modes, validated against the mode's
    channel (``attacks.CHANNEL_KEYS``) so an attacker never sees more
    of the :class:`~repro.core.attacks.AttackState` than its channel
    grants. Build it with ``AttackState.observation(channel)``."""

    payload: Any
    form: str = "leaf"
    strategy: VoteStrategy = VoteStrategy.AUTO
    codec: str = "sign1bit"
    plan: Optional[Any] = None            # core.vote_plan.VotePlan
    failures: FailureSpec = FailureSpec()
    prev: Any = None
    step: Any = None
    salt: int = 0
    server_state: Optional[Dict[str, Any]] = None
    diagnostics: bool = False
    overlap: bool = False
    voter_ids: Any = None
    weights: Any = None
    attack_obs: Any = None

    # ---- build-time validation -----------------------------------------

    def __post_init__(self):
        from repro.core import codecs as codecs_mod
        if self.form not in FORMS:
            raise ValueError(f"unknown payload form {self.form!r}; "
                             f"have {FORMS}")
        codec = codecs_mod.get_codec(self.codec)     # raises on unknown
        if not isinstance(self.strategy, VoteStrategy):
            raise ValueError(f"strategy must be a VoteStrategy, got "
                             f"{self.strategy!r}")
        if self.plan is None and self.strategy != VoteStrategy.AUTO:
            codec.validate_strategy(self.strategy)
        if self.form == "tree":
            if not isinstance(self.payload, dict) or not self.payload:
                raise ValueError(
                    "tree-form payload must be a non-empty dict of "
                    f"leaves, got {type(self.payload).__name__}")
        elif self.form == "streamed":
            self._validate_streamed()
        else:
            if not hasattr(self.payload, "shape"):
                raise ValueError(
                    f"{self.form}-form payload must be an array, got "
                    f"{type(self.payload).__name__}")
            if self.form == "stacked" and len(self.payload.shape) != 2:
                raise ValueError(
                    "stacked-form payload must be (M, n) — M voters by n "
                    f"coordinates — got shape {tuple(self.payload.shape)}")
        if self.failures.n_stale > 0:
            has_prev = (self.payload.prev is not None
                        if self.form == "streamed" else
                        self.prev is not None)
            if not has_prev:
                raise ValueError(
                    f"failures.n_stale={self.failures.n_stale} substitutes "
                    "stale votes but the request has no prev signs to "
                    "substitute (set VoteRequest.prev"
                    + (" / PopulationStream.prev"
                       if self.form == "streamed" else "") + ")")
        self._validate_voter_axes()
        self._validate_attack_obs()
        self._validate_plan()
        # a stacked request always decodes through the codec (even M=1),
        # so missing server state is a build-time error there; leaf/tree
        # requests may execute in the no-axes M=1 degenerate case where
        # the vote is the local sign and no decode state is ever touched
        # (the legacy entry points allowed exactly that), so the backend
        # raises at execution instead when the region has vote axes
        needs_state = (self.plan.has_server_state if self.plan is not None
                       else codec.server_state)
        if (needs_state and not self.server_state
                and self.form in ("stacked", "streamed")):
            raise ValueError(
                f"codec {self.codec!r} (or the plan's codec map) keeps "
                "server-side decode state; thread it through "
                "VoteRequest.server_state (init_server_state for the "
                "uninformed prior)")
        if self.diagnostics and self.form != "tree":
            raise ValueError(
                "diagnostics (margin/agreement in the WireReport) are "
                "computed over a voted tree; leaf/stacked callers "
                "measure their own quantities (form="
                f"{self.form!r})")
        if self.overlap and self.plan is None:
            raise ValueError(
                "overlap=True double-buffers a plan's bucket schedule; "
                "attach a VotePlan (VoteRequest.plan / "
                "OptimizerConfig.bucket_bytes) or drop overlap")

    def _validate_streamed(self):
        if not isinstance(self.payload, PopulationStream):
            raise ValueError(
                "streamed-form payload must be a PopulationStream, got "
                f"{type(self.payload).__name__}")
        if self.plan is not None:
            raise ValueError(
                "the streamed population engine accumulates one flat "
                "coordinate buffer and has no bucket walk; drop the "
                "plan or use the stacked form")
        if self.overlap:
            raise ValueError(
                "overlap double-buffers a plan's bucket schedule; the "
                "streamed form has no plan to overlap")
        if self.prev is not None:
            raise ValueError(
                "a streamed request's prev signs are a chunk producer "
                "on the stream (PopulationStream.prev), not a dense "
                "VoteRequest.prev array")
        if self.voter_ids is not None or self.weights is not None:
            raise ValueError(
                "a streamed request carries voter ids and weights on "
                "the PopulationStream (ids=/weights=), not on the "
                "VoteRequest")

    def _validate_voter_axes(self):
        if self.voter_ids is None and self.weights is None:
            return
        if self.form != "stacked":
            raise ValueError(
                "voter_ids/weights annotate the rows of a stacked "
                f"(M, n) payload, not the {self.form!r} form (streamed "
                "requests carry them on the PopulationStream)")
        if self.plan is not None:
            raise ValueError(
                "voter_ids/weights do not compose with a bucketed plan "
                "yet; drop the plan (the population engine accumulates "
                "one flat buffer)")
        m = self.payload.shape[0]
        for name, arr in (("voter_ids", self.voter_ids),
                          ("weights", self.weights)):
            if arr is None:
                continue
            a = np.asarray(arr)
            if a.shape != (m,):
                raise ValueError(f"{name} must have shape ({m},) aligned "
                                 f"to the stacked rows, got {a.shape}")
            if not np.issubdtype(a.dtype, np.integer):
                raise ValueError(f"{name} must be an integer array, got "
                                 f"dtype {a.dtype}")
        if self.voter_ids is not None:
            ids = np.asarray(self.voter_ids)
            if ids.size and (int(ids.min()) < 0
                             or np.any(np.diff(ids) <= 0)):
                raise ValueError(
                    "voter_ids must be strictly increasing non-negative "
                    "logical voter indices (sort the sampled set)")
        if self.weights is not None:
            w = np.asarray(self.weights)
            if w.size and int(w.min()) < 1:
                raise ValueError(
                    "weights must be >= 1 (a zero-data client does not "
                    "vote; drop it from the sample instead)")

    def _validate_attack_obs(self):
        from repro.core.attacks import engine as attacks
        if not self.failures.adaptive:
            if self.attack_obs is not None:
                raise ValueError(
                    "attack_obs carries an adaptive adversary's "
                    "observation channel, but the request's adversary "
                    "mode is oblivious or absent — drop attack_obs or "
                    f"use one of the adaptive modes {attacks.ATTACK_MODES}")
            return
        byz = self.failures.byz
        if self.form not in ("stacked", "streamed"):
            raise ValueError(
                f"adaptive adversary mode {byz.mode!r} observes the "
                "previous round's flat broadcast vote; the "
                f"{self.form!r} form has no such observation channel "
                "(use the stacked or streamed form)")
        channel = attacks.MODE_CHANNEL[byz.mode]
        keys = attacks.CHANNEL_KEYS[channel]
        if (not isinstance(self.attack_obs, dict)
                or set(self.attack_obs) != set(keys)):
            got = (sorted(self.attack_obs) if isinstance(self.attack_obs,
                                                         dict)
                   else type(self.attack_obs).__name__)
            raise ValueError(
                f"adaptive mode {byz.mode!r} observes the {channel!r} "
                f"channel: attack_obs must be a dict with exactly the "
                f"keys {sorted(keys)} (AttackState.observation builds "
                f"it), got {got}")
        n = (self.payload.n_coords if self.form == "streamed"
             else self.payload.shape[1])
        for k in ("prev_vote", "prev_abs_counts"):
            if k in self.attack_obs:
                shape = tuple(np.shape(self.attack_obs[k]))
                if shape != (n,):
                    raise ValueError(
                        f"attack_obs[{k!r}] must have shape ({n},) "
                        f"aligned to the vote coordinates, got {shape}")
        if "rep" in self.attack_obs:
            shape = tuple(np.shape(self.attack_obs["rep"]))
            if self.form == "streamed":
                ids = self.payload.row_ids()
                need = int(ids[-1]) + 1 if ids.size else 1
            elif self.voter_ids is not None:
                ids = np.asarray(self.voter_ids)
                need = int(ids[-1]) + 1 if ids.size else 1
            else:
                need = self.payload.shape[0]
            if len(shape) != 1 or shape[0] < need:
                raise ValueError(
                    "attack_obs['rep'] must be a 1-D per-voter array "
                    f"covering every logical voter id (need >= {need} "
                    f"entries, got shape {shape}) — refit it on "
                    "rescale/churn like the flip-EMA "
                    "(AttackState.refit)")

    def _validate_plan(self):
        if self.plan is None:
            return
        plan = self.plan
        if self.form == "tree":
            names = {s.name for s in plan.leaves}
            keys = set(self.payload)
            if names != keys:
                raise ValueError(
                    "plan manifest and tree payload disagree: plan has "
                    f"{sorted(names - keys)} extra / misses "
                    f"{sorted(keys - names)}")
            for slot in plan.leaves:
                got = tuple(self.payload[slot.name].shape)
                if got != slot.shape:
                    raise ValueError(
                        f"leaf {slot.name!r} has shape {got}, plan "
                        f"manifest says {slot.shape}")
            return
        n = self.payload.shape[-1]
        if n != plan.n_params:
            raise ValueError(
                f"{self.form} payload has {n} coordinates, plan manifest "
                f"says {plan.n_params}")
        if self.form == "leaf" and len(self.payload.shape) != 1:
            raise ValueError(
                "a planned leaf payload is the flat (n_params,) buffer "
                f"in manifest order, got shape {tuple(self.payload.shape)}")

    def __repr__(self):  # payloads are arrays — keep the repr readable
        return (f"VoteRequest(form={self.form!r}, strategy="
                f"{self.strategy.value!r}, codec={self.codec!r}, "
                f"plan={'yes' if self.plan is not None else None}, "
                f"failures={self.failures}, salt={self.salt})")


# ---------------------------------------------------------------------------
# static wire accounting (the WireReport's bytes/messages half)
# ---------------------------------------------------------------------------


def _static_wire(plan, codec_name: str, resolved: Optional[VoteStrategy],
                 n_params: int, n_messages: int,
                 n_voters: int) -> WireReport:
    from repro.core import codecs as codecs_mod
    if plan is not None:
        payload = sum(
            g.total * codecs_mod.get_codec(g.codec).wire_bits(g.strategy)
            / 8.0 for g in plan.groups)
        strategies = {g.strategy for g in plan.groups}
        return WireReport(
            n_voters=n_voters, payload_bytes=payload,
            n_messages=plan.n_buckets,
            strategy=strategies.pop() if len(strategies) == 1 else None)
    if resolved is None or resolved == VoteStrategy.AUTO:
        # M=1 degenerate case: the vote is the local sign, no wire at all
        return WireReport(n_voters=n_voters, payload_bytes=0.0,
                          n_messages=0, strategy=None)
    c = codecs_mod.get_codec(codec_name)
    return WireReport(n_voters=n_voters,
                      payload_bytes=n_params * c.wire_bits(resolved) / 8.0,
                      n_messages=n_messages, strategy=resolved)


# ---------------------------------------------------------------------------
# in-region execution (absorbed from VoteEngine / fault_tolerance /
# vote_plan.plan_vote_signs — the mesh collectives path)
# ---------------------------------------------------------------------------


def _region_sizes(axes: Sequence[str]) -> Tuple[int, int]:
    data = compat.axis_size("data") if "data" in axes else 1
    pod = compat.axis_size("pod") if "pod" in axes else 1
    return data, pod


def _wire_vote_signs(signs: jax.Array, axes: Tuple[str, ...],
                     strategy: VoteStrategy, codec_name: str,
                     server_state):
    """int8 signs -> (int8 majority, new server state) over the manual
    `axes`, through the resolved strategy's stage methods and the
    codec's decode (the absorbed ``VoteEngine.vote_signs_codec``)."""
    from repro.core import codecs as codecs_mod
    from repro.core import vote_engine as ve
    c = codecs_mod.get_codec(codec_name)
    state = server_state if server_state is not None else {}
    if not axes:
        return signs, state
    data, pod = _region_sizes(axes)
    strat = ve.STRATEGIES[ve.resolve_strategy(strategy, signs.size, data,
                                              pod, codec=codec_name)]
    c.validate_strategy(strat.kind)
    if c.name == "ternary2bit" \
            and strat.kind == VoteStrategy.ALLGATHER_1BIT:
        from repro.core.codecs.ternary import TERNARY_WIRE
        return TERNARY_WIRE.vote(signs, axes), state
    if c.server_state:
        if not state:
            raise ValueError(
                f"codec {c.name!r} needs its server state threaded "
                "through the request (init_server_state)")
        from repro.core.codecs import weighted
        impl = ve.STRATEGIES[VoteStrategy.ALLGATHER_1BIT]
        m = ve.num_voters(axes)
        n = signs.shape[-1]
        arrived = impl.exchange(impl.pack(signs, m), axes)
        # crop the bit-pack padding lanes BEFORE decoding: padding
        # always agrees with the vote, so counting it would dilute
        # the flip-rate observations by n/32w
        stacked = sc.unpack_signs(arrived, jnp.int8)[..., :n]
        vote, new_ema = weighted.decode_stacked(stacked,
                                                state["flip_ema"])
        return vote, {**state, "flip_ema": new_ema}
    return strat.vote(signs, axes), state


def _plan_walk(plan, flat_signs: jax.Array, axes: Tuple[str, ...],
               server_state, overlap: bool = False):
    """The bucket-schedule walk (absorbed ``vote_plan.plan_vote_signs``,
    now the §11 executor's mesh wire): (n_params,) effective int8 signs
    -> ((n_params,) int8 votes, new server state). `overlap` selects the
    double-buffered issue order (bit-identical; see
    ``vote_plan.run_schedule``)."""
    from repro.core import vote_plan as vp
    if not axes:                     # M=1 degenerate case: vote = sign
        return flat_signs, dict(server_state) if server_state else {}
    return vp.run_schedule(plan, flat_signs, vp.MeshBucketWire(axes),
                           server_state, overlap=overlap)


def _leaf_execute(values: jax.Array, axes: Tuple[str, ...],
                  strategy: VoteStrategy, codec_name: str, plan,
                  byz: Optional[ByzantineConfig], salt: int, n_stale: int,
                  prev, step, server_state, overlap: bool = False,
                  obs=None):
    """One replica-local vote inside the manual region, with the full
    failure composition in the pinned order: stale substitution on the
    RAW payload (a straggling adversary corrupts its stale vector), sign
    extraction, the compiled adversary, then the wire (leaf-wise or the
    plan's bucket walk). Returns (votes in the payload dtype, state)."""
    from repro.distributed.fault_tolerance import (simulate_stragglers,
                                                   straggler_mask_for)
    axes = tuple(axes)
    if n_stale and prev is not None:
        mask = straggler_mask_for(axes, n_stale, like=values)
        values = simulate_stragglers(values, prev, mask)
    if plan is not None:
        signs = sc.sign_ternary(values)
        if byz is not None and axes:
            signs = byzantine.apply_adversary(signs, byz, axes, step=step,
                                              salt=salt, obs=obs)
        vote, new_state = _plan_walk(plan, signs, axes, server_state,
                                     overlap)
        return vote.astype(values.dtype), new_state
    shape = values.shape
    s = sc.sign_ternary(values if values.ndim else values.reshape(1))
    if byz is not None and axes:
        s = byzantine.apply_adversary(s, byz, axes, step=step, salt=salt,
                                      obs=obs)
    vote, new_state = _wire_vote_signs(s, axes, strategy, codec_name,
                                       server_state)
    return vote.reshape(shape).astype(values.dtype), new_state


# ---- tree execution (absorbed VoteEngine.vote_tree_codec /
# vote_plan.plan_tree_vote + the §7 diagnostics, computed once) ----------


def _tree_agreement(local: Dict, votes: Dict) -> jax.Array:
    """Fraction of coordinates where this replica's sign matches the
    vote."""
    num = sum(jnp.sum(sc.sign_ternary(l) == sc.sign_ternary(v))
              for l, v in zip(jax.tree.leaves(local),
                              jax.tree.leaves(votes)))
    den = sum(v.size for v in jax.tree.leaves(votes))
    return num / den


def _tree_margin(local: Dict, axes: Sequence[str],
                 byz: Optional[ByzantineConfig] = None,
                 step=None, salt: int = 0) -> jax.Array:
    """Mean |vote count| / M over all coordinates, measured on the signs
    that actually reach the wire (the compiled adversary re-applied with
    the same PRNG keys as the vote) — the §7 per-step margin."""
    from repro.core import vote_engine as ve
    leaves = jax.tree.leaves(local)
    m = ve.num_voters(axes) if axes else 1
    counts = []
    for l in leaves:
        s = sc.sign_ternary(l)
        if byz is not None and axes:
            s = byzantine.apply_adversary(s, byz, axes, step=step,
                                          salt=salt)
        if axes:
            counts.append(jax.lax.psum(s.astype(jnp.int32), tuple(axes)))
        else:
            counts.append(s.astype(jnp.int32))
    num = sum(jnp.sum(jnp.abs(c)) for c in counts)
    den = sum(l.size for l in leaves) * m
    return num / den


def _plan_tree_execute(plan, tree, axes: Tuple[str, ...],
                       byz: Optional[ByzantineConfig], step, salt: int,
                       server_state, diagnostics: bool,
                       overlap: bool = False):
    """The trainer's plan path (absorbed ``vote_plan.plan_tree_vote``):
    sign extraction per leaf, ONE flat buffer, the compiled adversary
    applied once to the whole wire buffer, then the bucket walk.
    Diagnostics are computed once over the flat buffer's true
    coordinates — the padded lanes the bucketed wire adds are never
    observed."""
    from repro.core import vote_engine as ve
    from repro.core import vote_plan as vp
    axes = tuple(axes)
    honest = vp.flatten_signs(plan, tree)
    eff = honest
    if byz is not None and axes:
        eff = byzantine.apply_adversary(eff, byz, axes, step=step,
                                        salt=salt)
    flat_votes, new_state = _plan_walk(plan, eff, axes, server_state,
                                       overlap)
    margin = agreement = None
    if diagnostics:
        m = ve.num_voters(axes) if axes else 1
        if axes:
            counts = jax.lax.psum(eff.astype(jnp.int32), axes)
        else:
            counts = eff.astype(jnp.int32)
        margin = jnp.sum(jnp.abs(counts)) / (plan.n_params * m)
        agreement = jnp.mean((honest == flat_votes).astype(jnp.float32))
    return (vp.unflatten_votes(plan, flat_votes, tree), new_state,
            margin, agreement)


def _tree_execute(tree, axes: Tuple[str, ...], strategy: VoteStrategy,
                  codec_name: str, byz: Optional[ByzantineConfig], step,
                  salt: int, server_state, diagnostics: bool):
    """Leaf-wise tree vote (absorbed ``VoteEngine.vote_tree_codec``).
    AUTO resolves once per tree on the total parameter count
    (codec-aware). Server-stateful codecs decode every leaf under this
    step's weights and fold ONE aggregate reliability update across the
    whole tree."""
    from repro.core import codecs as codecs_mod
    from repro.core import vote_engine as ve
    axes = tuple(axes)
    c = codecs_mod.get_codec(codec_name)
    resolved = strategy
    if strategy == VoteStrategy.AUTO and axes:
        total = sum(l.size for l in jax.tree.leaves(tree))
        data, pod = _region_sizes(axes)
        resolved = ve.select_strategy(total, data, pod, codec=codec_name)
    state = server_state if server_state is not None else {}
    if not c.server_state or not axes:
        votes = jax.tree.map(
            lambda leaf: _leaf_execute(leaf, axes, resolved, codec_name,
                                       None, byz, salt, 0, None, step,
                                       None)[0], tree)
        new_state = state
    else:
        # weighted decode with weights FIXED for the step, one EMA update
        c.validate_strategy(resolved)
        if not state:
            raise ValueError(
                f"codec {c.name!r} needs its server state threaded "
                "through the request (init_server_state)")
        from repro.core.codecs import weighted
        impl = ve.STRATEGIES[VoteStrategy.ALLGATHER_1BIT]
        m = ve.num_voters(axes)
        w = weighted.reliability_weights(state["flip_ema"])
        leaves, treedef = jax.tree.flatten(tree)
        out, mismatch, total_n = [], jnp.zeros_like(w), 0
        for leaf in leaves:
            shape = leaf.shape
            s = sc.sign_ternary(leaf if leaf.ndim else leaf.reshape(1))
            if byz is not None:
                s = byzantine.apply_adversary(s, byz, axes, step=step,
                                              salt=salt)
            n = s.shape[-1]
            arrived = impl.exchange(impl.pack(s, m), axes)
            # crop padding lanes before decoding (see _wire_vote_signs)
            stacked = sc.unpack_signs(arrived, jnp.int8)[..., :n]
            vote, mis = weighted.decode_leaf_fixed(stacked, w)
            mismatch = mismatch + mis
            total_n += stacked.size // stacked.shape[0]
            out.append(vote.reshape(shape).astype(leaf.dtype))
        new_ema = ((1.0 - weighted.RHO) * state["flip_ema"]
                   + weighted.RHO * mismatch / total_n)
        votes = jax.tree.unflatten(treedef, out)
        new_state = {**state, "flip_ema": new_ema}
    margin = agreement = None
    if diagnostics:
        agreement = _tree_agreement(tree, votes)
        margin = _tree_margin(tree, axes, byz, step, salt)
    return votes, new_state, margin, agreement, resolved


# ---------------------------------------------------------------------------
# virtualised execution (absorbed virtual_mesh.virtual_* — the exchange
# stage replaced by its exact host-side equivalent over a voter dim)
# ---------------------------------------------------------------------------


def effective_stacked_signs(values: jax.Array, prev=None, n_stale: int = 0,
                            byz: Optional[ByzantineConfig] = None,
                            step=None, salt: int = 0,
                            ids=None, obs=None) -> jax.Array:
    """The (M, n) int8 sign tensor that actually reaches the wire: sign
    extraction -> stale substitution (voter index < n_stale) -> adversary
    perturbation, in the pinned §7 order.

    ``ids`` (int32 (M,)) overrides the per-row voter index with logical
    population identities: both failure predicates and the adversary
    PRNG then depend on who each voter IS, not where its row landed, so
    a sampled or chunk-streamed round composes the same failures as the
    dense stack (default ``None`` = row position, the historical
    semantics)."""
    from repro.distributed.fault_tolerance import simulate_stragglers
    signs = sc.sign_ternary(values)
    m = signs.shape[0]
    idx = (jnp.arange(m, dtype=jnp.int32) if ids is None
           else jnp.asarray(ids).astype(jnp.int32))
    if n_stale and prev is not None:
        mask = (idx < n_stale)[:, None]
        signs = simulate_stragglers(signs, prev.astype(signs.dtype), mask)
    if byz is not None:
        signs = byzantine.apply_adversary_stacked(signs, byz, step=step,
                                                  salt=salt, ids=idx,
                                                  obs=obs)
    return signs


def _virtual_wire_vote(signs: jax.Array,
                       strategy: VoteStrategy) -> jax.Array:
    """(M, n) stacked int8 signs -> (n,) int8 majority, through the
    strategy's own pack/tally/unpack stages (exchange virtualised)."""
    from repro.core.vote_engine import STRATEGIES
    impl = STRATEGIES[strategy]
    m, n = signs.shape

    if strategy == VoteStrategy.PSUM_INT8:
        wire = impl.pack(signs, m)                       # (M, n) counts
        # psum over the vote axes == sum over the voter dim; the mesh op
        # accumulates in the wire dtype (safe: |sum| <= M <= dtype max)
        arrived = jnp.sum(wire, axis=0).astype(wire.dtype)
        return impl.unpack(impl.tally(arrived, m), n, jnp.int8)

    if strategy == VoteStrategy.ALLGATHER_1BIT:
        wire = impl.pack(signs, m)                       # (M, w) packed
        # the all-gather hands every replica the stacked wire — which is
        # exactly what the virtual mesh already holds
        return impl.unpack(impl.tally(wire, m), n, jnp.int8)

    if strategy == VoteStrategy.HIERARCHICAL:
        # virtual single-pod mesh: data axis = all M voters, no pod axis.
        # Mirrors HierarchicalStrategy.vote: pad to PACK * dsize so the
        # reduce-scatter shards stay word-aligned.
        padded, _ = pad_last(signs, sc.PACK * m)
        wire = impl.pack(padded, m)                      # (M, n_pad) counts
        # psum_scatter(tiled) over 'data': shard r of the summed counts
        summed = jnp.sum(wire, axis=0).astype(wire.dtype)
        shards = summed.reshape(m, padded.shape[-1] // m)
        decision = impl.tally(shards, m)                 # sign_binary/shard
        # unpack stage: pack each shard's decision, all-gather (tiled) the
        # packed words across 'data' = concatenate in replica order
        packed = sc.pack_signs(decision).reshape(-1)
        return sc.unpack_signs(packed, jnp.int8)[:n]

    raise ValueError(f"virtual mesh cannot realise {strategy!r}")


def _virtual_codec_vote(signs: jax.Array, strategy: VoteStrategy,
                        codec: str, server_state):
    """(M, n) stacked int8 signs -> ((n,) int8 majority, new server
    state) through the codec's wire stages, exchange virtualised."""
    state = server_state if server_state is not None else {}
    m, n = signs.shape

    if codec in ("sign1bit", "ef_sign"):
        # identical wire to the plain majority: only the encode input
        # (caller-side) differs
        return _virtual_wire_vote(signs, strategy), state

    if codec == "ternary2bit":
        if strategy == VoteStrategy.PSUM_INT8:
            # ternary symbols ARE the counts psum already sums
            return _virtual_wire_vote(signs, strategy), state
        from repro.core.codecs.ternary import TERNARY_WIRE
        wire = TERNARY_WIRE.pack(signs, m)       # (M, w) 2-bit packed
        return TERNARY_WIRE.unpack(TERNARY_WIRE.tally(wire, m), n,
                                   jnp.int8), state

    if codec == "weighted_vote":
        from repro.core.codecs import weighted
        from repro.core.vote_engine import STRATEGIES
        impl = STRATEGIES[VoteStrategy.ALLGATHER_1BIT]
        wire = impl.pack(signs, m)               # (M, w) 1-bit packed
        # crop the padding lanes before decoding, exactly like the mesh
        # tally: padding always agrees with the vote and would dilute
        # the flip-rate observations
        stacked = sc.unpack_signs(wire, jnp.int8)[:, :n]
        vote, new_ema = weighted.decode_stacked(stacked,
                                                state["flip_ema"])
        return vote, {**state, "flip_ema": new_ema}

    raise ValueError(f"virtual mesh cannot realise codec {codec!r}")


def _virtual_plan_walk(signs: jax.Array, plan, server_state,
                       overlap: bool = False):
    """(M, n_params) stacked int8 signs -> ((n_params,) int8 votes, new
    server state) through the plan's bucket schedule, exchange
    virtualised per bucket (the §11 executor's virtual wire) — the SAME
    static schedule the mesh walk drives, so plan drills hold mesh ==
    virtual bit-identity under either issue order."""
    from repro.core import vote_plan as vp
    m, n = signs.shape
    if n != plan.n_params:
        raise ValueError(f"stacked buffer has {n} coords, plan manifest "
                         f"says {plan.n_params}")
    return vp.run_schedule(plan, signs, vp.VirtualBucketWire(m),
                           server_state, overlap=overlap)


@functools.partial(jax.jit, static_argnames=("strategy", "codec", "plan",
                                             "n_stale", "byz", "salt",
                                             "overlap"))
def _virtual_execute(values, prev, step, server_state, attack_obs, *,
                     strategy, codec, plan, n_stale, byz, salt, overlap):
    # attack_obs is TRACED (the adaptive observation changes every
    # round; baking it static would recompile per step)
    eff = effective_stacked_signs(values, prev, n_stale, byz, step, salt,
                                  obs=attack_obs)
    if plan is not None:
        votes, state = _virtual_plan_walk(eff, plan, server_state, overlap)
    else:
        votes, state = _virtual_codec_vote(eff, strategy, codec,
                                           server_state)
    return votes, state, eff


# ---------------------------------------------------------------------------
# the backends
# ---------------------------------------------------------------------------


class VoteBackend(abc.ABC):
    """Executes :class:`VoteRequest`\\ s. Exactly two implementations
    exist — :class:`MeshBackend` (the real collectives) and
    :class:`VirtualBackend` (host-side exchange equivalents) — and the
    tier-2 harness proves them bit-identical on the same requests."""

    name: str = "?"

    def supports(self, request: VoteRequest) -> bool:
        """Capability introspection: can this backend execute the
        (already-validated) request?"""
        return self.why_unsupported(request) is None

    @abc.abstractmethod
    def why_unsupported(self, request: VoteRequest) -> Optional[str]:
        """None if supported, else an actionable reason."""

    def execute(self, request: VoteRequest) -> VoteOutcome:
        """Run the vote; raises ValueError (with the
        :meth:`why_unsupported` reason) on unsupported requests.

        Concrete template (DESIGN.md §13): capability check, the
        backend's :meth:`_execute`, then telemetry — a ``vote.execute``
        span when a recorder is active, and the exact wire counters
        (``vote.requests`` / ``vote.wire.bytes`` / ``vote.wire.
        messages``) from the outcome's once-computed WireReport,
        always. Both backends emit identical counter values for the
        same request because both count the SAME static report (the
        tier-2 obs drill asserts it). Under ``jit`` the increments run
        at trace time — once per compilation, the `kernels.ops`
        launch-count semantics."""
        self._check(request)
        rec = obs.get_recorder()
        if rec.enabled:
            with rec.span("vote.execute", backend=self.name,
                          form=request.form, codec=request.codec):
                out = self._execute(request)
        else:
            out = self._execute(request)
        c = obs.COUNTERS
        c.inc("vote.requests")
        c.inc("vote.wire.bytes", int(round(out.wire.payload_bytes)))
        c.inc("vote.wire.messages", out.wire.n_messages)
        return out

    @abc.abstractmethod
    def _execute(self, request: VoteRequest) -> VoteOutcome:
        """The backend's execution body (request already validated)."""

    def _check(self, request: VoteRequest) -> None:
        why = self.why_unsupported(request)
        if why is not None:
            raise ValueError(f"{self.name} backend cannot execute this "
                             f"request: {why}")


class MeshBackend(VoteBackend):
    """The real shard_map path.

    * ``leaf`` / ``tree`` requests execute **inside** an existing manual
      mesh region over `axes` (the trainer's configuration — construct
      with ``MeshBackend(axes=art.vote_axes)``); empty axes is the M=1
      single-process degenerate case.
    * ``stacked`` requests build the ``shard_map`` themselves: an M-wide
      'data' mesh over the first M local devices (`mesh_style` picks the
      trainer's partial-auto ``(M, 1)`` layout or a fully-manual ``(M,)``
      one), inputs round-tripped through numpy so outputs stay
      uncommitted when mesh sizes alternate in one process (elastic
      drills). Compiled executables are cached per static request
      configuration.
    """

    name = "mesh"

    def __init__(self, axes: Optional[Sequence[str]] = None,
                 mesh_style: str = "data_model"):
        if mesh_style not in MESH_STYLES:
            raise ValueError(f"unknown mesh_style {mesh_style!r}; "
                             f"have {MESH_STYLES}")
        self.axes = tuple(axes) if axes is not None else None
        self.mesh_style = mesh_style
        self._cache: Dict[Any, Any] = {}

    # ---- capability ----------------------------------------------------

    def why_unsupported(self, request: VoteRequest) -> Optional[str]:
        if request.form == "streamed":
            return ("the streamed population form virtualises more "
                    "voters than any physical mesh holds replicas; use "
                    "VirtualBackend")
        if request.voter_ids is not None or request.weights is not None:
            return ("logical voter ids / dataset-size vote weights "
                    "describe a virtual population; the mesh backend's "
                    "voters are physical replicas (use VirtualBackend)")
        if request.form == "stacked":
            m = request.payload.shape[0]
            have = len(jax.devices())
            if m > have:
                return (f"stacked execution needs {m} devices for "
                        f"{m} voters, have {have} (use VirtualBackend, "
                        "or XLA_FLAGS="
                        "--xla_force_host_platform_device_count=N)")
            return None
        if self.axes is None:
            return (f"{request.form}-form requests run inside a manual "
                    "mesh region; construct MeshBackend(axes=...) with "
                    "the vote axes")
        return None

    # ---- execution -----------------------------------------------------

    def _execute(self, request: VoteRequest) -> VoteOutcome:
        if request.form == "stacked":
            return self._execute_stacked(request)
        if request.form == "tree":
            return self._execute_tree(request)
        return self._execute_leaf(request)

    def _execute_leaf(self, req: VoteRequest) -> VoteOutcome:
        f = req.failures
        votes, state = _leaf_execute(
            req.payload, self.axes, req.strategy, req.codec, req.plan,
            f.byz, req.salt, f.n_stale, req.prev, req.step,
            req.server_state, req.overlap)
        from repro.core import vote_engine as ve
        if self.axes:
            data, pod = _region_sizes(self.axes)
            resolved = (None if req.plan is not None else
                        ve.resolve_strategy(req.strategy,
                                            req.payload.size, data, pod,
                                            codec=req.codec))
            n_voters = data * pod
        else:
            resolved, n_voters = None, 1
        wire = _static_wire(req.plan, req.codec, resolved,
                            req.payload.size, 1, n_voters)
        return VoteOutcome(votes=votes, server_state=state, wire=wire)

    def _execute_tree(self, req: VoteRequest) -> VoteOutcome:
        from repro.core import vote_engine as ve
        f = req.failures
        if req.plan is not None:
            votes, state, margin, agreement = _plan_tree_execute(
                req.plan, req.payload, self.axes, f.byz, req.step,
                req.salt, req.server_state, req.diagnostics, req.overlap)
            resolved = None
        else:
            votes, state, margin, agreement, resolved = _tree_execute(
                req.payload, self.axes, req.strategy, req.codec, f.byz,
                req.step, req.salt, req.server_state, req.diagnostics)
        if self.axes:
            data, pod = _region_sizes(self.axes)
            n_voters = data * pod
        else:
            n_voters, resolved = 1, None
        total = sum(l.size for l in jax.tree.leaves(req.payload))
        wire = _static_wire(req.plan, req.codec, resolved, total,
                            len(jax.tree.leaves(req.payload)), n_voters)
        wire = dataclasses.replace(wire, margin=margin,
                                   agreement=agreement)
        return VoteOutcome(votes=votes, server_state=state, wire=wire)

    # ---- stacked: the self-built shard_map (absorbed from the Scenario
    # Lab's mesh vote path) ----------------------------------------------

    def _stacked_fn(self, m: int, strategy: VoteStrategy, codec: str,
                    plan, byz, salt: int, n_stale: int, stateful: bool,
                    has_prev: bool, has_step: bool,
                    overlap: bool = False):
        key = (m, strategy, codec, plan, byz, salt, n_stale, stateful,
               has_prev, has_step, overlap)
        if key in self._cache:
            return self._cache[key]
        from jax.sharding import Mesh, PartitionSpec as P
        devs = np.array(jax.devices()[:m])
        if self.mesh_style == "data_model":
            mesh = Mesh(devs.reshape(m, 1), ("data", "model"))
        else:
            mesh = Mesh(devs, ("data",))
        manual = {"data"}
        axes = ("data",)

        # the adaptive observation dict rides as one more (replicated,
        # P()-spec) input — an empty dict for oblivious requests, so the
        # arity is uniform and jit's pytree structure separates the two
        def body(vals, prev, step, cstate, aobs):
            out, new_state = _leaf_execute(
                vals[0], axes, strategy, codec, plan, byz, salt, n_stale,
                prev[0] if has_prev else None,
                step if has_step else None, cstate, overlap,
                obs=aobs if aobs else None)
            return out[None], new_state

        # arity/specs vary with the static request shape; every variant
        # funnels into the same `body`
        if stateful:
            def f(vals, prev, step, cstate, aobs):
                return body(vals, prev, step, cstate, aobs)
            in_specs = (P("data"), P("data") if has_prev else P(),
                        P(), P(), P())
            out_specs = (P("data"), P())
        else:
            def f(vals, prev, step, aobs):
                return body(vals, prev, step, {}, aobs)[0]
            in_specs = (P("data"), P("data") if has_prev else P(), P(),
                        P())
            out_specs = P("data")
        sh = compat.shard_map(f, mesh=mesh, in_specs=in_specs,
                              out_specs=out_specs, axis_names=manual,
                              check_vma=False)
        fn = jax.jit(sh)
        self._cache[key] = fn
        return fn

    def _execute_stacked(self, req: VoteRequest) -> VoteOutcome:
        from repro.core import vote_engine as ve
        m, n = req.payload.shape
        f = req.failures
        stateful = bool(req.server_state)
        has_prev = req.prev is not None
        has_step = req.step is not None
        fn = self._stacked_fn(m, req.strategy, req.codec, req.plan,
                              f.byz, req.salt, f.n_stale, stateful,
                              has_prev, has_step, req.overlap)
        # host round-trips keep every array uncommitted: jit outputs
        # committed to one request's mesh devices would conflict with a
        # later (smaller) mesh in the same process (elastic drills)
        vals = np.asarray(req.payload)
        prev = np.asarray(req.prev) if has_prev else np.zeros((), np.int8)
        step = (np.asarray(req.step) if has_step
                else np.zeros((), np.int32))
        aobs = ({} if req.attack_obs is None else
                {k: np.asarray(a) for k, a in req.attack_obs.items()})
        if stateful:
            out, new_state = fn(vals, prev, step,
                                {k: np.asarray(a)
                                 for k, a in req.server_state.items()},
                                aobs)
            state = {k: jnp.asarray(np.asarray(a))
                     for k, a in new_state.items()}
        else:
            out = fn(vals, prev, step, aobs)
            state = dict(req.server_state or {})
        votes = jnp.asarray(np.asarray(out)[0].astype(np.int8))
        resolved = (None if req.plan is not None else
                    ve.resolve_strategy(req.strategy, n, m, 1,
                                        codec=req.codec))
        wire = _static_wire(req.plan, req.codec, resolved, n, 1, m)
        return VoteOutcome(votes=votes, server_state=state, wire=wire)


class VirtualBackend(VoteBackend):
    """The host-count-independent backend: ``stacked`` and ``streamed``
    requests only, exchange collectives replaced by their
    mathematically-exact equivalents over the voter dim (DESIGN.md §7).
    Bit-identical to :class:`MeshBackend` on the same request — asserted
    by the tier-2 harness and the hypothesis property suite.

    ``streamed`` requests run the §12 population engine: the stacked
    exchange in voter-chunks of ``chunk_size`` rows (chunk -> pack ->
    partial tally accumulate, exact integer arithmetic), peak sign
    memory O(chunk_size x n) instead of O(M x n), bit-identical to the
    dense stacked path by construction.

    ``use_kernels=True`` routes plain gathered-1-bit requests through
    the fused Pallas sign+pack+popcount kernel (the benchmark hot path);
    anything the kernel cannot realise (count-wire tie semantics,
    failure composition, server state, plans) is rejected rather than
    silently mis-decoded."""

    name = "virtual"

    def __init__(self, use_kernels: bool = False, chunk_size: int = 2048):
        self.use_kernels = use_kernels
        self.chunk_size = int(chunk_size)
        if self.chunk_size < 1:
            raise ValueError(f"chunk_size must be >= 1, got {chunk_size}")

    def why_unsupported(self, request: VoteRequest) -> Optional[str]:
        if request.form not in ("stacked", "streamed"):
            return ("the virtual backend executes host-local stacked "
                    f"(M, n) payloads or streamed populations, not "
                    f"{request.form!r} (use MeshBackend inside the mesh "
                    "region)")
        if request.form == "streamed":
            if self.use_kernels:
                return ("the fused-kernel path consumes one dense (M, n) "
                        "buffer; the streamed population engine exists "
                        "to never materialize it (use "
                        "VirtualBackend(use_kernels=False))")
            if request.strategy == VoteStrategy.HIERARCHICAL:
                return ("hierarchical's reduce-scatter wire pads to "
                        "PACK*M words — O(M) layout the streamed engine "
                        "exists to avoid; use psum_int8 or "
                        "allgather_1bit")
            return None
        if self.use_kernels:
            if request.overlap:
                return ("the fused-kernel path runs one fused launch per "
                        "request and cannot double-buffer a bucket "
                        "schedule (overlap=True); use "
                        "VirtualBackend(use_kernels=False)")
            if request.plan is not None:
                return ("the fused-kernel path has no bucket walk; use "
                        "vote_plan.plan_vote_stacked or "
                        "VirtualBackend(use_kernels=False)")
            if request.codec != "sign1bit":
                return ("the fused kernel realises the raw 1-bit wire "
                        f"only, not codec {request.codec!r}")
            if request.strategy != VoteStrategy.ALLGATHER_1BIT:
                return ("the fused kernel's binary majority (ties -> +1) "
                        "is allgather_1bit's tie rule, not "
                        f"{request.strategy.value!r}'s")
            if request.failures.active:
                return ("the fused kernel consumes raw voter values; "
                        "compose failures via "
                        "VirtualBackend(use_kernels=False)")
        return None

    def _execute(self, request: VoteRequest) -> VoteOutcome:
        req = request
        if req.form == "streamed":
            return self._execute_streamed(req)
        if req.voter_ids is not None or req.weights is not None:
            return self._execute_annotated(req)
        m, n = req.payload.shape
        eff = None
        if self.use_kernels:
            from repro.kernels import ops
            packed = ops.fused_majority(req.payload)
            votes = ops.bitunpack(packed, n, jnp.int8)
            state = dict(req.server_state or {})
            resolved = VoteStrategy.ALLGATHER_1BIT
        else:
            from repro.core import vote_engine as ve
            resolved = (None if req.plan is not None else
                        ve.resolve_strategy(req.strategy, n, m, 1,
                                            codec=req.codec))
            f = req.failures
            votes, state, eff = _virtual_execute(
                req.payload, req.prev, req.step, req.server_state,
                req.attack_obs,
                strategy=resolved, codec=req.codec, plan=req.plan,
                n_stale=f.n_stale, byz=f.byz, salt=req.salt,
                overlap=req.overlap)
        wire = _static_wire(req.plan, req.codec, resolved, n, 1, m)
        return VoteOutcome(votes=votes, server_state=state, wire=wire,
                           wire_signs=eff)

    def _execute_annotated(self, req: VoteRequest) -> VoteOutcome:
        """A stacked payload annotated with voter_ids/weights — the
        dense twin of a streamed request. Executes through the SAME
        population engine (one chunk spanning all M rows), so the
        chunked and dense decodes share one implementation and cannot
        drift: bit-identity is by construction, not by parallel
        maintenance of two float decode paths."""
        from repro.core import population
        m, n = req.payload.shape
        payload = jnp.asarray(req.payload)
        ids_np = (np.asarray(req.voter_ids, dtype=np.int32)
                  if req.voter_ids is not None
                  else np.arange(m, dtype=np.int32))
        ids_j = jnp.asarray(ids_np)

        def rows(ids):   # logical ids -> payload rows (ids_np sorted)
            return payload[jnp.searchsorted(ids_j, ids)]

        prev = None
        if req.prev is not None:
            prev_j = jnp.asarray(req.prev)
            prev = lambda ids: prev_j[jnp.searchsorted(ids_j, ids)]
        stream = PopulationStream(
            n_voters=m, n_coords=n, values=rows,
            ids=ids_np if req.voter_ids is not None else None,
            prev=prev,
            weights=(None if req.weights is None
                     else np.asarray(req.weights)))
        out = self._execute_stream_request(req, stream, chunk_size=m)
        # one more pass for the wire signs (dense M is small by
        # definition — the streamed form exists for the large-M case)
        f = req.failures
        eff = population._chunk_signs(stream, ids_np, req.step,
                                      f.n_stale, f.byz, req.salt,
                                      obs=req.attack_obs)
        return dataclasses.replace(out, wire_signs=eff)

    def _execute_streamed(self, req: VoteRequest) -> VoteOutcome:
        return self._execute_stream_request(req, req.payload,
                                            chunk_size=self.chunk_size)

    def _execute_stream_request(self, req: VoteRequest, stream,
                                chunk_size: int) -> VoteOutcome:
        from repro.core import population
        from repro.core import vote_engine as ve
        m, n = stream.n_voters, stream.n_coords
        resolved = ve.resolve_strategy(req.strategy, n, m, 1,
                                       codec=req.codec)
        f = req.failures
        votes, state, margin, counts = population.streamed_vote(
            stream, strategy=resolved, codec=req.codec,
            n_stale=f.n_stale, byz=f.byz, step=req.step, salt=req.salt,
            server_state=req.server_state, chunk_size=chunk_size,
            attack_obs=req.attack_obs)
        wire = _static_wire(req.plan, req.codec, resolved, n, 1, m)
        wire = dataclasses.replace(wire, margin=margin)
        return VoteOutcome(votes=votes, server_state=state, wire=wire,
                           counts=counts)


__all__ = [
    "FailureSpec", "MeshBackend", "PopulationStream", "VirtualBackend",
    "VoteBackend", "VoteOutcome", "VoteRequest", "WireReport",
    "count_dtype", "count_bytes", "effective_stacked_signs", "pad_last",
    "warn_legacy",
]
