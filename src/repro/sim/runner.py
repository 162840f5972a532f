"""ScenarioRunner: deterministic failure drills through the vote path.

Executes a :class:`~repro.sim.scenario.ScenarioSpec` on the paper's toy
objective (the 1000-dim quadratic family of Fig. 1, reduced): every voter
m holds the true gradient ``x`` plus N(0, sigma^2) noise, keeps per-worker
SIGNUM momentum (Algorithm 1), and the update applies the majority vote of
the momenta's signs. What makes it a *failure drill* is everything between
the local sign and the decision: stale-vote straggler substitution,
Byzantine perturbation, and elastic voter-set rescale — all DATA on one
declarative :class:`~repro.core.vote_api.VoteRequest` (DESIGN.md §10),
executed through the SAME code the trainer compiles.

Two interchangeable backends (bit-identical; asserted by tier-2) — both
build LITERALLY the same VoteRequest per step:

* ``virtual`` — :class:`~repro.core.vote_api.VirtualBackend`: the
  host-count-independent virtual mesh (any M on any device count).
* ``mesh``    — :class:`~repro.core.vote_api.MeshBackend`: the real
  thing, a ``shard_map`` over an M-wide 'data' axis on actual mesh
  replicas (requires M <= local device count; the tier-2 harness runs it
  on the 8-virtual-device platform).

Every step emits a :class:`StepTrace` (vote margin, fraction of
coordinates flipped vs the honest-majority oracle, convergence proxy);
the run digest hashes the raw vote bytes, so "reproducible" means
bit-identical, not approximately-equal (DESIGN.md §7).
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.checkpoint import (refit_leading_axis,
                                         refit_tree_leading_axis)
from repro.configs.base import VoteStrategy
from repro.core import attacks
from repro.core import codecs as codecs_mod
from repro.core import sign_compress as sc
from repro.core import vote_api as va
from repro.core.vote_engine import STRATEGIES
from repro.distributed.fault_tolerance import count_for_fraction
from repro.obs import recorder as obs
from repro.sim.scenario import ScenarioSpec

BACKENDS = ("virtual", "mesh")


# ---------------------------------------------------------------------------
# traces
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class StepTrace:
    """One step's structured trace record (schema: DESIGN.md §7).

    In population mode (§12) ``n_workers`` is the number of clients
    actually *sampled* into the round (the voters), ``n_population``
    the logical population they were drawn from (0 in the classic dense
    drills), and ``n_adversaries`` counts adversaries over the LOGICAL
    population — the realized adversarial fraction of a sampled round
    varies with the draw, which is exactly the federated threat model."""

    step: int
    n_workers: int
    n_adversaries: int
    n_stale: int
    margin: float          # mean |vote count| / M  (1 = unanimous)
    flip_fraction: float   # coords where vote != honest-majority oracle
    loss: float            # convergence proxy: 0.5 * mean(x^2) after update
    n_population: int = 0  # logical client population (§12; 0 = dense)


@dataclasses.dataclass(frozen=True)
class ScenarioTrace:
    """Full run record: spec + per-step traces + bit-level digest."""

    spec: ScenarioSpec
    backend: str
    steps: Tuple[StepTrace, ...]
    digest: str            # sha256 over every step's raw vote bytes + x
    #: the codec server state after the last step (e.g. the weighted
    #: vote's flip-EMA) — observability for defense-vs-attacker analysis
    #: (attacks/breaking_point.py reads the final reliability weights);
    #: not part of to_dict(), the JSON surface is unchanged
    final_server_state: Any = None

    def summary(self) -> Dict[str, Any]:
        impl = STRATEGIES[self.spec.strategy]
        codec = codecs_mod.get_codec(self.spec.codec)
        d = self.spec.dim
        # price the exchange at each step's ACTUAL voter count (elastic
        # events change it mid-run); payload bytes/replica are
        # m-independent for every strategy (bits/param is fixed). The
        # gathered exchange scales with the codec's symbol width (§8).
        wire_scale = (codec.bits_per_param / impl.wire_bits_per_param
                      if self.spec.strategy == VoteStrategy.ALLGATHER_1BIT
                      else 1.0)
        if self.spec.plan.enabled:
            # bucketed wire: price the WHOLE schedule (one alpha term per
            # bucket message — comm_model.schedule_time); one plan build
            # per distinct voter count, not per step
            plans = {m: self.spec.runtime_plan(m)
                     for m in {s.n_workers for s in self.steps}}
            est = float(np.mean(
                [plans[s.n_workers].schedule_cost(
                    s.n_workers, overlap=self.spec.plan.overlap)
                 for s in self.steps]))
            n_buckets = plans[self.steps[0].n_workers].n_buckets
        else:
            est = wire_scale * float(
                np.mean([impl.estimated_time(d, s.n_workers)
                         for s in self.steps]))
            n_buckets = 0
        return {
            "plan_buckets": n_buckets,
            "scenario": self.spec.name,
            "strategy": self.spec.strategy.value,
            "codec": self.spec.codec,
            "bits_per_param": codec.wire_bits(self.spec.strategy),
            "backend": self.backend,
            "tie_policy": self.spec.tie_policy,
            "first_loss": self.steps[0].loss,
            "final_loss": self.steps[-1].loss,
            "loss_drop": self.steps[0].loss - self.steps[-1].loss,
            "mean_margin": float(np.mean([s.margin for s in self.steps])),
            "mean_flip_fraction": float(
                np.mean([s.flip_fraction for s in self.steps])),
            "max_flip_fraction": float(
                np.max([s.flip_fraction for s in self.steps])),
            "wire_bytes_per_replica": d * codec.wire_bits(
                self.spec.strategy) / 8.0,
            "est_exchange_time_s": est,
            "digest": self.digest,
        }

    def to_dict(self) -> Dict[str, Any]:
        return {"spec": self.spec.to_dict(), "backend": self.backend,
                "digest": self.digest,
                "steps": [dataclasses.asdict(s) for s in self.steps],
                "summary": self.summary()}

    def to_json(self, **kw) -> str:
        return json.dumps(self.to_dict(), **kw)


# ---------------------------------------------------------------------------
# deterministic keys (scenario id + step folded; DESIGN.md §7)
# ---------------------------------------------------------------------------


def _root_key(spec: ScenarioSpec) -> jax.Array:
    return jax.random.fold_in(jax.random.PRNGKey(spec.seed), spec.salt)


def _noise(spec: ScenarioSpec, step: int, m: int) -> jax.Array:
    """Per-(scenario, step) gradient noise for m voters — independent of
    backend, device count and elastic history (shape depends only on the
    CURRENT voter count)."""
    key = jax.random.fold_in(jax.random.fold_in(_root_key(spec), 1), step)
    return jax.random.normal(key, (m, spec.dim), jnp.float32)


def _init_x(spec: ScenarioSpec) -> jax.Array:
    key = jax.random.fold_in(_root_key(spec), 0)
    return jax.random.normal(key, (spec.dim,), jnp.float32)


# population-mode keys (§12): every draw is keyed by LOGICAL client id
# and/or step — never by sampling order, chunk boundary, or device
# placement — so a round replays bit-identically whatever the host count
# or chunk size. Gradient noise uses the jax PRNG (tag 1, like the dense
# drills); client sampling (tag 2) and dataset sizes (tag 3) use a
# stateless splitmix64 hash in pure numpy — the host-side draws are
# O(population) per round, and hashing keeps them free of per-population
# jit recompiles (jax.random.permutation compiles once per distinct
# population size — ruinous across a churn schedule) while staying
# bit-stable across library versions.

_SM64 = (np.uint64(0x9E3779B97F4A7C15), np.uint64(0xBF58476D1CE4E5B9),
         np.uint64(0x94D049BB133111EB))


def _splitmix64(x: np.ndarray) -> np.ndarray:
    """The splitmix64 finalizer over a uint64 array (elementwise,
    vectorized, wrap-around arithmetic)."""
    x = (x + _SM64[0]).astype(np.uint64)
    x = ((x ^ (x >> np.uint64(30))) * _SM64[1]).astype(np.uint64)
    x = ((x ^ (x >> np.uint64(27))) * _SM64[2]).astype(np.uint64)
    return x ^ (x >> np.uint64(31))


def _hash_stream(spec: ScenarioSpec, tag: int, step: int = 0) -> np.ndarray:
    """A (1,) uint64 stream constant chaining (seed, salt, tag, step)."""
    h = np.zeros(1, dtype=np.uint64)
    for v in (spec.seed, spec.salt, tag, step):
        h = _splitmix64(h ^ np.uint64(v))
    return h


def _sample_ids(spec: ScenarioSpec, step: int, pop: int, k: int
                ) -> np.ndarray:
    """The sorted logical ids of the k clients sampled into `step`'s
    round: every id gets a (salt, step)-keyed hash score, the k smallest
    win — a uniform draw without replacement. Full participation skips
    the draw entirely, so turning sampling on cannot perturb any other
    stream."""
    if k >= pop:
        return np.arange(pop, dtype=np.int32)
    score = _splitmix64(np.arange(pop, dtype=np.uint64)
                        ^ _hash_stream(spec, 2, step))
    sel = np.argpartition(score, k - 1)[:k]
    return np.sort(sel).astype(np.int32)


def _client_sizes(spec: ScenarioSpec, ids: np.ndarray) -> np.ndarray:
    """Dataset sizes for a batch of clients, uniform on
    [min_data, max_data], hashed once per LOGICAL id (no step in the
    key): a client's dataset size is a property of the client, stable
    across rounds and churn — ids keep their sizes however the
    population around them changes."""
    pspec = spec.population
    r = _splitmix64(np.asarray(ids, dtype=np.uint64)
                    ^ _hash_stream(spec, 3))
    span = np.uint64(pspec.max_data - pspec.min_data + 1)
    return (pspec.min_data + (r % span)).astype(np.int32)


@jax.jit
def _pop_rows(ids, x, step, noise_root, noise_scale):
    """A chunk of client gradient rows: x plus per-(step, client) noise.
    Module-level jit on purpose — every spec-dependent quantity is a
    traced argument, so the compilation is keyed by SHAPES only and one
    compile serves every scenario in a sweep."""
    def one(cid):
        key = jax.random.fold_in(jax.random.fold_in(noise_root, step), cid)
        return x + noise_scale * jax.random.normal(key, x.shape,
                                                   jnp.float32)
    return jax.vmap(one)(ids)


def _population_rows(spec: ScenarioSpec):
    """The per-chunk gradient-row callback for the population stream."""
    noise_root = jax.random.fold_in(_root_key(spec), 1)
    scale = jnp.float32(spec.noise_scale)

    def rows(ids, x, step):
        return _pop_rows(ids, x, step, noise_root, scale)

    return rows


# ---------------------------------------------------------------------------
# the runner
# ---------------------------------------------------------------------------


class ScenarioRunner:
    """Executes one spec; ``run()`` returns the :class:`ScenarioTrace`.

    `backend` is "virtual" (default, host-count independent) or "mesh"
    (real shard_map collectives; every segment's voter count must fit the
    local device count). `mesh_style` picks the mesh layout for the mesh
    backend: "data_model" = an (M, 1) ('data', 'model') mesh, manual over
    'data' only — the trainer's partial-auto configuration; "data_only"
    = a fully-manual (M,) mesh.
    """

    def __init__(self, spec: ScenarioSpec, backend: str = "virtual",
                 mesh_style: str = "data_model"):
        if backend not in BACKENDS:
            raise ValueError(f"backend {backend!r} not in {BACKENDS}")
        if mesh_style not in ("data_model", "data_only"):
            raise ValueError(f"unknown mesh_style {mesh_style!r}")
        self.spec = spec
        self.backend = backend
        self.mesh_style = mesh_style
        if spec.population.enabled and backend != "virtual":
            raise ValueError(
                f"population mode ({spec.name!r}) virtualises more "
                "voters than any physical mesh holds replicas; it runs "
                "on backend='virtual' only (the streamed engine, §12)")
        # the execution backend: both build LITERALLY the same
        # VoteRequest per step; only the executor differs (DESIGN.md §10)
        if backend == "mesh":
            need = max([spec.n_workers] + [e.n_workers for e in spec.elastic])
            have = len(jax.devices())
            if need > have:
                raise ValueError(
                    f"mesh backend needs {need} devices for "
                    f"{spec.name!r}, have {have} (use backend='virtual', "
                    "or XLA_FLAGS=--xla_force_host_platform_device_count=N)")
            self._exec = va.MeshBackend(mesh_style=mesh_style)
        else:
            # population mode streams in spec-pinned voter chunks; the
            # default matches core.population.DEFAULT_CHUNK, so dense
            # drills are unaffected
            self._exec = va.VirtualBackend(
                chunk_size=spec.population.chunk_size)

    # ---- per-segment compiled pieces (rebuilt at elastic boundaries) ----

    def _segment(self, m: int, byz_cfg):
        spec = self.spec
        codec = codecs_mod.get_codec(spec.codec)
        byz = byz_cfg if byz_cfg.mode != "none" else None
        n_stale = count_for_fraction(spec.straggler_fraction, m)
        beta = spec.momentum
        has_ef = codec.worker_state
        # the bucketed wire schedule (§9); rebuilt per segment because
        # only the hierarchical alignment depends on the voter count
        plan = spec.runtime_plan(m)
        oracle_backend = va.VirtualBackend()

        @jax.jit
        def prepare(x, v, err, prev, cstate, noise, step, aobs):
            g = x[None, :] + spec.noise_scale * noise
            v2 = beta * v + (1.0 - beta) * g if beta > 0 else g
            # codec encode: fold the EF residual into the vote input (§8);
            # t == v2 for residual-free codecs, so the legacy path is
            # bit-identical
            t = err + v2 if has_ef else v2
            fresh = sc.sign_ternary(t)
            eff = va.effective_stacked_signs(t, prev, n_stale, byz, step,
                                             spec.salt, obs=aobs)
            # honest-majority oracle through the SAME codec decode (and
            # the same bucket schedule when the plan axis is on): a
            # failure-free VoteRequest on the virtual backend; state is
            # read-only here — the oracle must not advance the
            # reliability EMA
            oracle = oracle_backend.execute(va.VoteRequest(
                payload=fresh, form="stacked", strategy=spec.strategy,
                codec=spec.codec, plan=plan, server_state=cstate)).votes
            counts = jnp.sum(eff.astype(jnp.int32), axis=0)
            margin = jnp.mean(jnp.abs(counts).astype(jnp.float32)) / m
            return v2, t, fresh, eff, oracle, counts, margin

        @jax.jit
        def finish(x, applied, vote, oracle):
            # `applied` is what moves the iterate (the PREVIOUS step's
            # banked vote under delayed_vote, the fresh vote otherwise);
            # the flip trace always scores the FRESH vote against the
            # oracle — the delay shifts the update, not the decision
            flip = jnp.mean((vote != oracle).astype(jnp.float32))
            x2 = x - spec.learning_rate * applied.astype(jnp.float32)
            loss = 0.5 * jnp.mean(x2 * x2)
            return x2, flip, loss

        @jax.jit
        def ef_feedback(t, vote):
            # per-worker residual vs the APPLIED vote (codec feedback_leaf
            # semantics, vmapped over the stacked voter dim)
            scale = jnp.mean(jnp.abs(t), axis=1, keepdims=True)
            return t - scale * vote[None, :].astype(t.dtype)

        return prepare, finish, ef_feedback, n_stale, plan

    # ---- telemetry (DESIGN.md §13) ----

    def _record_step(self, rec, trace: StepTrace, wire,
                     phase_s: Dict[str, float], n_chunks: int = 0) -> None:
        """One unified step record: the StepTrace drill fields joined
        with the WireReport wire accounting and the per-phase span
        times, written to the active recorder's JSONL sink."""
        d = self.spec.dim
        payload = float(wire.payload_bytes)
        fields = dict(
            scenario=self.spec.name, backend=self.backend,
            step=trace.step, n_voters=trace.n_workers,
            n_population=trace.n_population,
            n_adversaries=trace.n_adversaries, n_stale=trace.n_stale,
            strategy=self.spec.strategy.value, codec=self.spec.codec,
            payload_bytes=payload, n_messages=int(wire.n_messages),
            n_coords=d, compression_vs_f32=payload / (4.0 * d),
            margin=trace.margin, flip_fraction=trace.flip_fraction,
            loss=trace.loss, phase_s=phase_s)
        if n_chunks:
            fields["n_chunks"] = n_chunks
        rec.step(**fields)

    # ---- the drill ----

    def run(self) -> ScenarioTrace:
        if self.spec.population.enabled:
            return self._run_population()
        spec = self.spec
        codec = codecs_mod.get_codec(spec.codec)
        x = _init_x(spec)
        m = spec.workers_at(0)
        v = jnp.zeros((m, spec.dim), jnp.float32)        # per-worker momentum
        # codec worker state: the EF residual, stacked like the momentum
        err = jnp.zeros((m, spec.dim), jnp.float32)
        # last step's locally COMPUTED signs (pre-stale, pre-adversary):
        # that is what a straggler re-submits; failures then apply to the
        # substituted vector (vote_with_failures order)
        prev = jnp.zeros((m, spec.dim), jnp.int8)
        # delayed-vote buffer (§11): the one-round-old majority applied
        # this step. Replicated (dim,), so elastic rescales never touch
        # it; zeros at step 0 -> the first update is a no-op, matching
        # the trainer's weight-decay-only first step
        pending = jnp.zeros((spec.dim,), jnp.int8)
        att = spec.adversary
        # the attacker's memory (§15): carried beside the server state,
        # updated once per round from the published outcome, refit on
        # elastic rescale like the reliability EMA
        astate = (attacks.AttackState.init(spec.dim, m) if att.adaptive
                  else None)
        # segments cache per (m, byz_cfg): an attack schedule swaps the
        # adversary config between steps, and re-jitting the whole
        # prepare/finish pipeline at every phase flip would dwarf the
        # step; config equality is exact because build_config collapses
        # honest phases to the canonical rest state
        segs: Dict = {}

        def segment(m_, cfg):
            key = (m_, cfg)
            if key not in segs:
                segs[key] = self._segment(m_, cfg)
            return segs[key]

        byz_cfg = att.byz_config_at(0, m, spec.seed)
        prepare, finish, ef_feedback, n_stale, plan = segment(m, byz_cfg)
        # codec server state: replicated decode memory (reliability EMA);
        # under a plan the schedule's codec set decides what exists
        if plan is not None:
            cstate = plan.init_server_state(m)
        else:
            cstate = (codec.init_server_state(m) if codec.server_state
                      else {})
        digest = hashlib.sha256()
        steps: List[StepTrace] = []
        rec = obs.get_recorder()
        for step in range(spec.n_steps):
            m_now = spec.workers_at(step)
            if m_now != m:
                # elastic rescale: per-worker state — momentum, EF
                # residual, stale vector, reliability EMA — refits by the
                # checkpoint rule (truncate / zero-pad axis 0, §6):
                # joiners start with zero momentum, zero residual, an
                # abstaining stale vector, and the uninformed-prior weight
                v = jnp.asarray(refit_leading_axis(
                    np.asarray(v), (m_now, spec.dim)))
                err = jnp.asarray(refit_leading_axis(
                    np.asarray(err), (m_now, spec.dim)))
                prev = jnp.asarray(refit_leading_axis(
                    np.asarray(prev), (m_now, spec.dim)))
                cstate = jax.tree.map(
                    jnp.asarray, refit_tree_leading_axis(
                        cstate, {k: (m_now,) + tuple(a.shape[1:])
                                 for k, a in cstate.items()}))
                m = m_now
                if astate is not None:
                    astate = astate.refit(m)
            # schedule resolution: the config in force THIS step (equal
            # to the base config when the schedule is empty, so
            # schedule-free runs reuse one cached segment and keep their
            # historical digests)
            byz_cfg = att.byz_config_at(step, m, spec.seed)
            prepare, finish, ef_feedback, n_stale, plan = segment(m, byz_cfg)
            # the observation the current phase's adversary may see —
            # None unless the phase's mode is adaptive, so oblivious
            # phases trace exactly the legacy signature
            aobs = (astate.observation(att.observe)
                    if astate is not None
                    and byz_cfg.mode in attacks.ATTACK_MODES else None)
            noise = _noise(spec, step, m)
            step_t = jnp.int32(step)
            # tracing never touches a traced value — the spans time host
            # perf_counter around each phase (block_until_ready so the
            # async dispatch doesn't bill one phase's work to the next),
            # so the run digest is bit-identical with the recorder on
            # (regression-tested by tests/test_obs.py)
            with rec.span("scenario.prepare", step=step) as sp_prep:
                v, t, fresh, eff, oracle, counts, margin = prepare(
                    x, v, err, prev, cstate, noise, step_t, aobs)
                if rec.enabled:
                    jax.block_until_ready(oracle)
            # ONE declarative request per step, identical on both
            # backends — payload is the raw stacked encode input, the
            # failure composition is data, the executor is the only
            # thing that differs (DESIGN.md §10). The mesh backend
            # round-trips through numpy internally so elastic segments
            # with different mesh sizes coexist in one process. (The
            # executor re-derives the effective signs prepare() captured
            # for the margin trace — the cost of keeping the request
            # backend-identical; both derivations are jitted.)
            with rec.span("scenario.vote", step=step,
                          backend=self.backend) as sp_vote:
                out = self._exec.execute(va.VoteRequest(
                    payload=t, form="stacked", strategy=spec.strategy,
                    codec=spec.codec, plan=plan,
                    failures=va.FailureSpec(n_stale=n_stale, byz=byz_cfg
                                            if byz_cfg.mode != "none"
                                            else None),
                    prev=prev, step=step_t, salt=spec.salt,
                    server_state=cstate, overlap=spec.plan.overlap,
                    attack_obs=aobs))
                if rec.enabled:
                    jax.block_until_ready(out.votes)
            vote, cstate = out.votes, out.server_state
            if spec.delayed_vote:
                applied, pending = pending, vote
            else:
                applied = vote
            with rec.span("scenario.finish", step=step) as sp_fin:
                x, flip, loss = finish(x, applied, vote, oracle)
                if codec.worker_state:
                    err = ef_feedback(t, vote)
                if rec.enabled:
                    jax.block_until_ready(x)
            prev = fresh
            if astate is not None:
                # one observation per round, from PUBLISHED outputs only:
                # the broadcast vote, its tally, and the wire signs the
                # reputation bookkeeping replays (all public, §15)
                astate = attacks.update_attack_state(astate, vote, counts,
                                                     eff)
            digest.update(np.asarray(vote).tobytes())
            trace = StepTrace(
                step=step, n_workers=m,
                n_adversaries=byz_cfg.num_adversaries, n_stale=n_stale,
                margin=float(margin), flip_fraction=float(flip),
                loss=float(loss))
            steps.append(trace)
            if rec.enabled:
                self._record_step(rec, trace, out.wire, phase_s={
                    "prepare": sp_prep.dur_s, "vote": sp_vote.dur_s,
                    "finish": sp_fin.dur_s})
        digest.update(np.asarray(x, np.float32).tobytes())
        return ScenarioTrace(spec=spec, backend=self.backend,
                             steps=tuple(steps), digest=digest.hexdigest(),
                             final_server_state=cstate)

    # ---- the federated drill (population mode, DESIGN.md §12) ----

    def _run_population(self) -> ScenarioTrace:
        """The streamed-population variant of :meth:`run`: each round
        samples clients from the logical population, streams their
        gradient rows through :func:`repro.core.population.streamed_vote`
        in voter-chunks (never materializing the population), and
        applies the (optionally dataset-weighted) majority to the
        iterate. Bit-identical across host counts, chunk sizes and
        backend wiring because every PRNG draw is keyed by logical
        client id / step and every tally is exact integer arithmetic."""
        spec = self.spec
        pspec = spec.population
        codec = codecs_mod.get_codec(spec.codec)
        rows = _population_rows(spec)
        x = _init_x(spec)
        pop = pspec.clients_at(0)
        # codec server state lives over the LOGICAL population (the
        # weighted vote tracks every client's reliability, sampled into
        # a round or not)
        cstate = codec.init_server_state(pop) if codec.server_state else {}
        att = spec.adversary
        # attacker memory over the LOGICAL population (ids, not rows):
        # the reputation mirror refits on churn like the flip-EMA
        astate = (attacks.AttackState.init(spec.dim, pop) if att.adaptive
                  else None)
        from repro.core import population as pop_engine
        pending = jnp.zeros((spec.dim,), jnp.int8)   # delayed-vote buffer
        digest = hashlib.sha256()
        steps: List[StepTrace] = []
        rec = obs.get_recorder()
        for step in range(spec.n_steps):
            pop_now = pspec.clients_at(step)
            if pop_now != pop:
                # churn: per-client server state — the weighted vote's
                # (pop,) flip-rate EMA — refits by the checkpoint rule
                # (§6): leavers truncate off the top of the id range,
                # joiners zero-pad in at the uninformed prior
                if cstate:
                    cstate = jax.tree.map(
                        jnp.asarray, refit_tree_leading_axis(
                            cstate,
                            {key: (pop_now,) + tuple(np.asarray(a).shape[1:])
                             for key, a in cstate.items()}))
                pop = pop_now
                if astate is not None:
                    astate = astate.refit(pop)
            # adversary count is pinned to the LOGICAL population (ids <
            # num_adversaries act evil); the realized count in a sampled
            # round varies with the draw. byz_config_at resolves the
            # attack schedule too — equal to byz_config when no schedule
            byz_cfg = att.byz_config_at(step, pop, spec.seed)
            byz = byz_cfg if byz_cfg.mode != "none" else None
            aobs = (astate.observation(att.observe)
                    if astate is not None
                    and byz_cfg.mode in attacks.ATTACK_MODES else None)
            k = max(1, count_for_fraction(pspec.sample_fraction, pop))
            ids = _sample_ids(spec, step, pop, k)
            step_t = jnp.int32(step)

            def values(cids, _x=x, _t=step_t):
                return rows(cids, _x, _t)

            stream = va.PopulationStream(
                n_voters=k, n_coords=spec.dim, values=values, ids=ids,
                weights=(_client_sizes(spec, ids)
                         if pspec.weighting == "dataset" else None))
            if byz is not None:
                # honest-majority oracle for the flip trace: the same
                # stream, failure-free, state read-only (runs FIRST so
                # the population.last.* counters reflect the real vote)
                oracle, _, _, _ = pop_engine.streamed_vote(
                    stream, strategy=spec.strategy, codec=spec.codec,
                    step=step_t, salt=spec.salt, server_state=cstate,
                    chunk_size=pspec.chunk_size)
            chunks_before = obs.COUNTERS.get("population.chunks")
            with rec.span("scenario.vote", step=step,
                          backend=self.backend) as sp_vote:
                out = self._exec.execute(va.VoteRequest(
                    payload=stream, form="streamed", strategy=spec.strategy,
                    codec=spec.codec, failures=va.FailureSpec(byz=byz),
                    step=step_t, salt=spec.salt, server_state=cstate,
                    attack_obs=aobs))
                if rec.enabled:
                    jax.block_until_ready(out.votes)
            vote, cstate = out.votes, out.server_state
            flip = (float(jnp.mean((vote != oracle).astype(jnp.float32)))
                    if byz is not None else 0.0)
            if spec.delayed_vote:
                applied, pending = pending, vote
            else:
                applied = vote
            x = x - spec.learning_rate * applied.astype(jnp.float32)
            loss = float(0.5 * jnp.mean(x * x))
            if astate is not None:
                if att.observe == "reputation":
                    # replay the flip-EMA observation the codec makes:
                    # per-sampled-voter mismatch counts vs the published
                    # vote, assembled chunk-by-chunk over the SAME wire
                    # signs the round produced (public bookkeeping, §15)
                    mis = np.zeros(k, np.float32)
                    for lo, ids_np in pop_engine._chunks(
                            stream, pspec.chunk_size):
                        eff_c = pop_engine._chunk_signs(
                            stream, ids_np, step_t, 0, byz, spec.salt,
                            obs=aobs)
                        mis[lo:lo + len(ids_np)] = np.asarray(
                            pop_engine._chunk_mismatch(eff_c, vote))
                    astate = attacks.update_attack_state_population(
                        astate, vote, out.counts, ids, mis / spec.dim)
                else:
                    astate = attacks.update_attack_state_population(
                        astate, vote, out.counts,
                        np.zeros(0, np.int32), np.zeros(0, np.float32))
            digest.update(np.asarray(vote).tobytes())
            trace = StepTrace(
                step=step, n_workers=k,
                n_adversaries=byz_cfg.num_adversaries, n_stale=0,
                margin=float(out.wire.margin), flip_fraction=flip,
                loss=loss, n_population=pop)
            steps.append(trace)
            if rec.enabled:
                self._record_step(
                    rec, trace, out.wire, phase_s={"vote": sp_vote.dur_s},
                    n_chunks=obs.COUNTERS.get("population.chunks")
                    - chunks_before)
        digest.update(np.asarray(x, np.float32).tobytes())
        return ScenarioTrace(spec=spec, backend=self.backend,
                             steps=tuple(steps), digest=digest.hexdigest(),
                             final_server_state=cstate)


def run_scenarios(specs, backend: str = "virtual",
                  mesh_style: str = "data_model") -> List[ScenarioTrace]:
    return [ScenarioRunner(s, backend=backend, mesh_style=mesh_style).run()
            for s in specs]
