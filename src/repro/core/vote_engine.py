"""The VoteEngine subsystem: one interface over every majority-vote wire
protocol (DESIGN.md §2).

The paper's parameter server is a four-stage pipeline

    pack  ->  exchange  ->  tally  ->  unpack

* **pack**     — turn a replica-local sign tensor into its wire format
                 (int counts, or 32-signs-per-uint32 packed words);
* **exchange** — the mesh collectives that move the wire format between
                 replicas (all-reduce / all-gather / reduce-scatter);
* **tally**    — compute the majority from what arrived (sign of counts,
                 or bit-sliced popcount over packed words);
* **unpack**   — decode the decision back to a ±1 sign tensor.

Each :class:`VoteStrategyImpl` realises those stages differently but is
interchangeable behind the declarative vote API (``core.vote_api``,
DESIGN.md §10): the trainer (`train/train_step.py`), the failure drills
and the benchmarks all build a ``VoteRequest`` and a backend walks these
stage methods — one wire implementation, one set of semantics, one
accounting model. :class:`VoteEngine` remains as the legacy object whose
vote methods are deprecation shims over that API.

Strategy selection: :func:`select_strategy` prices each strategy's wire
bytes through ``distributed.comm_model`` (alpha-beta ICI/DCI terms) for the
given mesh shape and parameter count; ``VoteStrategy.AUTO`` resolves to the
cheapest. The choice is compile-time (mesh shape and param count are
static), so AUTO costs nothing at runtime.

Tie conventions differ by wire format (DESIGN.md §5): integer-count
strategies use ternary signs (a tied or all-zero coordinate yields 0 —
abstention), while the 1-bit wire can only encode two states, so packed
strategies resolve ties to +1 exactly like ``kernels/ref.py``.

All vote entry points accept N-D tensors and pack along the LAST dim only:
flattening leaves would destroy their auto ('model') shardings and force
full all-gathers of every TP-sharded tensor (measured: 14.3 GB of int8
signs for qwen2-moe before this was changed).
"""
from __future__ import annotations

import abc
import dataclasses
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro import compat
from repro.configs.base import ByzantineConfig, VoteStrategy
from repro.core import sign_compress as sc
from repro.distributed import comm_model
from repro.obs.scopes import scope_stages


# ---------------------------------------------------------------------------
# mesh helpers (shared by majority_vote and the strategies)
# ---------------------------------------------------------------------------


def vote_axes_in(mesh_axis_names: Sequence[str]) -> Tuple[str, ...]:
    """The mesh axes the vote runs over, outermost first."""
    return tuple(a for a in ("pod", "data") if a in mesh_axis_names)


def num_voters(axes: Sequence[str]) -> int:
    """Static replica count over the (manual) vote axes, inside a trace."""
    n = 1
    for a in axes:
        n *= compat.axis_size(a)
    return n


# The pack-width helpers live in vote_api (DESIGN.md §10) — one source
# of truth for every wire; re-exported here for the existing importers.
from repro.core.vote_api import count_bytes as _count_bytes  # noqa: E402
from repro.core.vote_api import count_dtype  # noqa: F401,E402
from repro.core.vote_api import pad_last as _pad_last  # noqa: E402


# ---------------------------------------------------------------------------
# strategy interface
# ---------------------------------------------------------------------------

class VoteStrategyImpl(abc.ABC):
    """One wire protocol for the majority vote.

    ``vote`` composes the four pipeline stages over the vote axes; the
    accounting methods price the exchange stage for the cost model and the
    benchmarks. Each subclass's own stage methods run under their
    ``vote_<stage>`` device scopes (``obs.scopes.scope_stages``). Inputs
    to ``vote`` are replica-local int8 sign tensors (ternary ok); outputs
    are int8 majorities with this strategy's tie convention.
    """

    kind: VoteStrategy
    #: bits each replica puts on the wire per parameter, per exchange
    wire_bits_per_param: float
    #: tie convention of the decoded majority ("zero" or "plus_one")
    ties: str

    def __init_subclass__(cls, **kwargs):
        super().__init_subclass__(**kwargs)
        scope_stages(cls)

    # ---- pipeline stages ----

    @abc.abstractmethod
    def pack(self, signs: jax.Array, n_voters: int) -> jax.Array:
        """Replica-local signs -> wire tensor."""

    @abc.abstractmethod
    def exchange(self, wire: jax.Array, axes: Sequence[str]) -> jax.Array:
        """Run the collectives; returns whatever tally needs."""

    @abc.abstractmethod
    def tally(self, arrived: jax.Array, n_voters: int) -> jax.Array:
        """Aggregate to the (still-encoded) majority decision."""

    @abc.abstractmethod
    def unpack(self, decision: jax.Array, n: int, dtype) -> jax.Array:
        """Decode the decision to (..., n) ±1/0 signs in `dtype`."""

    def vote(self, signs: jax.Array, axes: Sequence[str]) -> jax.Array:
        """signs int8 (..., n) -> int8 majority (..., n) over `axes`."""
        m = num_voters(axes)
        n = signs.shape[-1]
        wire = self.pack(signs, m)
        arrived = self.exchange(wire, axes)
        decision = self.tally(arrived, m)
        return self.unpack(decision, n, jnp.int8)

    # ---- accounting (per-chip bytes; ring collective terms) ----

    def payload_bytes(self, n_params: int, n_voters: int = 2) -> float:
        """One replica's outbound wire payload (the paper's 'bits sent')."""
        return n_params * self.wire_bits_per_param / 8.0

    @abc.abstractmethod
    def ring_bytes(self, n_params: int, data_size: int,
                   pod_size: int = 1) -> Dict[str, float]:
        """Per-chip transit bytes of the exchange, split ICI/DCI, plus the
        collective count (for the latency term)."""

    def estimated_time(self, n_params: int, data_size: int,
                       pod_size: int = 1) -> float:
        b = self.ring_bytes(n_params, data_size, pod_size)
        return comm_model.collective_time(
            b["ici"], b["dci"], n_collectives=int(b["n_collectives"])).time_s


class PsumInt8Strategy(VoteStrategyImpl):
    """Integer-sum vote: one all-reduce of narrow counts, then sign.

    pack: cast ternary signs to the narrowest count dtype; exchange: psum
    over the vote axes; tally: the psum already is the count tensor; unpack:
    sign of counts (ties and all-abstain coordinates -> 0).
    """

    kind = VoteStrategy.PSUM_INT8
    wire_bits_per_param = 8.0   # int8 counts up to 127 voters
    ties = "zero"

    def pack(self, signs, n_voters):
        return signs.astype(count_dtype(n_voters))

    def exchange(self, wire, axes):
        return jax.lax.psum(wire, axis_name=tuple(axes))

    def tally(self, arrived, n_voters):
        return arrived

    def unpack(self, decision, n, dtype):
        return jnp.sign(decision).astype(dtype)

    def ring_bytes(self, n_params, data_size, pod_size=1):
        c = _count_bytes(data_size * pod_size)
        m = data_size * pod_size
        return {"ici": 2.0 * n_params * c * (data_size - 1) / data_size,
                "dci": (2.0 * (n_params / data_size) * c
                        * (pod_size - 1) / pod_size if pod_size > 1 else 0.0),
                "n_collectives": 1, "total": 2.0 * n_params * c * (m - 1) / m}


class Allgather1BitStrategy(VoteStrategyImpl):
    """The paper-faithful wire protocol: every chip plays the server.

    pack: bit-pack 32 signs per uint32 word (1 bit/param on the wire);
    exchange: all-gather the packed words over each vote axis; tally:
    bit-sliced count of the voters' words (``sc.packed_majority``); unpack:
    decode the packed majority (ties -> +1).
    """

    kind = VoteStrategy.ALLGATHER_1BIT
    wire_bits_per_param = 1.0
    ties = "plus_one"

    def __init__(self, tally_fn: Optional[Callable] = None):
        # override point for the Pallas popcount kernel (kernels.ops.majority)
        self._tally_fn = tally_fn

    def pack(self, signs, n_voters):
        padded, _ = _pad_last(signs, sc.PACK)
        return sc.pack_signs(padded)

    def exchange(self, wire, axes):
        packed = wire
        for a in axes:   # gather over each vote axis; leading M dims stack
            packed = compat.all_gather(packed, a, tiled=False)
        # collapse the stacked gather dims into one voter dim M
        return packed.reshape((-1,) + packed.shape[len(tuple(axes)):])

    def tally(self, arrived, n_voters):
        if self._tally_fn is not None:
            return self._tally_fn(arrived)
        return sc.packed_majority(arrived)

    def unpack(self, decision, n, dtype):
        return sc.unpack_signs(decision, dtype)[..., :n]

    def ring_bytes(self, n_params, data_size, pod_size=1):
        # exchange() gathers pod-first (vote_axes_in order): the DCI hop
        # moves one packed payload, the ICI hop then gathers the stacked
        # (pod, w) words
        m = data_size * pod_size
        dci = (pod_size - 1) * n_params / 8.0
        ici = (data_size - 1) * pod_size * n_params / 8.0
        assert abs((ici + dci) - (m - 1) * n_params / 8.0) < 1e-6 * max(m, 1)
        return {"ici": ici, "dci": dci,
                "n_collectives": 1 + (1 if pod_size > 1 else 0),
                "total": ici + dci}


class HierarchicalStrategy(VoteStrategyImpl):
    """Count-shards within the pod, sums counts across pods, rebroadcasts
    the 1-bit result: the global majority (counts cross pods — NOT a
    vote-of-votes).

    The stages interleave two exchanges, so ``vote`` overrides the default
    composition: pack casts to counts, exchange is the int8 reduce-scatter
    (+ cross-pod psum of the scattered counts), tally is the binary sign of
    the shard's counts, and unpack re-packs the shard decision, all-gathers
    it (1 bit/param), and decodes — the second collective is part of the
    decode because every replica needs the full decision back.
    """

    kind = VoteStrategy.HIERARCHICAL
    wire_bits_per_param = 8.0   # int8 counts in the reduce-scatter
    ties = "plus_one"

    def __init__(self, data_axis: str = "data",
                 pod_axis: Optional[str] = "pod"):
        self.data_axis = data_axis
        self.pod_axis = pod_axis

    def _axes(self, axes: Sequence[str]) -> Tuple[str, Optional[str]]:
        pod = self.pod_axis if self.pod_axis in tuple(axes) else None
        return self.data_axis, pod

    def pack(self, signs, n_voters):
        return signs.astype(count_dtype(n_voters))

    def exchange(self, wire, axes):
        data_axis, pod_axis = self._axes(axes)
        counts = jax.lax.psum_scatter(
            wire, data_axis, scatter_dimension=wire.ndim - 1, tiled=True)
        if pod_axis is not None:
            counts = jax.lax.psum(counts, pod_axis)
        return counts

    def tally(self, arrived, n_voters):
        return sc.sign_binary(arrived)       # ties -> +1 (1-bit wire)

    def unpack(self, decision, n, dtype):
        # second (cheap) exchange: packed all-gather of the shard decision
        packed = compat.all_gather(
            sc.pack_signs(decision), self.data_axis,
            axis=decision.ndim - 1, tiled=True)
        return sc.unpack_signs(packed, dtype)[..., :n]

    def vote(self, signs, axes):
        data_axis, pod_axis = self._axes(axes)
        dsize = compat.axis_size(data_axis)
        m = dsize * (compat.axis_size(pod_axis) if pod_axis else 1)
        n = signs.shape[-1]
        padded, _ = _pad_last(signs, sc.PACK * dsize)
        decision = self.tally(self.exchange(self.pack(padded, m), axes), m)
        return self.unpack(decision, n, jnp.int8)

    def ring_bytes(self, n_params, data_size, pod_size=1):
        d = float(n_params)
        rs = d * 1 * (data_size - 1) / data_size        # int8 RS in pod
        xpod = ((d / data_size) * 1 * 2 * (pod_size - 1) / max(pod_size, 1)
                if pod_size > 1 else 0.0)
        ag = (d / 8) * (data_size - 1) / data_size      # packed AG
        return {"ici": rs + ag, "dci": xpod,
                "n_collectives": 2 + (1 if pod_size > 1 else 0),
                "total": rs + xpod + ag}


STRATEGIES: Dict[VoteStrategy, VoteStrategyImpl] = {
    VoteStrategy.PSUM_INT8: PsumInt8Strategy(),
    VoteStrategy.ALLGATHER_1BIT: Allgather1BitStrategy(),
    VoteStrategy.HIERARCHICAL: HierarchicalStrategy(),
}


# ---------------------------------------------------------------------------
# strategy auto-selection
# ---------------------------------------------------------------------------


def select_strategy(n_params: int, data_size: int, pod_size: int = 1,
                    codec: str = "sign1bit") -> VoteStrategy:
    """Cheapest concrete strategy under the alpha-beta comm model for this
    mesh shape, parameter count and codec. Deterministic and static
    (compile-time); single-replica meshes degenerate to PSUM_INT8 (no wire
    traffic at all). Codec-aware (DESIGN.md §8): candidates are the
    codec's supported transports and the gathered exchange is priced at
    the codec's symbol width (2 bits/param for ``ternary2bit``), so AUTO
    under a wider codec tips toward the count wires earlier.
    """
    from repro.core import codecs as codecs_mod
    c = codecs_mod.get_codec(codec)
    candidates = c.supported_strategies
    if data_size * pod_size <= 1:
        return (VoteStrategy.PSUM_INT8
                if VoteStrategy.PSUM_INT8 in candidates else candidates[0])
    times = {}
    for k in candidates:
        s = STRATEGIES[k]
        b = s.ring_bytes(n_params, data_size, pod_size)
        # the gathered exchange is linear in the symbol width; the count
        # wires carry int8 counts whatever the codec symbols were
        scale = (c.bits_per_param / s.wire_bits_per_param
                 if k == VoteStrategy.ALLGATHER_1BIT else 1.0)
        times[k] = comm_model.collective_time(
            b["ici"] * scale, b["dci"] * scale,
            n_collectives=int(b["n_collectives"])).time_s
    return min(times, key=times.get)


def resolve_strategy(strategy: VoteStrategy, n_params: int,
                     data_size: int, pod_size: int = 1,
                     codec: str = "sign1bit") -> VoteStrategy:
    if strategy == VoteStrategy.AUTO:
        return select_strategy(n_params, data_size, pod_size, codec)
    return strategy


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class VoteEngine:
    """LEGACY pack -> exchange -> tally -> unpack, behind one object.

    Every vote method on this class is now a deprecation shim over the
    declarative vote API (DESIGN.md §10): it builds a
    :class:`~repro.core.vote_api.VoteRequest` from the engine's fields
    and executes it on a :class:`~repro.core.vote_api.MeshBackend`
    (``vote_stacked``: a :class:`~repro.core.vote_api.VirtualBackend`).
    The strategy registry (:data:`STRATEGIES`), the stage methods and
    the AUTO selector remain the wire's real implementation — only the
    imperative entry-point surface is deprecated.

    `axes` are the manual mesh axes the vote runs over (empty = the M=1
    single-process degenerate case where the vote is the local sign).
    `byz` compiles the Byzantine adversary models into the pack stage, so
    fault injection perturbs exactly the tensors the trainer votes on.
    `strategy` may be ``VoteStrategy.AUTO``; it resolves per tree against
    the comm cost model (needs the axis sizes, i.e. a trace context).
    `salt` namespaces the adversary PRNG stream (the Scenario Lab folds a
    scenario-id hash in here — DESIGN.md §7); pass `step` to the vote
    entry points so stochastic adversaries redraw each step.
    `codec` selects the gradient codec (DESIGN.md §8): what the workers
    encode onto the wire and how the tally decodes it. The default
    ``sign1bit`` is the paper's raw-sign majority and keeps every legacy
    entry point bit-identical; stateful codecs (``weighted_vote``) thread
    their server state through the ``*_codec`` entry points.
    """

    strategy: VoteStrategy
    axes: Tuple[str, ...] = ()
    byz: Optional[ByzantineConfig] = None
    salt: int = 0
    codec: str = "sign1bit"

    def _backend(self):
        from repro.core import vote_api as va
        return va.MeshBackend(axes=self.axes)

    def _codec(self):
        from repro.core import codecs as codecs_mod
        return codecs_mod.get_codec(self.codec)

    # ---- voting (deprecation shims over the vote API) ----

    def vote_signs(self, signs: jax.Array) -> jax.Array:
        """DEPRECATED shim: int8 signs (..., n) -> int8 majority, no
        adversary (the engine's compiled model applies in :meth:`vote`,
        not here)."""
        from repro.core import vote_api as va
        va.warn_legacy("VoteEngine.vote_signs")
        return self._backend().execute(va.VoteRequest(
            payload=signs, form="leaf", strategy=self.strategy,
            codec=self.codec, salt=self.salt)).votes

    def vote_signs_codec(self, signs: jax.Array, server_state=None):
        """DEPRECATED shim: int8 signs -> (int8 majority, new server
        state), no adversary."""
        from repro.core import vote_api as va
        va.warn_legacy("VoteEngine.vote_signs_codec")
        out = self._backend().execute(va.VoteRequest(
            payload=signs, form="leaf", strategy=self.strategy,
            codec=self.codec, salt=self.salt, server_state=server_state))
        return out.votes, out.server_state

    def vote_codec(self, values: jax.Array,
                   step: Optional[jax.Array] = None, server_state=None):
        """DEPRECATED shim: replica-local real tensor -> (majority in
        the input dtype, new server state), through the engine's
        compiled adversary and codec wire."""
        from repro.core import vote_api as va
        va.warn_legacy("VoteEngine.vote_codec")
        out = self._backend().execute(va.VoteRequest(
            payload=values, form="leaf", strategy=self.strategy,
            codec=self.codec, failures=va.FailureSpec(byz=self.byz),
            step=step, salt=self.salt, server_state=server_state))
        return out.votes, out.server_state

    def vote(self, values: jax.Array,
             step: Optional[jax.Array] = None) -> jax.Array:
        """DEPRECATED shim: replica-local real tensor -> majority of
        signs, in the input dtype."""
        from repro.core import vote_api as va
        va.warn_legacy("VoteEngine.vote")
        return self._backend().execute(va.VoteRequest(
            payload=values, form="leaf", strategy=self.strategy,
            codec=self.codec, failures=va.FailureSpec(byz=self.byz),
            step=step, salt=self.salt)).votes

    def vote_tree(self, tree, step: Optional[jax.Array] = None):
        """DEPRECATED shim: vote every leaf of a pytree; ±1 tree in the
        leaf dtypes. AUTO resolves once per tree (codec-aware, which for
        the default ``sign1bit`` codec is the historical resolution)."""
        from repro.core import vote_api as va
        va.warn_legacy("VoteEngine.vote_tree")
        return self._backend().execute(va.VoteRequest(
            payload=tree, form="tree", strategy=self.strategy,
            codec=self.codec, failures=va.FailureSpec(byz=self.byz),
            step=step, salt=self.salt)).votes

    def vote_tree_codec(self, tree, step: Optional[jax.Array] = None,
                        server_state=None):
        """DEPRECATED shim: codec-aware tree vote -> (±1 tree, new
        server state)."""
        from repro.core import vote_api as va
        va.warn_legacy("VoteEngine.vote_tree_codec")
        out = self._backend().execute(va.VoteRequest(
            payload=tree, form="tree", strategy=self.strategy,
            codec=self.codec, failures=va.FailureSpec(byz=self.byz),
            step=step, salt=self.salt, server_state=server_state))
        return out.votes, out.server_state

    def vote_stacked(self, stacked: jax.Array,
                     use_kernels: bool = True) -> jax.Array:
        """DEPRECATED shim: (M, n) host-local stacked values -> (n,)
        int8 majority on the gathered 1-bit wire (ties -> +1), fused
        Pallas kernel when `use_kernels`."""
        from repro.core import vote_api as va
        va.warn_legacy("VoteEngine.vote_stacked")
        return va.VirtualBackend(use_kernels=use_kernels).execute(
            va.VoteRequest(payload=stacked, form="stacked",
                           strategy=VoteStrategy.ALLGATHER_1BIT)).votes

    # ---- accounting ----

    def comm_bytes(self, n_params: int, data_size: int, pod_size: int = 1,
                   grad_bytes: int = 2) -> Dict[str, float]:
        """Analytic per-chip collective bytes for one vote vs a dense
        all-reduce of the same gradient (ring terms). Codec-aware: the
        gathered exchange scales with the codec's symbol width."""
        strat = STRATEGIES[resolve_strategy(
            self.strategy, n_params, data_size, pod_size, codec=self.codec)]
        d = float(n_params)
        m = data_size * pod_size
        dense = 2 * d * grad_bytes * (m - 1) / m        # ring all-reduce
        vote = strat.ring_bytes(n_params, data_size, pod_size)["total"]
        if strat.kind == VoteStrategy.ALLGATHER_1BIT:
            vote *= self._codec().bits_per_param / strat.wire_bits_per_param
        return {"dense_allreduce": dense, "vote": vote,
                "ratio": dense / vote if vote else float("inf")}
