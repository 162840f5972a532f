"""The streamed population engine (DESIGN.md §12).

``VirtualBackend`` executes a ``"streamed"`` :class:`~repro.core.
vote_api.VoteRequest` here: the stacked exchange runs in voter-chunks —
chunk -> effective signs -> pack -> **partial tally accumulate** — so
the voter count M decouples fully from host memory and device count. An
M in the 10^4–10^5 range votes with peak sign-buffer memory
O(chunk_size x n) instead of O(M x n).

Why the result is bit-identical to the dense stacked path *by
construction*: every wire this engine realises reduces the voter dim
with **exact integer arithmetic** —

* count wires (``psum_int8``; the ternary codec on either strategy):
  the decision is ``sign(sum_m s_m)`` — an integer sum, associative
  under any chunking.
* the gathered 1-bit wire (``allgather_1bit`` majority): the dense
  tally is per-bit-position *counts* (``Allgather1BitStrategy.tally``),
  again an integer sum; the majority threshold ``2*count >= M`` is
  applied once, on the final accumulated counts.
* dataset-weighted votes: integer weight times integer sign, summed in
  int32 per chunk / int64 across chunks (build-time guards keep every
  partial in range).
* the ``weighted_vote`` codec: its reliability weights are *defined*
  quantized to multiples of 1/256 (``codecs.weighted``), so the
  weighted sum is integer arithmetic at scale 256 — this engine
  accumulates exactly those integers. The EMA update runs once, on the
  assembled per-voter mismatch counts, with the same float expression
  as ``decode_stacked`` — and touches only the sampled ids.

Integer partial sums commute and associate exactly, so the chunk size
(and which rows land in which chunk) cannot change a single output bit
— asserted against the dense path by tests/test_population*.py across
codec x strategy, and chunk-size-invariance is drilled in tier 2.

``hierarchical`` is rejected: its reduce-scatter wire pads the
coordinate buffer to ``PACK * M`` words — an O(M) layout this engine
exists to avoid.

Chunk accounting lives in the global :data:`repro.obs.COUNTERS`
registry (DESIGN.md §13): cumulative ``population.chunks`` /
``population.passes``, high-water ``population.peak_rows``, and the
most recent run's gauges under ``population.last.*`` — the federated
benchmark's memory-bound row reads those, mirroring the kernel-launch
counters in ``kernels.ops``. The old ``LAST_STATS`` module-global
remains as a deprecation shim reading the registry; unlike the mutable
dict it replaced, concurrent requests in one process can no longer
clobber each other's accounting mid-read (each run publishes its
gauges atomically at the end of ``streamed_vote``).
"""
from __future__ import annotations

import functools
import math
from collections.abc import Mapping
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ByzantineConfig, VoteStrategy
from repro.core import sign_compress as sc
from repro.core import vote_api as va
from repro.core.codecs import weighted
from repro.obs.recorder import COUNTERS, warn_deprecated

#: default voter-chunk size (rows materialized at once)
DEFAULT_CHUNK = 2048

#: largest |reliability weight| * 256 the weighted_vote codec can emit
#: (P_MIN-clipped log-odds at the codec's own 1/256 quantization)
W256_CAP = int(round(math.log((1.0 - weighted.P_MIN) / weighted.P_MIN)
                     * 256.0))

#: the registry namespace of the streamed engine's counters
STATS_PREFIX = "population."

_STAT_KEYS = ("n_voters", "peak_rows", "n_chunks", "n_passes")


def _publish_stats(stats: Dict[str, int]) -> None:
    """Publish one run's chunk accounting to the registry: last-run
    gauges under ``population.last.*`` plus the cumulative/high-water
    process counters."""
    for k in _STAT_KEYS:
        COUNTERS.set(STATS_PREFIX + "last." + k, stats[k])
    COUNTERS.inc(STATS_PREFIX + "chunks", stats["n_chunks"])
    COUNTERS.inc(STATS_PREFIX + "passes", stats["n_passes"])
    COUNTERS.inc(STATS_PREFIX + "votes")
    COUNTERS.record_max(STATS_PREFIX + "peak_rows", stats["peak_rows"])


class _LastStatsShim(Mapping):
    """DEPRECATED read-only view of the most recent run's chunk
    accounting (``population.last.*`` in :data:`repro.obs.COUNTERS`) —
    keeps old readers of the ``LAST_STATS`` module-global working while
    the registry is the single source of truth."""

    def __getitem__(self, key: str) -> int:
        if key not in _STAT_KEYS:
            raise KeyError(key)
        warn_deprecated("population.LAST_STATS",
                        "read repro.obs.COUNTERS (population.last.*)")
        return COUNTERS.get(STATS_PREFIX + "last." + key)

    def __iter__(self):
        return iter(_STAT_KEYS)

    def __len__(self) -> int:
        return len(_STAT_KEYS)


#: DEPRECATED shim over the registry (see :class:`_LastStatsShim`)
LAST_STATS = _LastStatsShim()

_CODECS = ("sign1bit", "ef_sign", "ternary2bit", "weighted_vote")


# ---------------------------------------------------------------------------
# jitted per-chunk stages (two compiled shapes each: chunk + ragged tail)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("n_stale", "byz"))
def _chunk_eff(values, prev, ids, step, salt, obs, *, n_stale, byz):
    """Chunk values -> the (k, n) int8 signs that reach the wire, with
    failure predicates and adversary PRNG keyed by the LOGICAL ids.
    `salt` is traced (it only offsets a PRNG seed), so two scenarios
    that differ only in name share one compilation per chunk shape.
    `obs` (traced, possibly None) is the adaptive adversary's
    observation dict — per-chunk rows see the SAME full observation, so
    chunking cannot change an adaptive adversary's behaviour."""
    return va.effective_stacked_signs(values, prev, n_stale, byz, step,
                                      salt, ids=ids, obs=obs)


@jax.jit
def _partial_counts(eff):
    """Count-wire partial: integer sum of ternary signs over the chunk."""
    return jnp.sum(eff.astype(jnp.int32), axis=0)                 # (n,)


@jax.jit
def _partial_bit_counts(eff):
    """Gathered-1-bit partial: per-bit-position set-bit counts of the
    chunk's packed wire words. Bit 32w+j of the wire is eff >= 0 (an
    abstention binarized to +1), so the counts are taken on the signs."""
    padded, _ = va.pad_last(eff, sc.PACK)
    return jnp.sum(padded >= 0, axis=0, dtype=jnp.int32).reshape(-1, sc.PACK)


@jax.jit
def _wire_signs_1bit(eff):
    """What the 1-bit wire delivers for the chunk: pack/unpack round
    trip, abstentions binarized to +1, padding lanes cropped."""
    n = eff.shape[-1]
    padded, _ = va.pad_last(eff, sc.PACK)
    return sc.unpack_signs(sc.pack_signs(padded), jnp.int8)[:, :n]


@jax.jit
def _partial_weighted_counts(eff, w):
    """Weighted count-wire partial (w int32, |w*k| guarded in range)."""
    return jnp.sum(w[:, None] * eff.astype(jnp.int32), axis=0)    # (n,)


@jax.jit
def _partial_weighted_wire(eff, w):
    """Weighted gathered-1-bit partial: weights times the signs the
    wire actually delivered."""
    s_wire = _wire_signs_1bit(eff)
    return jnp.sum(w[:, None] * s_wire.astype(jnp.int32), axis=0)  # (n,)


@jax.jit
def _chunk_mismatch(eff, vote):
    """Per-voter mismatch counts of the chunk's wire signs vs the final
    vote (the weighted_vote codec's flip-rate observation)."""
    s_wire = _wire_signs_1bit(eff)
    return jnp.sum((s_wire != vote[None]).astype(jnp.float32), axis=1)


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------


def _validate(stream, strategy: VoteStrategy, codec: str,
              chunk_size: int, server_state) -> None:
    if strategy == VoteStrategy.HIERARCHICAL:
        raise ValueError(
            "hierarchical's reduce-scatter wire pads to PACK*M words — "
            "O(M) layout the streamed engine exists to avoid; use "
            "psum_int8 or allgather_1bit")
    if strategy not in (VoteStrategy.PSUM_INT8,
                        VoteStrategy.ALLGATHER_1BIT):
        raise ValueError(f"streamed engine cannot realise {strategy!r}")
    if codec not in _CODECS:
        raise ValueError(f"streamed engine cannot realise codec "
                         f"{codec!r}; have {_CODECS}")
    if chunk_size < 1:
        raise ValueError(f"chunk_size must be >= 1, got {chunk_size}")
    max_w = (int(np.max(np.asarray(stream.weights)))
             if stream.weights is not None else 1)
    # int32 partial-tally headroom: |per-chunk sum| <= chunk * max
    # per-term magnitude (reliability weights add a factor W256_CAP)
    max_mag = max_w * (W256_CAP if codec == "weighted_vote" else 1)
    if chunk_size * max_mag >= 2 ** 31:
        raise ValueError(
            f"chunk_size={chunk_size} x max per-voter weight magnitude "
            f"{max_mag} overflows the int32 partial tally; reduce "
            "chunk_size or the dataset weights")
    if codec == "weighted_vote":
        if not server_state or "flip_ema" not in server_state:
            raise ValueError(
                "codec 'weighted_vote' needs server_state['flip_ema'] "
                "over the LOGICAL population (init_server_state(pop))")
        pop = int(server_state["flip_ema"].shape[0])
        ids = stream.row_ids()
        if ids.size and int(ids[-1]) >= pop:
            raise ValueError(
                f"stream ids reach logical voter {int(ids[-1])} but "
                f"server_state['flip_ema'] covers only {pop} clients; "
                "refit it to the population size "
                "(checkpoint.refit_tree_leading_axis)")


def _chunks(stream, chunk_size: int):
    ids_all = stream.row_ids()
    for lo in range(0, stream.n_voters, chunk_size):
        yield lo, ids_all[lo:lo + chunk_size]


def _chunk_signs(stream, ids_np, step, n_stale, byz, salt, obs=None):
    """Materialize ONE chunk's effective wire signs ((k, n) int8)."""
    k, n = len(ids_np), stream.n_coords
    ids = jnp.asarray(ids_np, dtype=jnp.int32)
    vals = stream.values(ids)
    if tuple(vals.shape) != (k, n):
        raise ValueError(f"stream.values returned shape "
                         f"{tuple(vals.shape)} for a {k}-id chunk, want "
                         f"({k}, {n})")
    prev = None
    if n_stale and stream.prev is not None:
        prev = stream.prev(ids)
        if tuple(prev.shape) != (k, n):
            raise ValueError(f"stream.prev returned shape "
                             f"{tuple(prev.shape)} for a {k}-id chunk, "
                             f"want ({k}, {n})")
    return _chunk_eff(vals, prev, ids, step, jnp.int32(salt), obs,
                      n_stale=n_stale, byz=byz)


def streamed_vote(stream, *, strategy: VoteStrategy, codec: str,
                  n_stale: int = 0,
                  byz: Optional[ByzantineConfig] = None,
                  step=None, salt: int = 0,
                  server_state: Optional[Dict[str, Any]] = None,
                  chunk_size: int = DEFAULT_CHUNK,
                  attack_obs: Optional[Dict[str, Any]] = None
                  ) -> Tuple[jax.Array, Dict[str, Any], float,
                             np.ndarray]:
    """Run one majority vote over a :class:`~repro.core.vote_api.
    PopulationStream` in voter-chunks.

    Returns ``(votes, new_server_state, margin, counts)`` — votes (n,)
    int8, bit-identical to the dense stacked path on the same request;
    margin is the mean |tally| normalized by the total vote weight
    (measured on the wire signs, the §7 diagnostic at population
    scale); counts is the per-coordinate signed tally ((n,) int64, at
    the wire's own weight scale) — the attack engine's ``margin``
    observation channel, returned because the stack is never
    materialized and no caller could recompute it. ``attack_obs`` is
    the adaptive adversary's observation dict (DESIGN.md §15), fed
    whole to every chunk so chunking cannot change adaptive behaviour."""
    _validate(stream, strategy, codec, chunk_size, server_state)
    state = dict(server_state) if server_state else {}
    m, n = stream.n_voters, stream.n_coords
    weights = (None if stream.weights is None
               else np.asarray(stream.weights, dtype=np.int64))
    stats = {"n_voters": m, "peak_rows": 0, "n_chunks": 0, "n_passes": 1}

    def eff_of(ids_np):
        stats["peak_rows"] = max(stats["peak_rows"], len(ids_np))
        stats["n_chunks"] += 1
        return _chunk_signs(stream, ids_np, step, n_stale, byz, salt,
                            obs=attack_obs)

    if codec == "weighted_vote":
        votes, state, margin, counts = _weighted_codec_vote(
            stream, weights, state, chunk_size, eff_of, stats)
    elif weights is not None:
        votes, margin, counts = _data_weighted_vote(
            stream, strategy, codec, weights, chunk_size, eff_of)
    elif (strategy == VoteStrategy.PSUM_INT8 or codec == "ternary2bit"):
        # count wires: psum sums ternary counts directly; the 2-bit
        # ternary wire carries the same counts through a gather
        acc = np.zeros(n, dtype=np.int64)
        for lo, ids_np in _chunks(stream, chunk_size):
            acc += np.asarray(_partial_counts(eff_of(ids_np)),
                              dtype=np.int64)
        votes = jnp.sign(jnp.asarray(acc)).astype(jnp.int8)
        margin = float(np.mean(np.abs(acc)) / m)
        counts = acc
    else:
        # gathered 1-bit wire: accumulate per-bit-position counts, then
        # apply the dense tally's majority threshold once
        w_words = (n + sc.PACK - 1) // sc.PACK
        acc = np.zeros((w_words, sc.PACK), dtype=np.int64)
        for lo, ids_np in _chunks(stream, chunk_size):
            acc += np.asarray(_partial_bit_counts(eff_of(ids_np)),
                              dtype=np.int64)
        votes = jnp.asarray(np.where(2 * acc >= m, 1, -1).reshape(-1)[:n],
                            jnp.int8)
        # +1-count c -> signed count 2c - M, over the true n coords
        counts = 2 * acc.reshape(-1)[:n] - m
        margin = float(np.mean(np.abs(counts)) / m)

    _publish_stats(stats)
    return votes, state, margin, counts


def _data_weighted_vote(stream, strategy, codec, weights, chunk_size,
                        eff_of):
    """Dataset-weighted plain codecs: each voter casts weight-many
    identical votes on its wire (mirrors _virtual_data_weighted_vote)."""
    n = stream.n_coords
    gathered_binary = (strategy == VoteStrategy.ALLGATHER_1BIT
                       and codec != "ternary2bit")
    partial = (_partial_weighted_wire if gathered_binary
               else _partial_weighted_counts)
    acc = np.zeros(n, dtype=np.int64)
    for lo, ids_np in _chunks(stream, chunk_size):
        w = jnp.asarray(weights[lo:lo + len(ids_np)], dtype=jnp.int32)
        acc += np.asarray(partial(eff_of(ids_np), w), dtype=np.int64)
    if gathered_binary:
        votes = jnp.where(jnp.asarray(acc) >= 0, jnp.int8(1),
                          jnp.int8(-1))
    else:
        votes = jnp.sign(jnp.asarray(acc)).astype(jnp.int8)
    margin = float(np.mean(np.abs(acc)) / float(np.sum(weights)))
    return votes, margin, acc


def _weighted_codec_vote(stream, weights, state, chunk_size, eff_of,
                         stats):
    """The weighted_vote codec over a streamed population: two passes —
    (1) accumulate the reliability-weighted (x data-weighted) sum at the
    codec's own 1/256 integer quantization, (2) observe per-voter
    mismatch vs the final vote and EMA-update ONLY the sampled ids."""
    m, n = stream.n_voters, stream.n_coords
    ema = jnp.asarray(state["flip_ema"])
    ids_all = stream.row_ids()
    # the codec's weights are multiples of 1/256 BY DEFINITION
    # (codecs.weighted.reliability_weights), so w*256 is exact int32
    w256_full = jnp.round(weighted.reliability_weights(ema)
                          * 256.0).astype(jnp.int32)          # (pop,)
    acc = np.zeros(n, dtype=np.int64)
    wtot = 0
    for lo, ids_np in _chunks(stream, chunk_size):
        w = w256_full[jnp.asarray(ids_np, dtype=jnp.int32)]
        if weights is not None:
            w = w * jnp.asarray(weights[lo:lo + len(ids_np)],
                                dtype=jnp.int32)
        acc += np.asarray(_partial_weighted_wire(eff_of(ids_np), w),
                          dtype=np.int64)
        wtot += int(np.sum(np.abs(np.asarray(w, dtype=np.int64))))
    vote = jnp.where(jnp.asarray(acc) >= 0, jnp.int8(1), jnp.int8(-1))

    # pass 2: the flip-rate observation needs the final vote, so the
    # stream is walked again (chunks regenerate deterministically)
    stats["n_passes"] += 1
    mis = np.zeros(m, dtype=np.float32)
    for lo, ids_np in _chunks(stream, chunk_size):
        mis[lo:lo + len(ids_np)] = np.asarray(
            _chunk_mismatch(eff_of(ids_np), vote))
    idx = jnp.asarray(ids_all, dtype=jnp.int32)
    upd = ((1.0 - weighted.RHO) * ema[idx]
           + weighted.RHO * jnp.asarray(mis) / n)
    new_ema = ema.at[idx].set(upd)
    margin = float(np.mean(np.abs(acc)) / max(wtot, 1))
    return vote, {**state, "flip_ema": new_ema}, margin, acc


__all__ = ["DEFAULT_CHUNK", "LAST_STATS", "W256_CAP", "streamed_vote"]
