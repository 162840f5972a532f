"""Public jit'd wrappers around the Pallas kernels.

Handle arbitrary 1-D/N-D inputs (pad + reshape to the kernels' tiled 2-D
layout). The kernels compile for the TPU; on the CPU backend (tests) they
run in Pallas interpret mode, and any other backend is an error.

Every wrapper counts its invocations under ``kernel.launches.<name>``
in the global :data:`repro.obs.COUNTERS` registry (one wrapper call =
one ``pallas_call`` in the lowered program, so inside ``jit`` the count
taken at trace time equals launches per execution). The VotePlan
benchmark (``benchmarks/bench_vote_plan.py``) reads these counters to
prove the bucketed path issues one fused-kernel launch per bucket where
the leaf-wise path launched once per tensor. :data:`LAUNCHES` remains
as a deprecation shim over the registry; `launch_counts` /
`reset_launch_counts` are the supported surface.
"""
from __future__ import annotations

import functools
from collections.abc import Mapping
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import (bitpack as _bp, fused_vote as _fv,
                           signum_update as _su, ternary_pack as _tp,
                           vote as _vt)
from repro.obs.recorder import COUNTERS, warn_deprecated

PACK = 32
PACK2 = 16
TILE = 8 * 128 * PACK  # elements per (ROWS, WORDS*32) block
TILE2 = 8 * 128 * PACK2  # elements per (ROWS, WORDS*16) ternary block

#: the registry namespace of the kernel-launch counters
LAUNCH_PREFIX = "kernel.launches."


def _launch(name: str) -> None:
    COUNTERS.inc(LAUNCH_PREFIX + name)


def reset_launch_counts() -> None:
    COUNTERS.reset(LAUNCH_PREFIX)


def launch_counts() -> Dict[str, int]:
    return {k[len(LAUNCH_PREFIX):]: v
            for k, v in COUNTERS.snapshot(LAUNCH_PREFIX).items()}


class _LaunchShim(Mapping):
    """DEPRECATED Counter-alike view of the ``kernel.launches.*``
    registry namespace (the old module-global). Reads/writes go straight
    through to :data:`repro.obs.COUNTERS`, so the cross-run clobber
    hazard of a second mutable accounting surface is gone."""

    def __getitem__(self, name: str) -> int:
        warn_deprecated("kernels.ops.LAUNCHES",
                        "read repro.obs.COUNTERS (kernel.launches.*) or "
                        "ops.launch_counts()")
        return COUNTERS.get(LAUNCH_PREFIX + name)

    def __setitem__(self, name: str, value: int) -> None:
        warn_deprecated("kernels.ops.LAUNCHES",
                        "read repro.obs.COUNTERS (kernel.launches.*) or "
                        "ops.launch_counts()")
        COUNTERS.set(LAUNCH_PREFIX + name, int(value))

    def __iter__(self):
        return iter(launch_counts())

    def __len__(self) -> int:
        return len(launch_counts())

    def clear(self) -> None:
        reset_launch_counts()


#: DEPRECATED shim (see :class:`_LaunchShim`)
LAUNCHES = _LaunchShim()


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"the Pallas vote kernels compile for the TPU and interpret on "
            f"the CPU only; the default backend is {backend!r}")
    return backend == "cpu"


def _to_2d(flat: jax.Array) -> Tuple[jax.Array, int]:
    """Pad a 1-D array to a TILE multiple and reshape (rows, 4096)."""
    n = flat.shape[0]
    rem = (-n) % TILE
    if rem:
        flat = jnp.pad(flat, (0, rem))
    return flat.reshape(-1, 128 * PACK), n


def bitpack(x: jax.Array) -> jax.Array:
    """Any-shape real array -> (ceil(n/32),) uint32 of packed sign bits
    (padding bits are sign(0)=+1)."""
    _launch("bitpack")
    flat2d, n = _to_2d(x.reshape(-1))
    packed = _bp.bitpack_2d(flat2d, interpret=_interpret())
    return packed.reshape(-1)[: -(-n // PACK)]


def bitunpack(packed: jax.Array, n: int, dtype=jnp.float32) -> jax.Array:
    """(w,) uint32 -> (n,) ±1 `dtype` (first n of 32*w)."""
    _launch("bitunpack")
    w = packed.shape[0]
    rem = (-w) % (8 * 128)
    if rem:
        packed = jnp.pad(packed, (0, rem))
    out = _bp.bitunpack_2d(packed.reshape(-1, 128), dtype,
                           interpret=_interpret())
    return out.reshape(-1)[:n]


def fused_majority(x: jax.Array) -> jax.Array:
    """(M, n) real voter values -> (ceil(n/32),) uint32 packed majority in
    ONE pass (fused sign+bitpack+popcount; ties and padding -> sign(0)=+1)."""
    _launch("fused_majority")
    m, n = x.shape
    rem = (-n) % TILE
    if rem:
        x = jnp.pad(x, ((0, 0), (0, rem)))
    packed = _fv.fused_majority_2d(x, interpret=_interpret())
    return packed[: -(-n // PACK)]


def majority(packed: jax.Array) -> jax.Array:
    """(M, w) uint32 -> (w,) packed majority (ties -> +1)."""
    _launch("majority")
    m, w = packed.shape
    rem = (-w) % _vt.WBLOCK
    if rem:
        packed = jnp.pad(packed, ((0, 0), (0, rem)))
    return _vt.majority_packed(packed, interpret=_interpret())[:w]


def ternary_pack(s: jax.Array) -> jax.Array:
    """Any-shape ternary sign array -> (ceil(n/16),) uint32 of packed 2-bit
    symbols (padding fields are 0 = abstain)."""
    _launch("ternary_pack")
    flat = s.reshape(-1).astype(jnp.int32)
    n = flat.shape[0]
    rem = (-n) % TILE2
    if rem:
        flat = jnp.pad(flat, (0, rem))
    packed = _tp.ternary_pack_2d(flat.reshape(-1, 128 * PACK2),
                                 interpret=_interpret())
    return packed.reshape(-1)[: -(-n // PACK2)]


def ternary_unpack(packed: jax.Array, n: int, dtype=jnp.int8) -> jax.Array:
    """(w,) uint32 -> (n,) {-1,0,+1} `dtype` (first n of 16*w).

    Not counted in LAUNCHES: this wrapper lowers to the pure-jnp oracle,
    no pallas_call."""
    from repro.core import sign_compress as sc
    return sc.unpack_ternary(packed, dtype)[:n]


def ternary_majority(packed: jax.Array) -> jax.Array:
    """(M, w) uint32 packed ternary -> (w,) packed ternary majority
    (abstentions abstain, ties -> 0)."""
    _launch("ternary_majority")
    m, w = packed.shape
    rem = (-w) % _tp.WBLOCK
    if rem:
        packed = jnp.pad(packed, ((0, 0), (0, rem)))
    return _tp.ternary_tally_packed(packed, interpret=_interpret())[:w]


def momentum_sign_pack(g: jax.Array, m: jax.Array, beta: float
                       ) -> Tuple[jax.Array, jax.Array]:
    """Flat g/m (n,) -> (m_new (n,), packed (ceil(n/32),))."""
    _launch("momentum_sign_pack")
    n = g.shape[0]
    g2, _ = _to_2d(g)
    m2, _ = _to_2d(m)
    m_new, packed = _su.momentum_sign_pack(g2, m2, beta,
                                           interpret=_interpret())
    return m_new.reshape(-1)[:n], packed.reshape(-1)[: -(-n // PACK)]


def apply_vote(p: jax.Array, votes: jax.Array, eta: float,
               weight_decay: float) -> jax.Array:
    """Flat p (n,), votes (ceil(n/32),) packed -> updated p (n,)."""
    _launch("apply_vote")
    n = p.shape[0]
    p2, _ = _to_2d(p)
    w = votes.shape[0]
    rem = p2.shape[0] * 128 - w
    if rem:
        votes = jnp.pad(votes, (0, rem))
    out = _su.apply_vote(p2, votes.reshape(-1, 128), eta, weight_decay,
                         interpret=_interpret())
    return out.reshape(-1)[:n]
