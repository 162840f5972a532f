"""``ternary2bit`` — abstain-capable 2-bit packed wire.

The 1-bit wire's defect (DESIGN.md §5) is that it cannot say "no vote":
abstentions (a zero gradient — an expert no token routed to, a crashed
worker's zero substitute) binarise to +1 at pack time, and ties resolve
+1. The integer-count strategies keep abstention but pay 8 bits/param.
This codec is the middle point: ternary symbols {-1, 0, +1} packed 16 per
uint32 (2-bit two's complement, ``sign_compress.pack_ternary``), so the
gathered exchange costs 2 bits/param — 2× the paper's wire, 16× under
fp32 — while the decode keeps full ternary semantics: majority = sign of
the symbol sum, abstentions abstain, ties → 0 on every transport.

Transports: on ``allgather_1bit``'s exchange shape the packed ternary
words replace the packed sign bits (the 2-bit wire proper, tallied by the
``kernels/ternary_pack.py`` Pallas kernel on the stacked path); on
``psum_int8`` the ternary symbols ARE the counts the strategy already
sums, so that transport is untouched — and bit-identical to ``sign1bit``
over it, which ``tests/test_codecs.py`` pins. ``hierarchical`` is
excluded: its 1-bit rebroadcast would re-binarise the decision and
silently destroy exactly what this codec buys.

Stateless on both sides.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

from repro import compat
from repro.configs.base import VoteStrategy
from repro.core import sign_compress as sc
from repro.core.codecs.base import GradientCodec
from repro.obs.scopes import scope_stages


@scope_stages
class TernaryWire:
    """The 2-bit packed transport, shaped like a VoteStrategyImpl's four
    stages so the mesh engine composes them over collectives and the
    virtual mesh replays them over a stacked voter dim (exchange is the
    only stage either path swaps)."""

    wire_bits_per_param = 2.0
    ties = "zero"

    def pack(self, signs: jax.Array, n_voters: int) -> jax.Array:
        padded, _ = sc.pad_last(signs, sc.PACK2)
        return sc.pack_ternary(padded)

    def exchange(self, wire: jax.Array, axes: Sequence[str]) -> jax.Array:
        packed = wire
        for a in axes:   # gather over each vote axis; leading M dims stack
            packed = compat.all_gather(packed, a, tiled=False)
        return packed.reshape((-1,) + packed.shape[len(tuple(axes)):])

    def tally(self, arrived: jax.Array, n_voters: int) -> jax.Array:
        counts = jnp.sum(sc.unpack_ternary(arrived, jnp.int32), axis=0)
        return jnp.sign(counts).astype(jnp.int8)   # decoded, not re-packed

    def unpack(self, decision: jax.Array, n: int, dtype) -> jax.Array:
        return decision[..., :n].astype(dtype)

    def vote(self, signs: jax.Array, axes: Sequence[str]) -> jax.Array:
        from repro.core.vote_engine import num_voters
        m = num_voters(axes)
        n = signs.shape[-1]
        return self.unpack(
            self.tally(self.exchange(self.pack(signs, m), axes), m),
            n, jnp.int8)


TERNARY_WIRE = TernaryWire()


class Ternary2BitCodec(GradientCodec):
    name = "ternary2bit"
    bits_per_param = 2.0
    supported_strategies = (VoteStrategy.PSUM_INT8,
                            VoteStrategy.ALLGATHER_1BIT)

    def ties(self, strategy: VoteStrategy) -> str:
        return "zero"   # ternary symbols carry abstention on every wire
