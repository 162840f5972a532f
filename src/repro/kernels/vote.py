"""Pallas TPU kernel: popcount majority vote over packed sign words.

The "server" inner loop of the paper-faithful ``allgather_1bit`` strategy:
after the packed all-gather every chip holds (M, w) uint32 words and must
produce the (w,) packed majority. Bit-sliced counting: for each of the 32
bit positions, count set bits across the M voters (vectorised over the
word/lane dim), compare against M/2, re-pack. No unpacking to float ever
touches HBM — the whole vote is integer VPU work on VMEM tiles.

Block shape: (M, R, 128) words -> (R, 128) per grid step, the (M, w)
words viewed as (M, w/128, 128) so no lane row is ever reshaped in the
kernel (M is small — the vote runs over data-parallel replicas, 4..32 —
so a whole voter column fits VMEM).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.bitpack import PACK, WORDS, row_block

#: words per padding unit: 8 rows of 128 lanes
WBLOCK = 8 * WORDS


def _majority_kernel(p_ref, out_ref, *, m_voters: int):
    p = p_ref[...]                                    # (M, R, WORDS) uint32
    acc = jnp.zeros(p.shape[1:], jnp.uint32)
    for j in range(PACK):                             # bit-sliced count
        bits = (p >> jnp.uint32(j)) & jnp.uint32(1)
        cnt = jnp.sum(bits.astype(jnp.int32), axis=0)  # (R, WORDS)
        maj = (2 * cnt >= m_voters).astype(jnp.uint32)
        acc = acc | (maj << jnp.uint32(j))
    out_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("interpret",))
def majority_packed(packed: jax.Array, *, interpret: bool = False
                    ) -> jax.Array:
    """packed (M, w) uint32, w % 1024 == 0 -> (w,) packed majority."""
    m, w = packed.shape
    rows = w // WORDS
    r = row_block(rows, m * WORDS * 4)
    out = pl.pallas_call(
        functools.partial(_majority_kernel, m_voters=m),
        grid=(rows // r,),
        in_specs=[pl.BlockSpec((m, r, WORDS), lambda i: (0, i, 0))],
        out_specs=pl.BlockSpec((r, WORDS), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, WORDS), jnp.uint32),
        interpret=interpret,
    )(packed.reshape(m, rows, WORDS))
    return out.reshape(-1)
