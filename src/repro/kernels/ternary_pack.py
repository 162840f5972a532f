"""Pallas TPU kernels: ternary 2-bit packing and field-sliced tally.

The ``ternary2bit`` codec's wire (DESIGN.md §8): 16 ternary symbols per
uint32 word, 2-bit two's-complement fields, little-endian within the word
(+1 → 0b01, -1 → 0b11, 0/abstain → 0b00 — the layout of
``sign_compress.pack_ternary``, which is these kernels' oracle).

* ``ternary_pack_2d`` — pack a block of int32 ternary signs, 16 fields
  per word. Like ``bitpack``, the fields are gathered on the MXU
  (2-bit fields in place of 1-bit ones); bandwidth-bound, 1 read of the
  symbol source and a 1/16-size write.
* ``ternary_tally_packed`` — the "server" inner loop after the packed
  all-gather: (M, w) packed words -> (w,) packed ternary majority.
  Field-sliced: for each of the 16 fields, sign-extend across the M
  voters, sum, take the sign of the count (abstentions abstain, exact
  ties -> 0 — the integer-count tie convention, unlike the 1-bit wire's
  ties -> +1), re-pack. No unpacked ±1 tensor ever touches HBM.

Block shapes: pack input (R, 2048) int32 -> (R, 128) uint32 per grid
step; tally (M, R, 128) words -> (R, 128), the (M, w) words viewed as
(M, w/128, 128) (M is small — data-parallel replicas, 4..32 — so a whole
voter column fits VMEM).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.bitpack import (WORDS, matrix_spec, pack_fields,
                                  pack_matrix, row_block)

PACK2 = 16    # input lane dim = 16*128 = 2048
#: words per padding unit: 8 rows of 128 lanes
WBLOCK = 8 * WORDS


def _ternary_pack_kernel(s_ref, w_ref, out_ref):
    sym = (s_ref[...] & 0x3).astype(jnp.float32)    # 2-bit two's complement
    out_ref[...] = pack_fields(sym, w_ref[...])


def _ternary_tally_kernel(p_ref, out_ref):
    p = p_ref[...]                                   # (M, R, WORDS) uint32
    acc = jnp.zeros(p.shape[1:], jnp.uint32)
    for j in range(PACK2):                           # field-sliced count
        f = (p >> jnp.uint32(2 * j)) & jnp.uint32(0x3)
        s = jnp.where(f == 1, 1, jnp.where(f == 3, -1, 0))   # int32
        cnt = jnp.sum(s, axis=0)                     # (R, WORDS)
        maj = jnp.where(cnt > 0, jnp.uint32(1),
                        jnp.where(cnt < 0, jnp.uint32(3), jnp.uint32(0)))
        acc = acc | (maj << jnp.uint32(2 * j))
    out_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("interpret",))
def ternary_pack_2d(s: jax.Array, *, interpret: bool = False) -> jax.Array:
    """s (rows, 16*w) int32 in {-1,0,+1}, rows % 8 == 0, w % 128 == 0
    -> (rows, w) uint32."""
    rows, n = s.shape
    w = n // PACK2
    r = row_block(rows, WORDS * PACK2 * 4)
    return pl.pallas_call(
        _ternary_pack_kernel,
        grid=(rows // r, w // WORDS),
        in_specs=[pl.BlockSpec((r, WORDS * PACK2), lambda i, j: (i, j)),
                  matrix_spec(PACK2)],
        out_specs=pl.BlockSpec((r, WORDS), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rows, w), jnp.uint32),
        interpret=interpret,
    )(s, pack_matrix(PACK2, 2))


@functools.partial(jax.jit, static_argnames=("interpret",))
def ternary_tally_packed(packed: jax.Array, *, interpret: bool = False
                         ) -> jax.Array:
    """packed (M, w) uint32, w % 1024 == 0 -> (w,) packed ternary majority."""
    m, w = packed.shape
    rows = w // WORDS
    r = row_block(rows, m * WORDS * 4)
    out = pl.pallas_call(
        _ternary_tally_kernel,
        grid=(rows // r,),
        in_specs=[pl.BlockSpec((m, r, WORDS), lambda i: (0, i, 0))],
        out_specs=pl.BlockSpec((r, WORDS), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, WORDS), jnp.uint32),
        interpret=interpret,
    )(packed.reshape(m, rows, WORDS))
    return out.reshape(-1)
