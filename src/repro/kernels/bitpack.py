"""Pallas TPU kernels: sign bit-packing / unpacking (32 signs per uint32).

The TPU adaptation of the paper's CUDA bit-pack. Data is tiled into VMEM
as (rows, 32*WORDS) blocks, the trailing dim a multiple of 128 lanes.
Bit j of word k is element 32k+j, so the 32 bits of one word sit in 32
neighbouring lanes. Gathering them into one lane is a cross-lane move,
which Mosaic does not lower as a reshape of the lane dim. The packer
does it on the MXU instead: the 0/1 bits (bf16) times a constant 0/2^j
placement matrix (:func:`pack_matrix`) sums each word's low and high 16
bits into one f32 lane each, exactly (products and sums stay below
2^16). Two integer ops join the halves. The VPU only compares and
converts; the read of the sign source stays the bound.

Block shapes: pack input (R, 4096) fp32/bf16 -> (R, 128) uint32 per
grid step, R up to 128 rows; unpack (8, 128) uint32 -> (8, 4096). The
2 MiB placement matrix is one constant block, fetched once and
single-buffered (:func:`matrix_spec`).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

PACK = 32
ROWS = 8
WORDS = 128  # output lane dim; input lane dim = 32*128 = 4096
#: bytes of one input block the row-block size aims for
BLOCK_BYTES = 1 << 20
#: Mosaic's default scoped-VMEM limit on v5e
SCOPED_VMEM = 16 << 20


def row_block(rows: int, row_bytes: int) -> int:
    """Rows per grid step: a power-of-two divisor of `rows` (a multiple
    of 8) near BLOCK_BYTES of input, never below 8."""
    cap = max(ROWS, 1 << max(0, (BLOCK_BYTES // row_bytes).bit_length() - 1))
    return math.gcd(rows, min(cap, 256))


def vmem_params(vmem_bytes: int):
    """``compiler_params`` for a kernel whose blocks and temporaries take
    `vmem_bytes` of VMEM: None (the default limit) while that plus a
    quarter of headroom fits SCOPED_VMEM, else a limit that holds it, as
    when many voters push the 8-row floor of :func:`row_block` past it."""
    need = vmem_bytes * 5 // 4
    if need <= SCOPED_VMEM:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=need)


def matrix_spec(fields: int) -> pl.BlockSpec:
    """BlockSpec of :func:`pack_matrix` ``(fields, ...)``: the whole
    matrix as one block whose index never changes, so one buffer."""
    return pl.BlockSpec((fields * WORDS, 2 * WORDS), lambda *_: (0, 0),
                        pipeline_mode=pl.Buffered(1))


def pack_matrix(fields: int, width: int) -> jax.Array:
    """(fields*WORDS, 2*WORDS) bf16 placement matrix of the MXU packer.

    Row 'fields*k + j' (field j of word k) holds 2^(width*(j mod h)) in
    column k for the low h = fields/2 fields and in column WORDS+k for
    the high ones; every other entry is 0."""
    half = fields // 2
    p = jnp.arange(fields * WORDS, dtype=jnp.int32)[:, None]
    c = jnp.arange(2 * WORDS, dtype=jnp.int32)[None, :]
    k, j = p // fields, p % fields
    hit = (c % WORDS == k) & (c // WORDS == j // half)
    weight = jnp.left_shift(1, width * (j % half)).astype(jnp.float32)
    return jnp.where(hit, weight, 0.0).astype(jnp.bfloat16)


def pack_fields(sym: jax.Array, w: jax.Array) -> jax.Array:
    """In-kernel packer: sym (R, fields*WORDS) f32 field values in
    [0, 2^width), w = pack_matrix(fields, width) -> (R, WORDS) uint32,
    field j of word k at bits [width*j, width*(j+1))."""
    s = jnp.dot(sym.astype(jnp.bfloat16), w,
                preferred_element_type=jnp.float32)
    s = s.astype(jnp.int32).astype(jnp.uint32)
    return s[:, :WORDS] | (s[:, WORDS:] << jnp.uint32(16))


def sign_bits(x: jax.Array) -> jax.Array:
    """x >= 0 as 0.0/1.0 f32 (compared in f32: the v5e VPU has no bf16
    compare)."""
    return jnp.where(x.astype(jnp.float32) >= 0, 1.0, 0.0)


def _bitpack_kernel(x_ref, w_ref, out_ref):
    out_ref[...] = pack_fields(sign_bits(x_ref[...]), w_ref[...])


def _bitunpack_kernel(p_ref, out_ref, *, dtype):
    p = p_ref[...]                                   # (ROWS, WORDS)
    cols = []
    for j in range(PACK):
        bit = (p >> jnp.uint32(j)) & jnp.uint32(1)
        cols.append(jnp.where(bit == 1, 1, -1).astype(dtype))
    out = jnp.stack(cols, axis=-1)                   # (ROWS, WORDS, 32)
    out_ref[...] = out.reshape(p.shape[0], p.shape[1] * PACK)


@functools.partial(jax.jit, static_argnames=("interpret",))
def bitpack_2d(x: jax.Array, *, interpret: bool = False) -> jax.Array:
    """x (rows, 32*w) with rows % 8 == 0, w % 128 == 0 -> (rows, w) uint32."""
    rows, n = x.shape
    w = n // PACK
    r = row_block(rows, WORDS * PACK * x.dtype.itemsize)
    return pl.pallas_call(
        _bitpack_kernel,
        grid=(rows // r, w // WORDS),
        in_specs=[pl.BlockSpec((r, WORDS * PACK), lambda i, j: (i, j)),
                  matrix_spec(PACK)],
        out_specs=pl.BlockSpec((r, WORDS), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rows, w), jnp.uint32),
        interpret=interpret,
    )(x, pack_matrix(PACK, 1))


@functools.partial(jax.jit, static_argnames=("dtype", "interpret"))
def bitunpack_2d(p: jax.Array, dtype=jnp.float32, *,
                 interpret: bool = False) -> jax.Array:
    """p (rows, w) uint32 -> (rows, 32*w) ±1 in `dtype`."""
    rows, w = p.shape
    grid = (rows // ROWS, w // WORDS)
    return pl.pallas_call(
        functools.partial(_bitunpack_kernel, dtype=dtype),
        grid=grid,
        in_specs=[pl.BlockSpec((ROWS, WORDS), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((ROWS, WORDS * PACK), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rows, w * PACK), dtype),
        interpret=interpret,
    )(p)
