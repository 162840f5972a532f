"""Pallas TPU kernel: fused sign-extraction + bit-pack + popcount majority.

The VoteEngine's single-pass local tally (DESIGN.md §2): given the M
voters' raw real-valued tensors — momenta in the host-local simulation
path, or the would-be wire payloads in the benchmarks — produce the packed
uint32 majority words directly. The separate ``bitpack`` (pack each voter)
and ``vote`` (popcount over packed words) kernels made M+1 passes over HBM
and materialised M packed intermediates; this kernel reads the (M, n)
source once and writes only the n/32-word decision:

    bits    = x >= 0                      (sign extraction, binary wire
                                           convention: ties -> +1)
    counts  = sum over M of bits          (bit-sliced popcount)
    maj     = 2*counts >= M
    words   = pack 32 maj bits per uint32 (little-endian within the word)

Bandwidth-bound by design: one read of the sign source, one
1/(32*M)-size write.

Block shapes: input (M, R, 4096) fp32/bf16 -> output (R, 128) uint32 per
grid step (the flat (M, n) source viewed as (M, n/4096, 4096)); R shrinks
as M grows so a block stays near 1 MiB, down to 8 rows. The counts
accumulate voter by voter, so the temporaries are one (R, 4096) row
block whatever M is. M is small (the vote runs over data-parallel
replicas, 4..32: 4 MiB f32 blocks at 8 rows); where the 8-row floor
needs more VMEM than the default limit, the kernel asks for it
(``bitpack.vmem_params``). The pack runs on the MXU (see ``bitpack``).

``kernels/ref.py`` (``ref.fused_majority``) is the correctness oracle;
``kernels/ops.fused_majority`` is the shape-handling public wrapper.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.bitpack import (PACK, WORDS, matrix_spec, pack_fields,
                                  pack_matrix, row_block, sign_bits,
                                  vmem_params)


def _fused_majority_kernel(x_ref, w_ref, out_ref, *, m_voters: int):
    counts = jax.lax.fori_loop(                       # (R, WORDS*32)
        1, m_voters, lambda v, c: c + sign_bits(x_ref[v]),
        sign_bits(x_ref[0]))
    maj = jnp.where(2 * counts >= m_voters, 1.0, 0.0)
    out_ref[...] = pack_fields(maj, w_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_majority_2d(x: jax.Array, *, interpret: bool = False) -> jax.Array:
    """x (M, n) real with n % 32768 == 0 -> (n // 32,) uint32 packed majority.

    bit j of word k encodes majority(x[:, 32*k + j] >= 0), ties -> +1.
    """
    m, n = x.shape
    rows = n // (WORDS * PACK)
    lanes = WORDS * PACK
    r = row_block(rows, m * lanes * x.dtype.itemsize)
    # double-buffered input and output, the one matrix buffer, and the
    # f32 counts, sign bits and bf16 MXU operand of one row block
    vmem = (2 * m * r * lanes * x.dtype.itemsize + 2 * r * WORDS * 4
            + lanes * 2 * WORDS * 2 + r * lanes * (4 + 4 + 2))
    out = pl.pallas_call(
        functools.partial(_fused_majority_kernel, m_voters=m),
        grid=(rows // r,),
        in_specs=[pl.BlockSpec((m, r, lanes), lambda i: (0, i, 0)),
                  matrix_spec(PACK)],
        out_specs=pl.BlockSpec((r, WORDS), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, WORDS), jnp.uint32),
        compiler_params=vmem_params(vmem),
        interpret=interpret,
    )(x.reshape(m, rows, lanes), pack_matrix(PACK, 1))
    return out.reshape(-1)
