"""Pallas TPU kernels: fused SIGNUM worker-side update loops.

The optimizer step is HBM-bandwidth-bound; unfused it makes 4+ passes over
parameter-sized buffers. Two fused kernels cut that to the minimum:

``momentum_sign_pack`` — m' = beta*m + (1-beta)*g, packed = pack(sign(m'))
    one read of (g, m), one write of (m', packed/32): the entire
    pre-vote worker computation in a single pass.

``apply_vote`` — x <- x - eta*(unpack(vote) + lambda*x)
    one read of (x, packed vote), one write of x: the post-vote update,
    decoding the 1-bit vote on the fly (never materialising the ±1
    tensor in HBM).

Scalars (beta/eta/lambda) are compile-time constants (closure), matching
how the training step specialises on the optimizer config.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.bitpack import (PACK, ROWS, WORDS, matrix_spec,
                                  pack_fields, pack_matrix, row_block,
                                  sign_bits)


def _momentum_sign_pack_kernel(g_ref, m_ref, w_ref, m_out_ref, p_out_ref, *,
                               beta: float):
    g = g_ref[...]
    m = m_ref[...]
    m_new = beta * m + (1.0 - beta) * g.astype(m.dtype)
    m_out_ref[...] = m_new
    p_out_ref[...] = pack_fields(sign_bits(m_new), w_ref[...])


@functools.partial(jax.jit, static_argnames=("beta", "interpret"))
def momentum_sign_pack(g: jax.Array, m: jax.Array, beta: float, *,
                       interpret: bool = False):
    """g/m (rows, 32*w) -> (m_new (rows, 32*w), packed (rows, w))."""
    rows, n = g.shape
    w = n // PACK
    r = row_block(rows, WORDS * PACK * (g.dtype.itemsize + m.dtype.itemsize))
    return pl.pallas_call(
        functools.partial(_momentum_sign_pack_kernel, beta=beta),
        grid=(rows // r, w // WORDS),
        in_specs=[pl.BlockSpec((r, WORDS * PACK), lambda i, j: (i, j)),
                  pl.BlockSpec((r, WORDS * PACK), lambda i, j: (i, j)),
                  matrix_spec(PACK)],
        out_specs=[pl.BlockSpec((r, WORDS * PACK), lambda i, j: (i, j)),
                   pl.BlockSpec((r, WORDS), lambda i, j: (i, j))],
        out_shape=[jax.ShapeDtypeStruct((rows, n), m.dtype),
                   jax.ShapeDtypeStruct((rows, w), jnp.uint32)],
        interpret=interpret,
    )(g, m, pack_matrix(PACK, 1))


def _apply_vote_kernel(p_ref, v_ref, out_ref, *, eta: float,
                       weight_decay: float):
    p = p_ref[...].astype(jnp.float32)                # (ROWS, WORDS*32)
    v = v_ref[...]                                    # (ROWS, WORDS) uint32
    cols = []
    for j in range(PACK):
        bit = (v >> jnp.uint32(j)) & jnp.uint32(1)
        cols.append(jnp.where(bit == 1, 1.0, -1.0))
    vote = jnp.stack(cols, axis=-1).reshape(p.shape)  # ±1 fp32
    out_ref[...] = (p - eta * (vote + weight_decay * p)).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("eta", "weight_decay",
                                             "interpret"))
def apply_vote(p: jax.Array, votes: jax.Array, eta: float,
               weight_decay: float, *, interpret: bool = False) -> jax.Array:
    """p (rows, 32*w), votes (rows, w) uint32 -> updated p."""
    rows, n = p.shape
    w = n // PACK
    grid = (rows // ROWS, w // WORDS)
    return pl.pallas_call(
        functools.partial(_apply_vote_kernel, eta=eta,
                          weight_decay=weight_decay),
        grid=grid,
        in_specs=[pl.BlockSpec((ROWS, WORDS * PACK), lambda i, j: (i, j)),
                  pl.BlockSpec((ROWS, WORDS), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((ROWS, WORDS * PACK), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rows, n), p.dtype),
        interpret=interpret,
    )(p, votes)
