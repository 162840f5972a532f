"""Sign extraction and 1-bit packing (pure-jnp reference layer).

Two sign conventions coexist (DESIGN.md §5):

* ``sign_ternary`` — ``jnp.sign`` semantics, 0 maps to 0. Used by the
  integer-sum vote strategies; a zero gradient (e.g. an expert no local
  token routed to) *abstains* rather than voting +1.
* ``sign_binary``  — ``x >= 0 -> +1 else -1``. The 1-bit wire format of the
  paper: a packed bit can only encode two states.

Packing is 32 signs per uint32 word, little-endian within the word. The
ternary codec's 2-bit format (``pack_ternary``) stores 16 symbols per
uint32 — two's-complement 2-bit fields, so it can encode the abstention
the 1-bit wire cannot (DESIGN.md §8). The Pallas kernels in
``repro.kernels`` implement the same layouts; these jnp versions are
their oracles and the fallback path.

The 1-bit wire's arithmetic works on whole words; no intermediate holds
32 entries per word:

* ``pack_signs`` gathers each word's 32 neighbouring lanes in one matmul:
  the 0/1 bits (bf16) of every 128-lane block times a constant
  (128, 8) placement matrix sum each word's low and high 16 bits into one
  f32 each, exactly (every sum stays below 2^16); two integer ops join
  the halves. It is the arithmetic of ``kernels.bitpack.pack_matrix``.
* ``packed_majority`` counts the set bits of the M voters' words
  bit-sliced: ceil(log2(M+1)) uint32 count planes, each voter's word
  rippled in with ``^`` and ``&`` (past FAN_IN voters, blocks of voters
  side by side first), then a bitwise comparison of the planes against
  the static threshold ceil(M/2). Elementwise uint32 ops on (..., w)
  words only, no reduction.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

PACK = 32
#: lanes of one block of ``pack_signs``' placement matmul (4 words)
PACK_BLOCK = 128
#: voters ``packed_majority`` ripples in one after another; more are
#: summed in FAN_IN blocks side by side first
FAN_IN = 8
#: ternary symbols per uint32 word (2 bits each; codec ``ternary2bit``)
PACK2 = 16


def sign_ternary(x: jax.Array) -> jax.Array:
    return jnp.sign(x).astype(jnp.int8)


def sign_binary(x: jax.Array) -> jax.Array:
    return jnp.where(x >= 0, jnp.int8(1), jnp.int8(-1))


def pad_to_pack(flat: jax.Array, multiple: int = PACK) -> Tuple[jax.Array, int]:
    """Pad 1-D array to a multiple; returns (padded, original_len)."""
    n = flat.shape[0]
    rem = (-n) % multiple
    if rem:
        flat = jnp.pad(flat, (0, rem))
    return flat, n


def pad_last(x: jax.Array, multiple: int) -> Tuple[jax.Array, int]:
    """Zero-pad the LAST dim to a multiple; returns (padded, original_n).

    Delegates to the single canonical implementation in
    ``core.vote_api.pad_last`` (DESIGN.md §10), so every wire's pad
    semantics come from one function (lazy import: vote_api sits above
    this module)."""
    from repro.core.vote_api import pad_last as _impl
    return _impl(x, multiple)


def _placement() -> jax.Array:
    """(PACK_BLOCK, 2 * PACK_BLOCK // PACK) bf16: lane 32k+j of a block
    holds 2^(j mod 16) in column k (j < 16, the word's low half) or in
    column PACK_BLOCK//PACK + k (its high half); every other entry is 0."""
    words = PACK_BLOCK // PACK
    lane = np.arange(PACK_BLOCK)
    k, j = lane // PACK, lane % PACK
    m = np.zeros((PACK_BLOCK, 2 * words), np.float32)
    m[lane, np.where(j < PACK // 2, k, words + k)] = 2.0 ** (j % (PACK // 2))
    return jnp.asarray(m, jnp.bfloat16)


def pack_signs(x: jax.Array) -> jax.Array:
    """x (..., n) any real dtype, n % 32 == 0 -> uint32 (..., n // 32).

    bit j of word w encodes sign(x[..., 32*w + j]) >= 0. A last dim that
    is not a multiple of PACK_BLOCK is padded for the matmul and the
    padding's words cropped.
    """
    if x.shape[-1] % PACK != 0:
        # a bare assert here vanishes under `python -O`, silently packing
        # garbage from a misaligned reshape; callers either pre-pad
        # (pad_last / pad_to_pack) or get told exactly what they sent
        raise ValueError(
            f"pack_signs needs last dim % {PACK} == 0, got shape "
            f"{tuple(x.shape)}; pad with pad_to_pack/pad_last first")
    lead, n = x.shape[:-1], x.shape[-1]
    rem = (-n) % PACK_BLOCK
    if rem:
        x = jnp.pad(x, [(0, 0)] * len(lead) + [(0, rem)])
    blocks = (n + rem) // PACK_BLOCK
    # a select, not a cast of the predicate: the v5e compiler then makes
    # the bits inside the fusion that produces x (the momentum update)
    # instead of first copying x into another layout
    bits = jnp.where(x >= 0, 1.0, 0.0).astype(jnp.bfloat16)
    bits = bits.reshape(lead + (blocks, PACK_BLOCK))
    halves = jnp.dot(bits, _placement(), preferred_element_type=jnp.float32)
    halves = halves.astype(jnp.int32).astype(jnp.uint32)
    words = PACK_BLOCK // PACK
    packed = halves[..., :words] | (halves[..., words:] << jnp.uint32(16))
    return packed.reshape(lead + (blocks * words,))[..., :n // PACK]


def unpack_signs(packed: jax.Array, dtype=jnp.int8) -> jax.Array:
    """uint32 (..., w) -> (..., 32*w) of ±1 in `dtype`."""
    shifts = jnp.arange(PACK, dtype=jnp.uint32)
    bits = (packed[..., None] >> shifts) & jnp.uint32(1)
    signs = jnp.where(bits == 1, 1, -1).astype(dtype)
    return signs.reshape(packed.shape[:-1] + (packed.shape[-1] * PACK,))


def popcount(x: jax.Array) -> jax.Array:
    """Per-word population count of a uint32 array (SWAR)."""
    x = x.astype(jnp.uint32)
    x = x - ((x >> 1) & 0x55555555)
    x = (x & 0x33333333) + ((x >> 2) & 0x33333333)
    x = (x + (x >> 4)) & 0x0F0F0F0F
    return ((x * 0x01010101) >> 24).astype(jnp.int32)


def _add_planes(a, b, top: int):
    """Bit-sliced a + b: each a list of uint32 planes, least significant
    first; `top` is the largest value the sum can hold, so a carry out
    of the last plane is kept only where the sum needs the bit."""
    out, carry = [], None
    for i in range(max(len(a), len(b))):
        terms = [p[i] for p in (a, b) if i < len(p)]
        terms += [] if carry is None else [carry]
        if len(terms) == 1:
            out.append(terms[0])
            carry = None
        elif len(terms) == 2:
            x, y = terms
            out.append(x ^ y)
            carry = x & y
        else:
            x, y, c = terms
            t = x ^ y
            out.append(t ^ c)
            carry = (x & y) | (t & c)
    if carry is not None and len(out) < top.bit_length():
        out.append(carry)
    return out


def packed_majority(packed: jax.Array) -> jax.Array:
    """(M, ..., w) packed votes -> (..., w) packed majority.

    Bit-sliced: a count is a list of uint32 planes, plane i holding bit i
    of every coordinate's count of set bits, and the voters' words ripple
    in one after another with ``^`` / ``&``. Past FAN_IN voters they are
    cut into FAN_IN blocks that ripple in side by side, and the blocks'
    counts are added the same way, level by level, so the graph stays
    small. The majority bit is count >= ceil(M/2), i.e. 2*count >= M
    (ties -> +1, consistent with sign_binary), compared plane by plane
    from the least significant.
    """
    m = packed.shape[0]
    rows, most = [packed], 1      # K counts, each <= most: planes (K, ..., w)
    while rows[0].shape[0] > 1:
        k = rows[0].shape[0]
        b = -(-k // FAN_IN)                     # counts summed side by side
        blocks = [[p[i:i + b] for p in rows] for i in range(0, k, b)]
        short = b - blocks[-1][0].shape[0]
        if short:
            blocks[-1] = [jnp.pad(p, [(0, short)] + [(0, 0)] * (p.ndim - 1))
                          for p in blocks[-1]]
        rows, top = blocks[0], most
        for block in blocks[1:]:
            top += most
            rows = _add_planes(rows, block, top)
        most = top
    need = (m + 1) // 2
    ge = None        # count >= need on the bits seen so far; None: all ones
    for i, plane in enumerate(rows):
        plane = plane[0]
        if (need >> i) & 1:
            ge = plane if ge is None else plane & ge
        elif ge is not None:
            ge = plane | ge
    return ge


def compression_ratio(dtype: jnp.dtype) -> float:
    """Wire compression vs a dense gradient of `dtype` (per direction)."""
    return jnp.dtype(dtype).itemsize * 8.0


# ---------------------------------------------------------------------------
# ternary 2-bit format (codec ``ternary2bit``; DESIGN.md §8)
# ---------------------------------------------------------------------------
#
# 16 symbols per uint32, 2-bit two's complement per field, little-endian:
# +1 -> 0b01, -1 -> 0b11, 0 (abstain) -> 0b00. Unlike the 1-bit wire this
# format carries the ternary sign convention end to end, so an abstaining
# replica (zero gradient) stays an abstention on the wire and a tied
# coordinate decodes to 0, exactly like the integer-count strategies.


def pack_ternary(s: jax.Array) -> jax.Array:
    """s (..., n) int8 in {-1, 0, +1}, n % 16 == 0 -> uint32 (..., n // 16).

    bits [2j, 2j+1] of word w encode s[..., 16*w + j] in 2-bit two's
    complement (the 0b10 pattern is never produced).
    """
    if s.shape[-1] % PACK2 != 0:
        raise ValueError(
            f"pack_ternary needs last dim % {PACK2} == 0, got shape "
            f"{tuple(s.shape)}; pad with pad_last first")
    sym = (s.astype(jnp.int32) & 0x3).astype(jnp.uint32)
    fields = sym.reshape(s.shape[:-1] + (s.shape[-1] // PACK2, PACK2))
    acc = jnp.zeros(fields.shape[:-1], jnp.uint32)
    for j in range(PACK2):   # unrolled shift/OR (SPMD-partitioner-safe)
        acc = acc | (fields[..., j] << jnp.uint32(2 * j))
    return acc


def unpack_ternary(packed: jax.Array, dtype=jnp.int8) -> jax.Array:
    """uint32 (..., w) -> (..., 16*w) of {-1, 0, +1} in `dtype`."""
    shifts = jnp.arange(PACK2, dtype=jnp.uint32) * 2
    fields = (packed[..., None] >> shifts) & jnp.uint32(0x3)
    signs = jnp.where(fields == 1, 1,
                      jnp.where(fields == 3, -1, 0)).astype(dtype)
    return signs.reshape(packed.shape[:-1] + (packed.shape[-1] * PACK2,))


def ternary_majority(packed: jax.Array) -> jax.Array:
    """(M, w) packed ternary votes -> (w,) packed ternary majority.

    Field-sliced: sum the sign-extended symbols across M workers; the
    majority is the sign of the sum — abstentions abstain and exact ties
    decode to 0, matching the integer-count tie convention."""
    counts = jnp.sum(unpack_ternary(packed, jnp.int32), axis=0)
    return pack_ternary(jnp.sign(counts).astype(jnp.int8))
