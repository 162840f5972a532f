"""Mamba2 mixer by the SSD paper's minimal chunked algorithm.

Dao & Gu, "Transformers are SSMs" (arXiv:2405.21060), section 7 and its
``ssd_minimal_discrete`` listing: one group of B and C shared by all
heads, a causal depthwise convolution over [x, B, C] followed by SiLU,
dt = softplus(dt_raw + dt_bias), A = -exp(A_log), y = SSD(x dt, A dt, B,
C) + D x, then y * SiLU(z) through a gated RMSNorm and the
out-projection. Everything in float32; contractions through ``mm``.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from chipbench.reference.common import mm, rms_norm


def segsum(x: jax.Array) -> jax.Array:
    """(..., T) -> (..., T, T): out[i, j] = x[j+1] + ... + x[i] for j <= i,
    -inf above the diagonal."""
    T = x.shape[-1]
    c = jnp.cumsum(x, axis=-1)
    diff = c[..., :, None] - c[..., None, :]
    return jnp.where(jnp.tril(jnp.ones((T, T), bool)), diff, -jnp.inf)


def ssd(prec: str, X, A, B, C, chunk: int):
    """X (b,S,h,p), A (b,S,h), B and C (b,S,n) -> Y (b,S,h,p)."""
    b, S, h, p = X.shape
    n = B.shape[-1]
    l = min(chunk, S)
    c = S // l
    X = X.reshape(b, c, l, h, p)
    B = B.reshape(b, c, l, n)
    C = C.reshape(b, c, l, n)
    A = jnp.moveaxis(A.reshape(b, c, l, h), -1, 1)          # (b,h,c,l)
    A_cs = jnp.cumsum(A, axis=-1)
    # within each chunk: the masked, decayed (l x l) mix
    Lmat = jnp.exp(segsum(A))                               # (b,h,c,l,l)
    CB = mm(prec, "bcln,bcsn->bcls", C, B)
    W = CB[:, None] * Lmat                                  # (b,h,c,l,s)
    Y_diag = mm(prec, "bhcls,bcshp->bclhp", W, X)
    # each chunk's final state
    decay = jnp.exp(A_cs[..., -1:] - A_cs)                  # (b,h,c,l)
    Xd = X * jnp.moveaxis(decay, 1, -1)[..., None]          # (b,c,l,h,p)
    states = mm(prec, "bcln,bclhp->bchpn", B, Xd)
    # carry states across chunks
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], 1)
    chunk_decay = jnp.exp(segsum(jnp.pad(A_cs[..., -1], ((0, 0), (0, 0),
                                                         (1, 0)))))
    new = mm(prec, "bhzc,bchpn->bzhpn", chunk_decay, states)
    states = new[:, :-1]                                    # entering chunk
    # the carried state's output
    Y_off = mm(prec, "bcln,bchpn->bclhp", C, states)
    Y_off = Y_off * jnp.moveaxis(jnp.exp(A_cs), 1, -1)[..., None]
    return (Y_diag + Y_off).reshape(b, S, h, p)


def causal_conv(x, w, bias):
    """Depthwise causal convolution: x (b,S,ch), w (W,ch), bias (ch,)."""
    W = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    S = x.shape[1]
    return sum(xp[:, i:i + S] * w[i] for i in range(W)) + bias


def mixer(prec: str, x, lp: Dict, *, prefix: str, d_state: int,
          headdim: int, chunk: int, eps: float):
    """One Mamba2 mixer (no residual): x (b,S,d) -> (b,S,d)."""
    b, S, _ = x.shape
    di = lp[f"{prefix}_zproj"].shape[-1]
    nh = di // headdim
    z = mm(prec, "bsd,de->bse", x, lp[f"{prefix}_zproj"])
    xbc = mm(prec, "bsd,de->bse", x, lp[f"{prefix}_xbcproj"])
    dt = mm(prec, "bsd,de->bse", x, lp[f"{prefix}_dtproj"])
    xbc = jax.nn.silu(causal_conv(xbc, lp[f"{prefix}_conv_w"],
                                  lp[f"{prefix}_conv_b"]))
    xs, B, C = jnp.split(xbc, [di, di + d_state], axis=-1)
    dt = jax.nn.softplus(dt + lp[f"{prefix}_dt_bias"])       # (b,S,h)
    A = -jnp.exp(lp[f"{prefix}_A_log"])                      # (h,)
    X = xs.reshape(b, S, nh, headdim)
    y = ssd(prec, X * dt[..., None], dt * A, B, C, chunk)
    y = y + X * lp[f"{prefix}_D"][:, None]
    y = y.reshape(b, S, di) * jax.nn.silu(z)
    y = rms_norm(y, lp[f"{prefix}_norm_scale"], eps)
    return mm(prec, "bse,ed->bsd", y, lp[f"{prefix}_out_proj"])
