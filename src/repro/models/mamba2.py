"""Mamba2 / SSD (state-space duality) block, chunked-scan formulation.

Follows the SSD algorithm of Dao & Gu (arXiv:2405.21060): the sequence is
split into chunks; within a chunk the output is computed with a masked
quadratic (attention-like) term, across chunks a linear recurrence carries
the (H, P, N) state. Single B/C group (as mamba2-2.7b).

Train path: ``mamba2_forward`` (B,S,d) -> (B,S,d).
Decode path: ``mamba2_decode_step`` carries {ssm (B,H,P,N), conv (B,W-1,CD)}.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import BATCH, shard
from repro.models.layers import rms_norm


def _segsum(x: jax.Array) -> jax.Array:
    """x (..., S) -> (..., S, S) with out[..., i, j] = sum_{j < k <= i} x_k,
    -inf above the diagonal (standard SSD helper)."""
    S = x.shape[-1]
    cum = jnp.cumsum(x, axis=-1)
    diff = cum[..., :, None] - cum[..., None, :]
    mask = jnp.tril(jnp.ones((S, S), bool), k=0)
    return jnp.where(mask, diff, -jnp.inf)


def causal_conv1d(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv: x (B,S,C), w (W,C), b (C,)."""
    W = w.shape[0]
    pad = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    out = jnp.zeros_like(x)
    for i in range(W):
        out = out + pad[:, i:i + x.shape[1], :] * w[i]
    return out + b


def _project(p, prefix: str, x: jax.Array) -> Tuple[jax.Array, ...]:
    """Three aligned projections (z | xBC | dt) — each output dim is a
    multiple of the model axis, so TP sharding flows without resharding."""
    z = x @ p[f"{prefix}_zproj"]
    xBC = x @ p[f"{prefix}_xbcproj"]
    dt = x @ p[f"{prefix}_dtproj"]
    return z, xBC, dt


def mamba2_forward(p: Dict[str, jax.Array], x_in: jax.Array, cfg,
                   prefix: str = "mamba") -> jax.Array:
    """One Mamba2 mixer (no residual). x_in (B,S,d) -> (B,S,d).

    Device scopes: ``mixer`` over the whole body, ``ssd`` over the
    chunked scan (DESIGN.md §13)."""
    with jax.named_scope("mixer"):
        return _mixer(p, x_in, cfg, prefix)


def _mixer(p: Dict[str, jax.Array], x_in: jax.Array, cfg,
           prefix: str) -> jax.Array:
    s = cfg.ssm
    B, S, d = x_in.shape
    di, N, nh, P = s.d_inner(d), s.state_dim, s.n_heads(d), s.head_dim
    cs = min(s.chunk_size, S)
    while S % cs:
        cs //= 2
    nc = S // cs

    z, xBC, dt = _project(p, prefix, x_in)
    xBC = jax.nn.silu(
        causal_conv1d(xBC, p[f"{prefix}_conv_w"], p[f"{prefix}_conv_b"]))
    x, B_, C_ = jnp.split(xBC, [di, di + N], axis=-1)
    x = shard(x, BATCH, None, "model")

    dt = jax.nn.softplus(dt.astype(jnp.float32) + p[f"{prefix}_dt_bias"])
    A = -jnp.exp(p[f"{prefix}_A_log"].astype(jnp.float32))     # (nh,)
    with jax.named_scope("ssd"):
        Y = _ssd(p, prefix, x, B_, C_, dt, A, (nc, cs, nh, P), x_in.dtype)

    # gated RMSNorm then output projection
    Y = Y * jax.nn.silu(z).astype(x_in.dtype)
    Y = rms_norm(Y, p[f"{prefix}_norm_scale"], cfg.norm_eps)
    return Y @ p[f"{prefix}_out_proj"]


def _ssd(p, prefix: str, x, B_, C_, dt, A, dims, cdt) -> jax.Array:
    """The chunked scan: x (B,S,d_inner), B_/C_ (B,S,N), dt (B,S,nh) f32,
    A (nh,), `dims` (chunks, chunk size, heads, head dim) -> Y
    (B,S,d_inner) in `cdt`, the D skip included."""
    B, S, di = x.shape
    N = B_.shape[-1]
    nc, cs, nh, P = dims

    # Big (B,S,d_inner)-sized tensors stay bf16 (activation dtype); decay /
    # cumsum / state-recurrence math stays fp32 (small: (b,s,h) and
    # (b,nc,h,p,n)). This halves the dominant SSD temporaries.
    xh = x.reshape(B, nc, cs, nh, P).astype(cdt)
    xh = shard(xh, BATCH, None, None, "model", None)
    Bc = B_.reshape(B, nc, cs, N).astype(cdt)
    Cc = C_.reshape(B, nc, cs, N).astype(cdt)
    dtc = dt.reshape(B, nc, cs, nh)                            # (b,c,l,h) f32
    dtc = shard(dtc, BATCH, None, None, "model")
    dA = dtc * A                                               # (b,c,l,h)
    dA_cs = jnp.cumsum(dA, axis=2)                             # (b,c,l,h)
    xdt = xh * dtc[..., None].astype(cdt)                      # x * dt

    # ---- intra-chunk (quadratic) term ----
    # L is the big intermediate: (b,c,h,l,l) — heads on 'model', bf16
    L = jnp.exp(_segsum(jnp.moveaxis(dA, -1, -2))).astype(cdt)  # (b,c,h,l,l)
    L = shard(L, BATCH, None, "model", None, None)
    scores = jnp.einsum("bcln,bcsn->bcls", Cc, Bc)             # (b,c,l,s)
    Y_diag = jnp.einsum("bcls,bchls,bcshp->bclhp",
                        scores, L, xdt)

    # ---- chunk states and inter-chunk recurrence (fp32) ----
    decay_states = jnp.exp(dA_cs[:, :, -1:, :] - dA_cs)        # (b,c,l,h)
    states = jnp.einsum("bcln,bclh,bclhp->bchpn", Bc,
                        decay_states.astype(cdt), xdt,
                        preferred_element_type=jnp.float32)
    states = shard(states, BATCH, None, "model", None, None)
    chunk_decay = jnp.exp(dA_cs[:, :, -1, :])                  # (b,c,h)

    def scan_fn(carry, inp):
        st, dec = inp
        new = carry * dec[:, :, None, None] + st
        return new, carry                                       # emit state *entering* chunk

    init = jnp.zeros((B, nh, P, N), jnp.float32)
    _, prev_states = jax.lax.scan(
        scan_fn, init,
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    prev_states = jnp.moveaxis(prev_states, 0, 1)               # (b,c,h,p,n)

    state_decay = jnp.exp(dA_cs)                                # (b,c,l,h)
    Y_off = jnp.einsum("bcln,bchpn,bclh->bclhp", Cc,
                       prev_states.astype(cdt),
                       state_decay.astype(cdt))

    Y = (Y_diag + Y_off).reshape(B, S, nh, P)
    Y = Y + xh.reshape(B, S, nh, P) * p[f"{prefix}_D"].astype(cdt)[:, None]
    return Y.reshape(B, S, di)


# ---------------------------------------------------------------------------
# decode (single-token) path
# ---------------------------------------------------------------------------


def mamba2_init_state(cfg, batch: int, dtype=jnp.float32
                      ) -> Dict[str, jax.Array]:
    s = cfg.ssm
    d = cfg.d_model
    return {
        "ssm": jnp.zeros((batch, s.n_heads(d), s.head_dim, s.state_dim),
                         jnp.float32),
        "conv": jnp.zeros((batch, s.conv_width - 1, s.conv_dim(d)), dtype),
    }


def mamba2_decode_step(p: Dict[str, jax.Array], x_in: jax.Array, state,
                       cfg, prefix: str = "mamba"):
    """x_in (B,1,d); state {'ssm','conv'} -> (out (B,1,d), new state)."""
    s = cfg.ssm
    B, _, d = x_in.shape
    di, N, nh, P = s.d_inner(d), s.state_dim, s.n_heads(d), s.head_dim

    z, xBC, dt = _project(p, prefix, x_in[:, 0])
    # conv over [cache, new]
    window = jnp.concatenate([state["conv"], xBC[:, None, :]], axis=1)
    w = p[f"{prefix}_conv_w"]
    xBC = jax.nn.silu(jnp.einsum("bwc,wc->bc", window, w)
                      + p[f"{prefix}_conv_b"])
    new_conv = window[:, 1:]

    x, B_, C_ = jnp.split(xBC, [di, di + N], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p[f"{prefix}_dt_bias"])
    A = -jnp.exp(p[f"{prefix}_A_log"].astype(jnp.float32))
    dA = jnp.exp(dt * A)                                        # (B,nh)

    xh = x.reshape(B, nh, P).astype(jnp.float32)
    xdt = xh * dt[..., None]
    ssm = state["ssm"] * dA[..., None, None] + jnp.einsum(
        "bhp,bn->bhpn", xdt, B_.astype(jnp.float32))
    y = jnp.einsum("bhpn,bn->bhp", ssm, C_.astype(jnp.float32))
    y = y + xh * p[f"{prefix}_D"].astype(jnp.float32)[:, None]
    y = y.reshape(B, di) * jax.nn.silu(z.astype(jnp.float32))
    y = rms_norm(y.astype(x_in.dtype), p[f"{prefix}_norm_scale"], cfg.norm_eps)
    out = (y @ p[f"{prefix}_out_proj"])[:, None, :]
    return out, {"ssm": ssm, "conv": new_conv}
