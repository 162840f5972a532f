"""zamba2-style hybrid stack: Mamba2 backbone + one weight-shared attention
block applied after every ``shared_attn_every`` mamba layers.

The mamba backbone scans in segments (static slices of the stacked layer
params); after each full segment the shared block (single weight set,
re-invoked) runs. Each shared-block invocation owns its own KV cache slot
for decoding.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import BATCH, shard
from repro.models import layers as L
from repro.models.mamba2 import (mamba2_decode_step, mamba2_forward,
                                 mamba2_init_state)


def _layer_tree(p, prefix="layers."):
    return {k[len(prefix):]: v for k, v in p.items() if k.startswith(prefix)}


def _shared_tree(p):
    return {k[len("shared_block."):]: v for k, v in p.items()
            if k.startswith("shared_block.")}


def _segments(cfg) -> List[Tuple[int, int, bool]]:
    """(start, end, shared_after) segments of the mamba stack."""
    segs = []
    e = cfg.shared_attn_every
    start = 0
    while start < cfg.num_layers:
        end = min(start + e, cfg.num_layers)
        segs.append((start, end, end - start == e))
        start = end
    return segs


def _mamba_segment_scan(lp: Dict[str, jax.Array], h: jax.Array, cfg,
                        start: int, end: int, hook=None,
                        remat: str = "none") -> jax.Array:
    from repro.models.transformer import maybe_remat
    seg = {k: v[start:end] for k, v in lp.items()}

    def body(carry, layer_p):
        if hook is not None:
            layer_p = hook(layer_p, "layers")
        x = L.rms_norm(carry, layer_p["norm1_scale"], cfg.norm_eps)
        carry = carry + mamba2_forward(layer_p, x, cfg)
        from repro.models.transformer import residual_shard
        return residual_shard(carry, cfg), None

    h, _ = jax.lax.scan(maybe_remat(body, remat), h, seg)
    return h


def _shared_attn_block(sp: Dict[str, jax.Array], h: jax.Array, cfg
                       ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """The shared attention + SwiGLU block with its norms, under the
    ``shared_block`` device scope."""
    with jax.named_scope("shared_block"):
        x = L.rms_norm(h, sp["norm1_scale"], cfg.norm_eps)
        attn_out, kv = L.self_attention_block(sp, "attn", x, cfg,
                                              causal=True)
        h = h + attn_out
        x = L.rms_norm(h, sp["norm2_scale"], cfg.norm_eps)
        h = h + L.swiglu_mlp(sp, "mlp", x)
        return h, kv


def hybrid_forward(p: Dict[str, jax.Array], h: jax.Array, cfg,
                   hook=None, remat: str = "none") -> jax.Array:
    from repro.models.transformer import maybe_remat
    lp, sp = _layer_tree(p), _shared_tree(p)

    def shared_fn(sp_, h_):
        return _shared_attn_block(sp_, h_, cfg)[0]

    shared_fn = maybe_remat(shared_fn, remat)
    for start, end, shared_after in _segments(cfg):
        h = _mamba_segment_scan(lp, h, cfg, start, end, hook=hook,
                                remat=remat)
        if shared_after:
            h = shared_fn(sp, h)
    return h


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------


def hybrid_init_cache(cfg, batch: int, max_len: int, dtype
                      ) -> Dict[str, jax.Array]:
    st = mamba2_init_state(cfg, batch, dtype)
    n_calls = cfg.num_shared_attn_calls
    K, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    shape = (n_calls, batch, max_len, K, hd)
    return {
        "ssm": jnp.zeros((cfg.num_layers,) + st["ssm"].shape, jnp.float32),
        "conv": jnp.zeros((cfg.num_layers,) + st["conv"].shape, dtype),
        "attn_k": jnp.zeros(shape, dtype),
        "attn_v": jnp.zeros(shape, dtype),
    }


def hybrid_decode_step(p: Dict[str, jax.Array], h: jax.Array, cache,
                       pos: jax.Array, cfg):
    lp, sp = _layer_tree(p), _shared_tree(p)
    new_ssm, new_conv = [], []
    new_k, new_v = [], []
    call_idx = 0
    for start, end, shared_after in _segments(cfg):
        seg = {k: v[start:end] for k, v in lp.items()}

        def body(carry, xs):
            layer_p, ssm, conv = xs
            x = L.rms_norm(carry, layer_p["norm1_scale"], cfg.norm_eps)
            out, st = mamba2_decode_step(layer_p, x, {"ssm": ssm, "conv": conv}, cfg)
            return carry + out, (st["ssm"], st["conv"])

        h, (ssm_seg, conv_seg) = jax.lax.scan(
            body, h, (seg, cache["ssm"][start:end], cache["conv"][start:end]))
        new_ssm.append(ssm_seg)
        new_conv.append(conv_seg)
        if shared_after:
            x = L.rms_norm(h, sp["norm1_scale"], cfg.norm_eps)
            attn_out, k_c, v_c = L.decode_self_attention(
                sp, "attn", x, cfg,
                k_cache=cache["attn_k"][call_idx],
                v_cache=cache["attn_v"][call_idx], pos=pos)
            h = h + attn_out
            x = L.rms_norm(h, sp["norm2_scale"], cfg.norm_eps)
            h = h + L.swiglu_mlp(sp, "mlp", x)
            new_k.append(k_c)
            new_v.append(v_c)
            call_idx += 1
    return h, {
        "ssm": jnp.concatenate(new_ssm, axis=0),
        "conv": jnp.concatenate(new_conv, axis=0),
        "attn_k": jnp.stack(new_k, axis=0),
        "attn_v": jnp.stack(new_v, axis=0),
    }
