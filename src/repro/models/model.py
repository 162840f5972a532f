"""Model integration layer: init / loss / prefill / decode for every arch.

This is the public model API the trainer, server, dry-run and tests use:

  init_params(cfg, key)                 -> flat param dict (stacked layout)
  loss_fn(cfg, params, batch)           -> (scalar loss, metrics dict)
  forward_logits(cfg, params, batch)    -> (B, S, V) logits
  init_cache(cfg, batch, max_len)       -> cache pytree (family-specific)
  prefill(cfg, params, batch)           -> (logits, cache)
  decode_step(cfg, params, tokens, cache, pos) -> (logits, cache)
  input_specs(cfg, cell)                -> ShapeDtypeStruct pytrees for the
                                           dry-run (no allocation)

Batches are dicts: ``tokens`` (B, S) int32 always; plus ``enc_embeds``
(whisper) or ``patch_embeds`` (pixtral) when the frontend is stubbed.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchFamily, ModelConfig, ShapeCell
from repro.distributed.sharding import BATCH, shard
from repro.models import encdec, hybrid, layers as L, transformer
from repro.models.mamba2 import (mamba2_decode_step, mamba2_forward,
                                 mamba2_init_state)


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init_params(cfg: ModelConfig, key: jax.Array,
                dtype: Optional[Any] = None) -> Dict[str, jax.Array]:
    dtype = dtype or _dtype(cfg)
    shapes = cfg.param_shapes()
    keys = jax.random.split(key, len(shapes))
    params = {}
    for (name, shape), k in zip(sorted(shapes.items()), keys):
        if name.endswith("_scale") or ".scale" in name:
            params[name] = jnp.ones(shape, dtype)
        elif name.endswith(("_b", "_bq", "_bk", "_bv", "_conv_b", "dt_bias")):
            params[name] = jnp.zeros(shape, dtype)
        elif name.endswith("A_log"):
            # A in [1, 16) as in mamba2 reference init
            nh = shape[-1]
            a = jnp.tile(jnp.log(jnp.arange(1, nh + 1, dtype=jnp.float32)
                                 + 0.5), shape[:-1] + (1,)).reshape(shape)
            params[name] = a.astype(jnp.float32)
        elif name.endswith("mamba_D"):
            params[name] = jnp.ones(shape, jnp.float32)
        else:
            fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
            std = 1.0 / math.sqrt(fan_in)
            params[name] = (jax.random.normal(k, shape, jnp.float32)
                            * std).astype(dtype)
    return params


# ---------------------------------------------------------------------------
# embedding frontends
# ---------------------------------------------------------------------------


def _vlm_split(cell_seq: int) -> Tuple[int, int]:
    """pixtral: first quarter of the sequence is image patches."""
    s_img = cell_seq // 4
    return s_img, cell_seq - s_img


def _embed_input(cfg: ModelConfig, params, batch) -> jax.Array:
    """Build the (B, S, d) input stream for decoder-style archs (the
    ``embed`` device scope)."""
    with jax.named_scope("embed"):
        tok = L.embed_tokens(params["embed.table"], batch["tokens"])
        if cfg.family == ArchFamily.VLM and "patch_embeds" in batch:
            h = jnp.concatenate(
                [batch["patch_embeds"].astype(tok.dtype), tok], axis=1)
        else:
            h = tok
        return shard(h, BATCH, None, None)


# ---------------------------------------------------------------------------
# forward / loss
# ---------------------------------------------------------------------------


def forward_logits(cfg: ModelConfig, params, batch, hook=None,
                   remat: str = "none") -> Tuple[jax.Array, jax.Array]:
    """Returns (logits (B,S,V), aux_loss scalar).

    `hook(tree, scope)` is the ZeRO-3 gather(+vote-backward) transform
    (core.majority_vote.make_fsdp_hooks); applied to top-level params here
    and to per-layer trees inside the depth scans.
    """
    if hook is not None:
        top = {k: v for k, v in params.items()
               if not k.startswith(("layers.", "encoder."))}
        params = {**params, **hook(top, "top")}
    aux = jnp.zeros((), jnp.float32)
    if cfg.family == ArchFamily.AUDIO:
        enc = encdec.encoder_forward(params, batch["enc_embeds"], cfg,
                                     hook=hook, remat=remat)
        h = L.embed_tokens(params["embed.table"], batch["tokens"])
        S = h.shape[1]
        h = h + L.sinusoidal_positions(jnp.arange(S), cfg.d_model
                                       ).astype(h.dtype)
        h = encdec.decoder_forward(params, h, enc, cfg, hook=hook,
                                   remat=remat)
    elif cfg.family == ArchFamily.SSM:
        h = _embed_input(cfg, params, batch)
        lp = transformer._layer_tree(params)

        def body(carry, layer_p):
            if hook is not None:
                layer_p = hook(layer_p, "layers")
            x = L.rms_norm(carry, layer_p["norm1_scale"], cfg.norm_eps)
            carry = carry + mamba2_forward(layer_p, x, cfg)
            return transformer.residual_shard(carry, cfg), None

        h, _ = jax.lax.scan(transformer.maybe_remat(body, remat), h, lp)
    elif cfg.family == ArchFamily.HYBRID:
        h = _embed_input(cfg, params, batch)
        h = hybrid.hybrid_forward(params, h, cfg, hook=hook, remat=remat)
    else:
        h = _embed_input(cfg, params, batch)
        h, aux = transformer.decoder_stack(params, h, cfg, hook=hook,
                                           remat=remat)
    with jax.named_scope("lm_head"):
        h = L.rms_norm(h, params["final_norm.scale"], cfg.norm_eps)
        table = params.get("unembed.table", params["embed.table"])
        logits = jnp.einsum("bsd,vd->bsv", h, table)
        return shard(logits, BATCH, None, "model"), aux


def loss_fn(cfg: ModelConfig, params, batch, hook=None, remat: str = "none"
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    logits, aux = forward_logits(cfg, params, batch, hook=hook, remat=remat)
    tokens = batch["tokens"]
    if cfg.family == ArchFamily.VLM and "patch_embeds" in batch:
        # loss only over the text segment (last `len(tokens)` positions)
        logits = logits[:, -tokens.shape[1]:]
    with jax.named_scope("lm_head"):
        ce = L.cross_entropy_loss(logits[:, :-1], tokens[:, 1:])
    loss = ce + aux
    return loss, {"ce": ce, "aux": aux}


# ---------------------------------------------------------------------------
# caches / prefill / decode
# ---------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype: Optional[Any] = None) -> Dict[str, jax.Array]:
    dtype = dtype or _dtype(cfg)
    if cfg.family == ArchFamily.SSM:
        st = mamba2_init_state(cfg, batch, dtype)
        return {
            "ssm": jnp.zeros((cfg.num_layers,) + st["ssm"].shape, jnp.float32),
            "conv": jnp.zeros((cfg.num_layers,) + st["conv"].shape, dtype),
        }
    if cfg.family == ArchFamily.HYBRID:
        return hybrid.hybrid_init_cache(cfg, batch, max_len, dtype)
    if cfg.family == ArchFamily.AUDIO:
        t_src = cfg.max_source_positions
        return encdec.encdec_init_cache(None, cfg, batch, max_len, t_src, dtype)
    return transformer.init_kv_cache(cfg, batch, max_len, dtype)


def prefill(cfg: ModelConfig, params, batch, hook=None
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Run the prompt; return (logits, populated cache)."""
    if hook is not None and cfg.family not in (ArchFamily.SSM,
                                               ArchFamily.HYBRID,
                                               ArchFamily.AUDIO):
        top = {k: v for k, v in params.items()
               if not k.startswith(("layers.", "encoder."))}
        params = {**params, **hook(top, "top")}
    if cfg.family == ArchFamily.AUDIO:
        enc = encdec.encoder_forward(params, batch["enc_embeds"], cfg)
        xk, xv = encdec.encdec_precompute_cross(params, enc, cfg)
        h = L.embed_tokens(params["embed.table"], batch["tokens"])
        S = h.shape[1]
        h = h + L.sinusoidal_positions(jnp.arange(S), cfg.d_model
                                       ).astype(h.dtype)
        h = encdec.decoder_forward(params, h, enc, cfg)
        h = L.rms_norm(h, params["final_norm.scale"], cfg.norm_eps)
        table = params.get("unembed.table", params["embed.table"])
        logits = jnp.einsum("bsd,vd->bsv", h, table)
        # self-attn caches from a fresh pass would need per-layer K/V; for
        # serving we re-run decoder_prefill-style below (cross K/V reused).
        cache = init_cache(cfg, batch["tokens"].shape[0], S)
        cache["xk"], cache["xv"] = xk, xv
        return logits, cache
    if cfg.family in (ArchFamily.SSM, ArchFamily.HYBRID):
        # recurrent archs: prefill == forward (state materialisation for
        # serving is chunk-scan; dry-run exercises the forward path)
        logits, _ = forward_logits(cfg, params, batch, hook=hook)
        cache = init_cache(cfg, batch["tokens"].shape[0],
                           batch["tokens"].shape[1])
        return logits, cache
    h = _embed_input(cfg, params, batch)
    h, cache = transformer.decoder_prefill(params, h, cfg, hook=hook)
    h = L.rms_norm(h, params["final_norm.scale"], cfg.norm_eps)
    table = params.get("unembed.table", params["embed.table"])
    logits = jnp.einsum("bsd,vd->bsv", h, table)
    return shard(logits, BATCH, None, "model"), cache


def decode_step(cfg: ModelConfig, params, tokens: jax.Array, cache,
                pos: jax.Array) -> Tuple[jax.Array, Any]:
    """tokens (B,1) int32; pos scalar int32 -> (logits (B,V), cache)."""
    h = L.embed_tokens(params["embed.table"], tokens)
    if cfg.family == ArchFamily.AUDIO:
        h = h + L.sinusoidal_positions(pos[None], cfg.d_model).astype(h.dtype)
        h, cache = encdec.encdec_decode_step(params, h, cache, pos, cfg)
    elif cfg.family == ArchFamily.SSM:
        lp = transformer._layer_tree(params)

        def body(carry, xs):
            layer_p, ssm, conv = xs
            x = L.rms_norm(carry, layer_p["norm1_scale"], cfg.norm_eps)
            out, st = mamba2_decode_step(
                layer_p, x, {"ssm": ssm, "conv": conv}, cfg)
            return carry + out, (st["ssm"], st["conv"])

        h, (ssm, conv) = jax.lax.scan(body, h, (lp, cache["ssm"], cache["conv"]))
        cache = {"ssm": ssm, "conv": conv}
    elif cfg.family == ArchFamily.HYBRID:
        h, cache = hybrid.hybrid_decode_step(params, h, cache, pos, cfg)
    else:
        h, cache = transformer.decoder_decode_step(params, h, cache, pos, cfg)
    h = L.rms_norm(h, params["final_norm.scale"], cfg.norm_eps)
    table = params.get("unembed.table", params["embed.table"])
    logits = jnp.einsum("bsd,vd->bsv", h, table)[:, 0]
    return logits, cache


# ---------------------------------------------------------------------------
# dry-run input specs (ShapeDtypeStructs — never allocates)
# ---------------------------------------------------------------------------


def input_specs(cfg: ModelConfig, cell: ShapeCell) -> Dict[str, Any]:
    """Abstract inputs for a shape cell.

    train/prefill -> {'batch': {...}}
    decode        -> {'tokens', 'cache', 'pos'}
    """
    B, S = cell.global_batch, cell.seq_len
    dt = _dtype(cfg)
    i32 = jnp.int32
    sds = jax.ShapeDtypeStruct

    def token_batch() -> Dict[str, Any]:
        if cfg.family == ArchFamily.AUDIO:
            t_src = cfg.max_source_positions
            return {"tokens": sds((B, S), i32),
                    "enc_embeds": sds((B, t_src, cfg.d_model), dt)}
        if cfg.family == ArchFamily.VLM:
            s_img, s_txt = _vlm_split(S)
            return {"tokens": sds((B, s_txt), i32),
                    "patch_embeds": sds((B, s_img, cfg.d_model), dt)}
        return {"tokens": sds((B, S), i32)}

    if cell.kind in ("train", "prefill"):
        return {"batch": token_batch()}

    # decode: cache of length S, one new token at pos S-1
    cache = jax.eval_shape(lambda: init_cache(cfg, B, S))
    return {
        "tokens": sds((B, 1), i32),
        "cache": cache,
        "pos": sds((), i32),
    }


def make_batch(cfg: ModelConfig, batch: int, seq: int, key: jax.Array
               ) -> Dict[str, jax.Array]:
    """Concrete random batch (tests / examples)."""
    k1, k2 = jax.random.split(key)
    out = {"tokens": jax.random.randint(k1, (batch, seq), 0, cfg.vocab_size,
                                        jnp.int32)}
    if cfg.family == ArchFamily.AUDIO:
        t_src = min(cfg.max_source_positions, 64)
        out["enc_embeds"] = jax.random.normal(
            k2, (batch, t_src, cfg.d_model), jnp.float32).astype(_dtype(cfg))
    if cfg.family == ArchFamily.VLM:
        s_img, s_txt = _vlm_split(seq)
        out["tokens"] = out["tokens"][:, :s_txt]
        out["patch_embeds"] = jax.random.normal(
            k2, (batch, s_img, cfg.d_model), jnp.float32).astype(_dtype(cfg))
    return out
