"""zamba2: Mamba2 backbone with one weight-shared attention + MLP block.

After every ``shared_block_every`` Mamba2 layers (whole groups only) the
shared block runs: h <- h + Attn(RMSNorm(h)); h <- h + SwiGLU(RMSNorm(h)),
causal multi-head attention with rotary embeddings (rotate-half, theta
``rope_theta``) on queries and keys. This is the trainer's form of the
shared block, which reads the residual stream alone (the released model
concatenates the original embedding and adds LoRA per call). Keys
(``chipbench/configs/zamba2-1.2b.json``): hidden_size,
num_hidden_layers, mamba_d_state, mamba_headdim, mamba_expand,
mamba_d_conv, chunk_size, num_attention_heads, num_key_value_heads,
ffn_hidden_size, shared_block_every, vocab_size, rope_theta,
rms_norm_eps, and the trainer's ``attention_head_dim`` (the file's
``assumed_sizes``).
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

from chipbench.reference import ssd
from chipbench.reference.common import Block, mm, rms_norm, vocab_rows
from chipbench.reference.mamba2 import mamba_leaves
from chipbench.yardstick import flops


def param_shapes(c: Dict) -> Dict[str, Tuple[int, ...]]:
    d = c["hidden_size"]
    di = c["mamba_expand"] * d
    H, K, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                c["attention_head_dim"])
    ff = c["ffn_hidden_size"]
    shapes = {"embed.table": (vocab_rows(c), d), "final_norm.scale": (d,)}
    shapes.update(mamba_leaves(d, di, di // c["mamba_headdim"],
                               c["mamba_d_state"], c["mamba_d_conv"],
                               c["num_hidden_layers"]))
    shapes.update({
        "shared_block.norm1_scale": (d,),
        "shared_block.attn_wq": (d, H * hd),
        "shared_block.attn_wk": (d, K * hd),
        "shared_block.attn_wv": (d, K * hd),
        "shared_block.attn_wo": (H * hd, d),
        "shared_block.norm2_scale": (d,),
        "shared_block.mlp_w_gate": (d, ff),
        "shared_block.mlp_w_up": (d, ff),
        "shared_block.mlp_w_down": (ff, d),
    })
    return shapes


def schedule(c: Dict) -> List[Tuple[str, int]]:
    out, e = [], c["shared_block_every"]
    for i in range(c["num_hidden_layers"]):
        out.append(("mamba", i))
        if (i + 1) % e == 0:
            out.append(("shared", None))
    return out


def _mamba(prec, c, lp, h):
    lp = {k[len("layers."):]: v for k, v in lp.items()}
    x = rms_norm(h, lp["norm1_scale"], c["rms_norm_eps"])
    return h + ssd.mixer(prec, x, lp, prefix="mamba",
                         d_state=c["mamba_d_state"],
                         headdim=c["mamba_headdim"], chunk=c["chunk_size"],
                         eps=c["rms_norm_eps"])


def rope(x, theta: float):
    """x (b,S,h,dh): rotate the two halves of each head by position."""
    S, dh = x.shape[1], x.shape[-1]
    half = dh // 2
    freq = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freq     # (S, half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _shared(prec, c, lp, h):
    lp = {k[len("shared_block."):]: v for k, v in lp.items()}
    eps = c["rms_norm_eps"]
    b, S, _ = h.shape
    H, K, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                c["attention_head_dim"])
    x = rms_norm(h, lp["norm1_scale"], eps)
    q = mm(prec, "bsd,de->bse", x, lp["attn_wq"]).reshape(b, S, H, hd)
    k = mm(prec, "bsd,de->bse", x, lp["attn_wk"]).reshape(b, S, K, hd)
    v = mm(prec, "bsd,de->bse", x, lp["attn_wv"]).reshape(b, S, K, hd)
    q, k = rope(q, c["rope_theta"]), rope(k, c["rope_theta"])
    k, v = jnp.repeat(k, H // K, axis=2), jnp.repeat(v, H // K, axis=2)
    s = mm(prec, "bshd,bthd->bhst", q, k) * hd ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = mm(prec, "bhst,bthd->bshd", jax.nn.softmax(s, axis=-1), v)
    h = h + mm(prec, "bse,ed->bsd", o.reshape(b, S, H * hd), lp["attn_wo"])
    x = rms_norm(h, lp["norm2_scale"], eps)
    g = jax.nn.silu(mm(prec, "bsd,df->bsf", x, lp["mlp_w_gate"]))
    u = mm(prec, "bsd,df->bsf", x, lp["mlp_w_up"])
    return h + mm(prec, "bsf,fd->bsd", g * u, lp["mlp_w_down"])


BLOCKS = {"mamba": Block(_mamba, "layers.", stacked=True),
          "shared": Block(_shared, "shared_block.", stacked=False)}


def train_flops_per_step(c: Dict, global_batch: int, seq: int) -> float:
    d = c["hidden_size"]
    mixer = flops.mamba2_mixer(d, c["mamba_d_state"], c["mamba_headdim"],
                               c["mamba_expand"], c["chunk_size"], seq)
    block = flops.attention_mlp_block(d, c["num_attention_heads"],
                                      c["num_key_value_heads"],
                                      c["attention_head_dim"],
                                      c["ffn_hidden_size"], seq)
    calls = c["num_hidden_layers"] // c["shared_block_every"]
    fwd = (c["num_hidden_layers"] * mixer + calls * block
           + flops.lm_head(d, vocab_rows(c), seq))
    return flops.train_step(fwd, global_batch)
