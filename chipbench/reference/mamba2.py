"""mamba2: a stack of pre-norm Mamba2 mixers, attention-free.

Per layer h <- h + Mixer(RMSNorm(h)); then a final RMSNorm and the tied
unembedding. Keys (``chipbench/configs/mamba2-2.7b.json``): d_model,
n_layer, d_state, headdim, expand, d_conv, chunk_size, vocab_size,
pad_vocab_size_multiple, rms_norm_eps. The leaf names are the trainer's stacked layout
(``layers.<leaf>`` with a leading layer axis).
"""
from __future__ import annotations

from typing import Dict, List, Tuple

from chipbench.reference import ssd
from chipbench.reference.common import Block, rms_norm, vocab_rows
from chipbench.yardstick import flops


def mamba_leaves(d: int, di: int, nh: int, n: int, w: int, L: int
                 ) -> Dict[str, Tuple[int, ...]]:
    conv = di + 2 * n
    return {
        "layers.norm1_scale": (L, d),
        "layers.mamba_zproj": (L, d, di),
        "layers.mamba_xbcproj": (L, d, conv),
        "layers.mamba_dtproj": (L, d, nh),
        "layers.mamba_conv_w": (L, w, conv),
        "layers.mamba_conv_b": (L, conv),
        "layers.mamba_dt_bias": (L, nh),
        "layers.mamba_A_log": (L, nh),
        "layers.mamba_D": (L, nh),
        "layers.mamba_norm_scale": (L, di),
        "layers.mamba_out_proj": (L, di, d),
    }


def param_shapes(c: Dict) -> Dict[str, Tuple[int, ...]]:
    d, di = c["d_model"], c["expand"] * c["d_model"]
    shapes = {"embed.table": (vocab_rows(c), d), "final_norm.scale": (d,)}
    shapes.update(mamba_leaves(d, di, di // c["headdim"], c["d_state"],
                               c["d_conv"], c["n_layer"]))
    return shapes


def schedule(c: Dict) -> List[Tuple[str, int]]:
    return [("mamba", i) for i in range(c["n_layer"])]


def _mamba(prec, c, lp, h):
    lp = {k[len("layers."):]: v for k, v in lp.items()}
    x = rms_norm(h, lp["norm1_scale"], c["rms_norm_eps"])
    return h + ssd.mixer(prec, x, lp, prefix="mamba", d_state=c["d_state"],
                         headdim=c["headdim"], chunk=c["chunk_size"],
                         eps=c["rms_norm_eps"])


BLOCKS = {"mamba": Block(_mamba, "layers.", stacked=True)}


def train_flops_per_step(c: Dict, global_batch: int, seq: int) -> float:
    d = c["d_model"]
    fwd = (c["n_layer"] * flops.mamba2_mixer(d, c["d_state"], c["headdim"],
                                             c["expand"], c["chunk_size"],
                                             seq)
           + flops.lm_head(d, vocab_rows(c), seq))
    return flops.train_step(fwd, global_batch)
