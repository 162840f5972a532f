"""Forward FLOPs of the blocks the configurations are made of.

Counted from a configuration's published sizes, never from the program.
A matmul of (m, k) by (k, n) is 2mkn FLOPs, and a training step is taken
as three forwards (the backward of a matmul costs twice its forward).
Recomputation (rematerialisation) is not counted; neither is elementwise
work. Each reference family (``chipbench/reference/<family>.py``) sums
these blocks into its ``train_flops_per_step``.
"""
from __future__ import annotations


def mamba2_mixer(d: int, d_state: int, headdim: int, expand: int,
                 chunk: int, seq: int) -> float:
    """One Mamba2 (SSD) mixer over one sequence, chunked form.

    In-projections z | xBC | dt and the out-projection, then per chunk of
    length l: C B^T (l x l over the state), the masked (l x l) mix of x
    per head, the chunk's state B^T x per head, and C times the carried
    state per head.
    """
    di = expand * d
    nh = di // headdim
    proj = 2.0 * seq * d * (di + (di + 2 * d_state) + nh)
    out = 2.0 * seq * di * d
    cs = min(chunk, seq)
    nc = seq // cs
    intra = nc * (2.0 * cs * cs * d_state + 2.0 * nh * cs * cs * headdim)
    states = nc * 2.0 * cs * nh * headdim * d_state
    carried = nc * 2.0 * cs * nh * headdim * d_state
    return proj + out + intra + states + carried


def attention_mlp_block(d: int, heads: int, kv_heads: int, head_dim: int,
                        d_ff: int, seq: int) -> float:
    """One causal attention + SwiGLU MLP block over one sequence.

    Q, K, V and O projections; QK^T and PV over the causal lower triangle
    (S(S+1)/2 pairs per head); gate, up and down projections.
    """
    qkvo = 2.0 * seq * d * head_dim * (2 * heads + 2 * kv_heads)
    pairs = seq * (seq + 1) / 2.0
    core = 2 * 2.0 * pairs * head_dim * heads
    mlp = 2.0 * seq * d * d_ff * 3
    return qkvo + core + mlp


def lm_head(d: int, vocab: int, seq: int) -> float:
    """The unembedding matmul over one sequence (the embedding is a
    gather and costs no FLOPs)."""
    return 2.0 * seq * d * vocab


def train_step(forward_per_sequence: float, global_batch: int) -> float:
    """Forward + backward of a step: three forwards per sequence."""
    return 3.0 * forward_per_sequence * global_batch
