"""Sharding rules: parameter-name / activation -> PartitionSpec.

The mesh has axes ``('data', 'model')`` single-pod or ``('pod', 'data',
'model')`` multi-pod (launch/mesh.py). Batch is sharded over
``('pod','data')`` jointly; weights are TP-sharded over ``'model'`` and
(for Mode-B archs) FSDP-sharded over ``'data'``.

``shard(x, spec)`` is the in-model annotation helper: it applies
``with_sharding_constraint`` when tracing under a non-empty mesh and is the
identity otherwise, so the same model code runs in single-device tests and
in the 512-chip dry-run.
"""
from __future__ import annotations

import re
from typing import Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import compat

# ---------------------------------------------------------------------------
# activation annotation helper
# ---------------------------------------------------------------------------


def shard(x: jax.Array, *spec) -> jax.Array:
    """Constrain ``x`` to PartitionSpec(*spec) if a mesh is active.

    Robustness rules so model code can annotate unconditionally:
    * axis names absent from the mesh (e.g. 'pod' single-pod) are dropped;
    * axes Manual in the current context (inside shard_map) are dropped —
      they are already consumed;
    * entries whose dimension is not divisible by the axis size are
      dropped (e.g. 60 experts on a 16-wide model axis).
    """
    mesh = compat.get_abstract_mesh()
    if mesh is None or mesh.empty:
        return x
    manual = {n for n, t in zip(mesh.axis_names, mesh.axis_types)
              if t == compat.AxisType.Manual}
    avail = set(mesh.axis_names) - manual
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))

    def fix(entry, dim):
        if entry is None:
            return None
        if isinstance(entry, (tuple, list)):
            kept = tuple(e for e in entry if e in avail)
        else:
            kept = (entry,) if entry in avail else ()
        total = 1
        for e in kept:
            total *= sizes.get(e, 1)
        if not kept or total <= 1 or dim % total != 0:
            return None
        return kept if len(kept) > 1 else kept[0]

    fixed = P(*(fix(e, d) for e, d in zip(spec, x.shape)))
    if all(e is None for e in fixed):
        return x
    return jax.lax.with_sharding_constraint(x, fixed)


def mesh_axis_size(name: str) -> int:
    """Size of a mesh axis in the current (abstract) mesh context; 1 if
    absent/no mesh. Includes Manual axes (shard_map context)."""
    mesh = compat.get_abstract_mesh()
    if mesh is None or mesh.empty:
        return 1
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    return sizes.get(name, 1)


def batch_axes() -> Tuple[str, ...]:
    """Mesh axes the global batch is sharded over."""
    return ("pod", "data")


BATCH = ("pod", "data")  # spec entry for the batch dimension


# ---------------------------------------------------------------------------
# parameter sharding rules
# ---------------------------------------------------------------------------

# Patterns are matched in order against the flat param name. `fsdp` entries
# ('data',) are only applied when the arch's optimizer runs in Mode B
# (global momentum); Mode A keeps params replicated over 'data' so each
# replica votes on the full TP shard.
#
# Legend for spec entries: "M" = 'model' (TP/EP), "F" = 'data' (FSDP), None.
_RULES: Tuple[Tuple[str, Tuple[Optional[str], ...]], ...] = (
    # embeddings: vocab over model only — deliberately NOT FSDP-sharded:
    # at vocab/16 they are ~100 MB/chip, and gathering a (vocab-sharded,
    # d-FSDP) table forces the SPMD partitioner into an involuntary full
    # fp32 rematerialisation (measured 2x 3.35 GiB on deepseek-67b). Their
    # gradients take the explicit per-leaf vote instead.
    (r"^embed\.table$", ("M", None)),
    (r"^unembed\.table$", ("M", None)),
    (r"^enc_embed\.pos$", (None, None)),
    # attention (stacked: leading L axis)
    (r"\.attn_wq$|\.xattn_wq$", (None, "F", "M")),
    (r"\.attn_wk$|\.xattn_wk$", (None, "F", "M")),
    (r"\.attn_wv$|\.xattn_wv$", (None, "F", "M")),
    (r"\.attn_wo$|\.xattn_wo$", (None, "M", "F")),
    (r"\.attn_b[qkv]$|\.xattn_b[qkv]$", (None, "M")),
    # dense mlp / shared-expert mlp (stacked)
    (r"\.(mlp|shared)_w_gate$", (None, "F", "M")),
    (r"\.(mlp|shared)_w_up$", (None, "F", "M")),
    (r"\.(mlp|shared)_w_down$", (None, "M", "F")),
    (r"\.shared_gate_w$", (None, "F", None)),
    # MoE experts: expert axis over model (EP); when num_experts is not
    # divisible by the model axis (qwen2-moe: 60 experts on 16) param_spec
    # falls back to sharding the per-expert d_ff (TP-within-expert).
    (r"\.experts_w_gate$", (None, "M", "F", "M2")),
    (r"\.experts_w_up$", (None, "M", "F", "M2")),
    (r"\.experts_w_down$", (None, "M", "M2", "F")),
    (r"\.router_w$", (None, "F", None)),
    # mamba2 (stacked): inner dim over model
    (r"\.mamba_(zproj|xbcproj|dtproj)$", (None, "F", "M")),
    (r"\.mamba_out_proj$", (None, "M", "F")),
    (r"\.mamba_conv_w$", (None, None, "M")),
    (r"\.mamba_conv_b$", (None, "M")),
    (r"\.mamba_norm_scale$", (None, "M")),
    (r"\.mamba_(dt_bias|A_log|D)$", (None, "M")),
    # zamba2 shared block (no leading L axis)
    (r"^shared_block\.attn_wq$", ("F", "M")),
    (r"^shared_block\.attn_wk$", ("F", "M")),
    (r"^shared_block\.attn_wv$", ("F", "M")),
    (r"^shared_block\.attn_wo$", ("M", "F")),
    (r"^shared_block\.mlp_w_gate$", ("F", "M")),
    (r"^shared_block\.mlp_w_up$", ("F", "M")),
    (r"^shared_block\.mlp_w_down$", ("M", "F")),
    # norms etc: replicated
    (r".*", (None,) * 8),
)


def _entry(tag: Optional[str], fsdp: bool) -> Optional[object]:
    if tag in ("M", "M2"):
        return "model"
    if tag == "F":
        return "data" if fsdp else None
    return tag


def param_spec(name: str, shape: Tuple[int, ...], *, fsdp: bool,
               mesh_axes: Tuple[str, ...] = ("data", "model"),
               mesh_shape: Optional[Dict[str, int]] = None) -> P:
    """PartitionSpec for parameter `name` of `shape`.

    Drops a sharded axis whenever the dim is not divisible by the mesh
    axis size (e.g. kv-head projections smaller than the model axis).
    "M2" entries are fallbacks: used only when the "M" dim dropped.
    """
    for pat, tags in _RULES:
        if re.search(pat, name):
            tags = list(tags[: len(shape)])
            tags += [None] * (len(shape) - len(tags))
            entries = [_entry(t, fsdp) if t != "M2" else None for t in tags]
            if mesh_shape:
                for i, e in enumerate(entries):
                    if e is not None and shape[i] % mesh_shape.get(e, 1) != 0:
                        entries[i] = None
            # activate "M2" fallback if the primary "M" was dropped
            if "M2" in tags and not any(
                    e == "model" for e in entries):
                i = tags.index("M2")
                if not mesh_shape or shape[i] % mesh_shape.get("model", 1) == 0:
                    entries[i] = "model"
            # never shard the same mesh axis twice in one spec
            seen = set()
            for i, e in enumerate(entries):
                if e in seen:
                    entries[i] = None
                elif e is not None:
                    seen.add(e)
            return P(*entries)
    raise AssertionError("unreachable: catch-all rule")


def param_specs(shapes: Dict[str, Tuple[int, ...]], *, fsdp: bool,
                mesh_shape: Optional[Dict[str, int]] = None) -> Dict[str, P]:
    return {
        k: param_spec(k, v, fsdp=fsdp, mesh_shape=mesh_shape)
        for k, v in shapes.items()
    }


# ---------------------------------------------------------------------------
# KV-cache sharding
# ---------------------------------------------------------------------------


def kv_cache_spec(num_kv_heads: int, model_axis: int) -> Tuple[P, str]:
    """Spec for (L, B, S, Hkv, hd) caches.

    Shard heads over 'model' when divisible, else shard the sequence
    (flash-decode style — XLA handles the partial-softmax reduction).
    """
    if num_kv_heads % model_axis == 0:
        return P(None, BATCH, None, "model", None), "heads"
    return P(None, BATCH, "model", None, None), "seq"
