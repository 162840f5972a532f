"""The mesh / shard_map surface the repo calls, on the installed JAX (0.9).

Each name here is a thin pass-through to the JAX API of the same name.
Call sites import them from one place; rewriting them to bare ``jax.*``
is open work (ROADMAP C2).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Set

import jax
import jax.numpy as jnp

__all__ = [
    "AxisType", "all_gather", "axis_size", "cost_analysis_dict",
    "get_abstract_mesh", "make_mesh", "set_mesh", "shard_map",
    "tree_leaves_with_path",
]

AxisType = jax.sharding.AxisType
get_abstract_mesh = jax.sharding.get_abstract_mesh
set_mesh = jax.set_mesh
axis_size = jax.lax.axis_size
tree_leaves_with_path = jax.tree.leaves_with_path


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str],
              *, axis_types: Optional[Sequence[Any]] = None, **kw):
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=axis_types, **kw)


def shard_map(f, *, mesh=None, in_specs, out_specs,
              axis_names: Optional[Set[str]] = None, check_vma: bool = False):
    """``jax.shard_map``; `mesh` defaults to the active one and
    `axis_names` (the manual axes) to all of its axes."""
    kwargs = dict(in_specs=in_specs, out_specs=out_specs,
                  check_vma=check_vma)
    if axis_names is not None:
        kwargs["axis_names"] = set(axis_names)
    if mesh is not None:
        kwargs["mesh"] = mesh
    return jax.shard_map(f, **kwargs)


def axis_index(axis_name: str, like=None):
    """``jax.lax.axis_index``; `like` is accepted and unused."""
    del like
    return jax.lax.axis_index(axis_name)


def all_gather(x, axis_name: str, *, axis: int = 0, tiled: bool = False):
    return jax.lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def with_sharding_constraint(x, spec):
    """``jax.lax.with_sharding_constraint`` with a bare PartitionSpec; a
    fully unconstrained spec is a no-op (it needs no active mesh)."""
    if all(e is None for e in spec):
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def pad_trailing(x, count: int):
    """Zero-pad the last dim by `count` (`x` itself when count == 0)."""
    if count == 0:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, count)])


def cost_analysis_dict(compiled) -> Dict[str, float]:
    """``compiled.cost_analysis()`` as a dict ({} when unavailable)."""
    return dict(compiled.cost_analysis() or {})
