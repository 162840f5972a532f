"""Readings of a parameter or momentum tree, shared by the harness (on
the program's state) and the reference (on its own), so both sides are
read the same way. Each returns a jitted function: one call per reading.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from chipbench.yardstick import init as yinit



def _norm(x, axes=None):
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)), axis=axes))


def leaf_norms():
    """tree -> {leaf: L2 norm}."""
    return jax.jit(lambda T: {k: _norm(v) for k, v in T.items()})


def replica_norms():
    """tree of (R, ...) leaves -> {leaf: (R,) L2 norms, one per replica}."""
    return jax.jit(lambda T: {k: _norm(v, tuple(range(1, v.ndim)))
                              for k, v in T.items()})


def delta_norms(shapes: Dict[str, Tuple[int, ...]], c: Dict):
    """(params, key) -> {leaf: ||params - the key's initial weights||}
    under configuration `c`."""
    def fn(P, key):
        P0 = yinit.init_leaves(shapes, key, c)
        return {k: _norm(P[k].astype(jnp.float32)
                         - P0[k].astype(jnp.float32)) for k in P}
    return jax.jit(fn)


def checksum():
    """array -> (sum of its bit patterns as uint32, sum of squares): equal
    copies give equal pairs."""
    def fn(x):
        bits = {2: jnp.uint16, 4: jnp.uint32}[x.dtype.itemsize]
        b = jax.lax.bitcast_convert_type(x, bits).astype(jnp.uint32)
        return jnp.sum(b), jnp.sum(jnp.square(x.astype(jnp.float32)))
    return jax.jit(fn)
