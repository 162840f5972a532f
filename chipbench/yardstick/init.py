"""Weights from the seed, by the initialisation rule of the configuration's
source (the configuration file's ``init``).

One jitted call on the device. Leaves are taken in sorted order of their
names and each draws from its own split of the key. By name:

* ``*_scale`` / ``*.scale`` (norm weights): 1;
* ``*mamba_D``: 1, in float32;
* ``*A_log``: log A in float32, A = h for head h = 1..H
  (``"A": "arange"``) or A ~ U(lo, hi) (``"A": [lo, hi]``);
* ``*dt_bias``: softplus⁻¹(dt), dt log-uniform in [``dt_min``,
  ``dt_max``] and at least ``dt_floor``;
* ``embed.table``: N(0, ``embed_std``²);
* ``*_conv_w`` and ``*_conv_b``: by ``conv``, biases 0 under ``normal``
  and of fan-in ``conv_fan_in`` under ``kaiming_uniform``;
* every other leaf (a projection, stored fan-in first): by ``linear``;
  ``*out_proj`` divided by √``out_proj_rescale`` where that is given.

A rule is ``normal`` (N(0, ``std``²)) or ``kaiming_uniform`` (PyTorch's
default for a Linear or a Conv1d: U(±1/√fan_in), fan_in the
second-to-last dimension). Values are drawn in float32 and rounded to
the parameter dtype. The program under test is given these weights; the
reference makes them here itself.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

F32_LEAVES = ("A_log", "mamba_D")


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size (the low and high 32 bits)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


def leaf_dtype(name: str, dtype) -> jnp.dtype:
    return jnp.dtype(jnp.float32 if name.endswith(F32_LEAVES) else dtype)


def _draw(rule: str, rule_args: Dict, shape, key, fan_in: int) -> jax.Array:
    if rule == "normal":
        return jax.random.normal(key, shape, jnp.float32) * rule_args["std"]
    if rule == "kaiming_uniform":
        bound = 1.0 / math.sqrt(fan_in)
        return jax.random.uniform(key, shape, jnp.float32, -bound, bound)
    raise ValueError(f"unknown initialisation rule {rule!r}")


def _leaf(name: str, shape: Tuple[int, ...], key, dtype, rule: Dict
          ) -> jax.Array:
    if name.endswith(("_scale", ".scale")):
        return jnp.ones(shape, dtype)
    if name.endswith("mamba_D"):
        return jnp.ones(shape, jnp.float32)
    if name.endswith("A_log"):
        if rule["A"] == "arange":
            a = jnp.arange(1, shape[-1] + 1, dtype=jnp.float32)
            return jnp.broadcast_to(jnp.log(a), shape)
        lo, hi = rule["A"]
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, lo, hi))
    if name.endswith("dt_bias"):
        lo, hi = math.log(rule["dt_min"]), math.log(rule["dt_max"])
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi))
        dt = jnp.maximum(dt, rule["dt_floor"])
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    if name == "embed.table":
        return (jax.random.normal(key, shape, jnp.float32)
                * rule["embed_std"]).astype(dtype)
    if name.endswith("_conv_b"):
        if rule["conv"] == "normal":
            return jnp.zeros(shape, dtype)
        fan_in = rule["conv_fan_in"]
        return _draw(rule["conv"], rule, shape, key, fan_in).astype(dtype)
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    kind = rule["conv"] if name.endswith("_conv_w") else rule["linear"]
    w = _draw(kind, rule, shape, key, fan_in)
    if name.endswith("out_proj") and "out_proj_rescale" in rule:
        w = w / math.sqrt(rule["out_proj_rescale"])
    return w.astype(dtype)


def init_leaves(shapes: Dict[str, Tuple[int, ...]], key, c: Dict
                ) -> Dict[str, jax.Array]:
    """All leaves of configuration `c` (traceable; call inside a jit)."""
    dtype = jnp.dtype(c["dtype"])
    names = sorted(shapes)
    keys = jax.random.split(key, len(names))
    return {n: _leaf(n, shapes[n], k, dtype, c["init"])
            for n, k in zip(names, keys)}


def init_params(shapes: Dict[str, Tuple[int, ...]], seed: int, c: Dict,
                device) -> Dict[str, jax.Array]:
    """All leaves on `device`, in one jitted call."""
    fn = jax.jit(lambda k: init_leaves(shapes, k, c),
                 out_shardings=jax.sharding.SingleDeviceSharding(device))
    return fn(jax.device_put(seed_key(seed), device))
