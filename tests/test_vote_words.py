"""The 1-bit wire's pack and tally against independent numpy oracles.

Bit j of word k is element 32k+j, and a tie votes +1. ``pack_signs`` must
equal ``np.packbits(bitorder="little")`` read as little-endian uint32;
``packed_majority`` and ``Allgather1BitStrategy.tally`` must equal a numpy
count of each coordinate's set bits against 2·count >= M. The tally works
on whole words: no intermediate of its trace may be larger than its input.
The last case votes reduced zamba2 leaves through ``vote_api`` on four CPU
devices and holds the 1-bit majority to ``psum_int8``'s counts.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import sign_compress as sc
from repro.core.vote_engine import Allgather1BitStrategy

_REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
VOTERS = (1, 2, 3, 4, 5, 8, 15, 16, 32, 33)
WIDTHS = (32, 64, 4224, 4096)


def _np_pack(x: np.ndarray) -> np.ndarray:
    bits = (x >= 0).astype(np.uint8)
    return np.packbits(bits, axis=-1, bitorder="little").view("<u4")


def _np_majority(words: np.ndarray) -> np.ndarray:
    m = words.shape[0]
    bits = np.unpackbits(words.view(np.uint8), axis=-1, bitorder="little")
    count = bits.sum(axis=0, dtype=np.int64)
    return _np_pack(np.where(2 * count >= m, 1, -1))


def _leaf(width: int) -> tuple:
    """An N-D leaf shape whose last axis is `width` wide."""
    return (2, 3, width) if width < 1024 else (2, width)


@pytest.mark.parametrize("unit", ["signs", "words"])
@pytest.mark.parametrize("width", WIDTHS)
def test_pack_signs_is_numpy_packbits(width, unit):
    n = width if unit == "signs" else width * sc.PACK
    rng = np.random.default_rng(width)
    x = rng.standard_normal(_leaf(n)).astype(np.float32)
    x[..., ::7] = 0.0                       # sign(0) packs as +1
    got = np.asarray(sc.pack_signs(jnp.asarray(x)))
    want = _np_pack(x)
    assert got.dtype == np.uint32 and got.shape == want.shape
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(
        np.asarray(sc.pack_signs(sc.sign_ternary(jnp.asarray(x)))), want)


@pytest.mark.parametrize("width", WIDTHS)
@pytest.mark.parametrize("m", VOTERS)
def test_majority_is_numpy_count(m, width):
    rng = np.random.default_rng(1000 * m + width)
    words = rng.integers(0, 2**32, (m,) + _leaf(width), dtype=np.uint32)
    want = _np_majority(words)
    got = np.asarray(sc.packed_majority(jnp.asarray(words)))
    np.testing.assert_array_equal(got, want)
    tally = Allgather1BitStrategy().tally(jnp.asarray(words), m)
    np.testing.assert_array_equal(np.asarray(tally), want)


@pytest.mark.parametrize("m", [v for v in VOTERS if v % 2 == 0])
def test_every_coordinate_tied_votes_plus_one(m):
    rng = np.random.default_rng(m)
    half = rng.integers(0, 2**32, (m // 2, 2, 4224), dtype=np.uint32)
    words = np.concatenate([half, ~half])
    rng.shuffle(words)                      # voters in any order
    want = np.full((2, 4224), 0xFFFFFFFF, np.uint32)
    np.testing.assert_array_equal(_np_majority(words), want)
    np.testing.assert_array_equal(
        np.asarray(sc.packed_majority(jnp.asarray(words))), want)
    np.testing.assert_array_equal(
        np.asarray(Allgather1BitStrategy().tally(jnp.asarray(words), m)),
        want)


def _sizes(jaxpr):
    """Element counts of every value a jaxpr (and its sub-jaxprs) makes."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            yield eqn.primitive.name, int(np.prod(v.aval.shape))
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _sizes(inner)


@pytest.mark.parametrize("tally", ["packed_majority", "strategy"])
def test_tally_makes_nothing_larger_than_its_input(tally):
    m, leaf = 4, (3, 5, 40)
    fn = (sc.packed_majority if tally == "packed_majority" else
          lambda a: Allgather1BitStrategy().tally(a, m))
    closed = jax.make_jaxpr(fn)(jnp.zeros((m,) + leaf, jnp.uint32))
    limit = m * int(np.prod(leaf))          # the input: M x output words
    sizes = list(_sizes(closed.jaxpr))
    assert sizes
    too_big = [(name, n) for name, n in sizes if n > limit]
    assert not too_big, too_big


_FOUR = textwrap.dedent("""
    import json, sys
    sys.path.insert(0, {src!r})
    import jax, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro import compat
    from repro.configs.base import VoteStrategy
    from repro.core import vote_api as va
    from repro.launch import train as LT
    from repro.launch.mesh import make_replica_mesh
    cfg, _ = LT.build("zamba2-1.2b", reduced=True, batch=4, seq=32,
                      microbatches=1)
    mesh = make_replica_mesh(jax.devices()[:4])
    rng = np.random.default_rng(0)
    tree = {{}}
    for name, shape in cfg.param_shapes().items():
        v = rng.standard_normal((4,) + tuple(shape)).astype(np.float32)
        v[2:, ..., 1::5] = -v[:2, ..., 1::5]      # tied coordinates
        tree[name] = v
    spec = {{k: P("data") for k in tree}}
    backend = va.MeshBackend(axes=("data",))

    def vote(strategy):
        def f(t):
            out = backend.execute(va.VoteRequest(
                payload={{k: x[0] for k, x in t.items()}}, form="tree",
                strategy=strategy))
            return {{k: x[None] for k, x in out.votes.items()}}
        return jax.jit(compat.shard_map(
            f, mesh=mesh, in_specs=(spec,), out_specs=spec,
            axis_names={{"data"}}, check_vma=False))(tree)

    one_bit = vote(VoteStrategy.ALLGATHER_1BIT)
    counts = vote(VoteStrategy.PSUM_INT8)
    report = {{}}
    for k, v in tree.items():
        got = np.asarray(one_bit[k])
        want = np.where(np.asarray(counts[k]) >= 0, 1, -1)
        count = np.sign(v).sum(axis=0)
        report[k] = [list(v.shape[1:]),
                     bool((got == want).all()),
                     bool((got[0] == np.where(count >= 0, 1, -1)).all()),
                     int((count == 0).sum())]
    print(json.dumps(report))
""")


def test_one_bit_vote_on_four_devices_is_psum_counts_with_ties_plus_one():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    res = subprocess.run(
        [sys.executable, "-c", _FOUR.format(src=os.path.join(_REPO, "src"))],
        env=env, capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    report = json.loads(res.stdout.strip().splitlines()[-1])
    assert len(report) > 10
    for name, (shape, equals_psum, equals_numpy, ties) in report.items():
        assert equals_psum and equals_numpy, (name, shape)
    assert any(ties for *_, ties in report.values())
