"""Regression tests for repro/compat.py, the pass-through layer over the
JAX mesh/shard_map API that every caller imports.

The surface under test: ``shard_map`` kwargs (axis_names/check_vma,
mesh from context), ``axis_index`` / ``all_gather`` inside a
partial-manual region, ``pad_trailing``,
``set_mesh`` / ``get_abstract_mesh``, and ``make_mesh`` with
axis_types — all on the 1-device harness here; the 8-device half lives in
``tests/tier2/scenario_harness.py`` (XLA_FLAGS-forced device count, run
by test_harness8.py).
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.compat import AxisType


def _mesh11():
    return compat.make_mesh((1, 1), ("data", "model"),
                            axis_types=(AxisType.Auto,) * 2)


def _mesh1():
    return compat.make_mesh((1,), ("data",),
                            axis_types=(AxisType.Auto,))


# ---------------------------------------------------------------------------
# shard_map kwarg surface
# ---------------------------------------------------------------------------


def test_shard_map_kwargs_partial_manual():
    """kwargs (axis_names subset, check_vma) map through; 'model' stays
    auto."""
    mesh = _mesh11()

    def f(x):
        return x * compat.axis_size("data")

    sh = compat.shard_map(f, mesh=mesh, in_specs=(P("data"),),
                          out_specs=P("data"), axis_names={"data"},
                          check_vma=False)
    out = jax.jit(sh)(jnp.ones((1, 4)))
    np.testing.assert_array_equal(np.asarray(out), np.ones((1, 4)))


def test_shard_map_full_manual_defaults():
    """Omitted axis_names means manual over every mesh axis."""
    mesh = _mesh1()

    def f(x):
        return x + compat.axis_size("data")

    sh = compat.shard_map(f, mesh=mesh, in_specs=(P("data"),),
                          out_specs=P("data"))
    out = jax.jit(sh)(jnp.zeros((1, 3)))
    np.testing.assert_array_equal(np.asarray(out), np.ones((1, 3)))


def test_shard_map_mesh_from_context():
    """mesh=None resolves from the set_mesh context."""
    mesh = _mesh11()
    with compat.set_mesh(mesh):
        sh = compat.shard_map(lambda x: x * 2.0, in_specs=(P("data"),),
                              out_specs=P("data"), axis_names={"data"},
                              check_vma=False)
        out = jax.jit(sh)(jnp.ones((1, 2)))
    np.testing.assert_array_equal(np.asarray(out), 2 * np.ones((1, 2)))


def test_get_abstract_mesh_views():
    mesh = _mesh11()
    with compat.set_mesh(mesh):
        view = compat.get_abstract_mesh()
        assert not view.empty
        assert tuple(view.axis_names) == ("data", "model")
    # outside any context: empty view, never an exception
    outside = compat.get_abstract_mesh()
    assert hasattr(outside, "empty")


# ---------------------------------------------------------------------------
# collectives and axis index (partial-auto region)
# ---------------------------------------------------------------------------


def test_axis_index_with_anchor_partial_auto():
    mesh = _mesh11()

    def f(x):
        idx = compat.axis_index("data", like=x)
        return x + idx.astype(x.dtype)

    sh = compat.shard_map(f, mesh=mesh, in_specs=(P("data"),),
                          out_specs=P("data"), axis_names={"data"},
                          check_vma=False)
    out = jax.jit(sh)(jnp.zeros((1, 4)))
    np.testing.assert_array_equal(np.asarray(out), np.zeros((1, 4)))


def test_all_gather_tiled_and_stacked_partial_auto():
    mesh = _mesh11()
    x = jnp.arange(6, dtype=jnp.float32).reshape(1, 6)

    def f(xl):
        t = compat.all_gather(xl[0], "data", axis=0, tiled=True)
        s = compat.all_gather(xl[0], "data", tiled=False)
        return t[None], s[None]

    sh = compat.shard_map(f, mesh=mesh, in_specs=(P("data"),),
                          out_specs=(P("data"), P("data")),
                          axis_names={"data"}, check_vma=False)
    tiled, stacked = jax.jit(sh)(x)
    np.testing.assert_array_equal(np.asarray(tiled)[0], np.asarray(x)[0])
    np.testing.assert_array_equal(np.asarray(stacked)[0, 0],
                                  np.asarray(x)[0])


def test_pad_trailing_and_zeros_like_inside_region():
    mesh = _mesh11()

    def f(x):
        return compat.pad_trailing(x[0], 3)[None]

    sh = compat.shard_map(f, mesh=mesh, in_specs=(P("data"),),
                          out_specs=P("data"), axis_names={"data"},
                          check_vma=False)
    p = jax.jit(sh)(jnp.ones((1, 5)))
    np.testing.assert_array_equal(
        np.asarray(p)[0], np.concatenate([np.ones(5), np.zeros(3)]))


def test_pad_trailing_noop_and_plain():
    x = jnp.ones((2, 5))
    assert compat.pad_trailing(x, 0) is x
    np.testing.assert_array_equal(
        np.asarray(compat.pad_trailing(x, 2))[:, 5:], np.zeros((2, 2)))


def test_axis_size_inside_and_make_mesh_tolerance():
    # make_mesh passes axis_types through
    mesh = compat.make_mesh((1,), ("data",),
                            axis_types=(AxisType.Auto,))
    assert tuple(mesh.axis_names) == ("data",)

    def f(x):
        return x * compat.axis_size("data")

    sh = compat.shard_map(f, mesh=mesh, in_specs=(P("data"),),
                          out_specs=P("data"))
    np.testing.assert_array_equal(
        np.asarray(jax.jit(sh)(jnp.ones((1, 2)))), np.ones((1, 2)))


def test_engine_vote_runs_inside_one_device_region():
    """The full VoteEngine wire path (every strategy) composes with the
    compat layer on the 1-device partial-auto mesh — the configuration
    every laptop run of the trainer uses."""
    from repro.configs.base import VoteStrategy
    from repro.core.vote_engine import VoteEngine

    mesh = _mesh11()
    x = jnp.asarray(np.linspace(-1, 1, 37)[None], jnp.float32)
    for strategy in (VoteStrategy.PSUM_INT8, VoteStrategy.ALLGATHER_1BIT,
                     VoteStrategy.HIERARCHICAL):
        eng = VoteEngine(strategy=strategy, axes=("data",))

        def f(vals):
            return eng.vote(vals[0])[None]

        sh = compat.shard_map(f, mesh=mesh, in_specs=(P("data"),),
                              out_specs=P("data"), axis_names={"data"},
                              check_vma=False)
        out = np.asarray(jax.jit(sh)(x))[0]
        want = np.sign(np.asarray(x)[0])
        if strategy != VoteStrategy.PSUM_INT8:
            want = np.where(np.asarray(x)[0] >= 0, 1, -1)  # M=1 binarises
        np.testing.assert_array_equal(out, want, err_msg=str(strategy))
