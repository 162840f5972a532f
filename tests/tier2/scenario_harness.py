"""Scenario Lab multi-device validation harness, run in a subprocess by
test_harness8.py (so the main pytest session keeps 1 CPU device).

On an 8-device host platform it validates the Scenario Lab's central
claim — the virtual mesh IS the wire path:

  1. compat on 8 devices: axis_index / all_gather in a partial-auto
     region, straggler_mask_for, and apply_adversary (mesh) ==
     apply_adversary_stacked (virtual) for every stochastic mode;
  2. mesh backend == virtual backend, bit for bit (digest equality), for
     every strategy x adversary-mode x straggler x elastic composition,
     on both mesh styles (partial-auto 'data_model' and fully-manual
     'data_only');
  3. the honest path decides bit-identically across all three strategies
     on the mesh backend (odd voter count).

Run with ``virtual-only`` as argv[1] to skip the mesh half — the parent
test runs that mode under a 1-device platform and diffs the printed
VDIGEST lines against the 8-device run, which is the "reproducible
across host counts" guarantee, asserted rather than assumed.
"""
import os
import sys

if os.environ.get("XLA_FLAGS") is None:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.compat import AxisType
from repro.configs.base import ByzantineConfig, VoteStrategy
from repro.core import byzantine, sign_compress as sc
from repro.distributed import fault_tolerance as ft
from repro.sim import (AdversarySpec, ElasticEvent, PlanSpec,
                       ScenarioRunner, ScenarioSpec)

RNG = np.random.default_rng(0)


def harness_specs():
    S = VoteStrategy
    return [
        # odd voter count: honest path must be strategy-independent
        ScenarioSpec("h8/honest7", n_workers=7, n_steps=5, dim=129,
                     strategy=S.PSUM_INT8),
        ScenarioSpec("h8/flip_stale", n_workers=8, n_steps=5, dim=128,
                     strategy=S.ALLGATHER_1BIT,
                     adversary=AdversarySpec("sign_flip", 0.25),
                     straggler_fraction=0.25),
        ScenarioSpec("h8/random", n_workers=8, n_steps=5, dim=100,
                     strategy=S.PSUM_INT8,
                     adversary=AdversarySpec("random", 0.375)),
        ScenarioSpec("h8/blind_half", n_workers=8, n_steps=5, dim=96,
                     strategy=S.HIERARCHICAL,
                     adversary=AdversarySpec("blind", 0.5, flip_prob=0.8)),
        ScenarioSpec("h8/zero", n_workers=8, n_steps=4, dim=64,
                     strategy=S.HIERARCHICAL,
                     adversary=AdversarySpec("zero", 0.25)),
        ScenarioSpec("h8/collude_elastic", n_workers=8, n_steps=9, dim=64,
                     strategy=S.PSUM_INT8,
                     adversary=AdversarySpec("colluding", 0.375),
                     straggler_fraction=0.125,
                     elastic=(ElasticEvent(3, 4, "pod loss"),
                              ElasticEvent(6, 6, "rejoin"))),
        # codec axis (DESIGN.md §8): every non-default codec through the
        # same mesh==virtual and host-count-invariance gauntlet
        ScenarioSpec("h8/ef_flip_stale", n_workers=8, n_steps=6, dim=100,
                     strategy=S.ALLGATHER_1BIT, codec="ef_sign",
                     adversary=AdversarySpec("sign_flip", 0.25),
                     straggler_fraction=0.25),
        ScenarioSpec("h8/ternary_random", n_workers=8, n_steps=6, dim=90,
                     strategy=S.ALLGATHER_1BIT, codec="ternary2bit",
                     adversary=AdversarySpec("random", 0.375)),
        ScenarioSpec("h8/weighted_flip_elastic", n_workers=8, n_steps=8,
                     dim=96, strategy=S.ALLGATHER_1BIT,
                     codec="weighted_vote",
                     adversary=AdversarySpec("sign_flip", 0.375),
                     elastic=(ElasticEvent(4, 6, "pod loss"),)),
        # VotePlan axis (DESIGN.md §9): bucketed wire schedules through
        # the same mesh==virtual and host-count-invariance gauntlet —
        # a mixed-codec plan under a colluding coalition, a weighted
        # plan crossing an elastic rescale, and a bucketed hierarchical
        # wire with stragglers
        ScenarioSpec("h8/plan_mixed_collude", n_workers=8, n_steps=6,
                     dim=128, strategy=S.ALLGATHER_1BIT,
                     adversary=AdversarySpec("colluding", 0.375),
                     plan=PlanSpec(bucket_bytes=8,
                                   leaves=(("embed.table", 48),
                                           ("body.w", 80)),
                                   codec_map=(("embed*", "ternary2bit"),
                                              ("*", "sign1bit")))),
        ScenarioSpec("h8/plan_weighted_elastic", n_workers=8, n_steps=8,
                     dim=96, strategy=S.ALLGATHER_1BIT,
                     codec="weighted_vote",
                     adversary=AdversarySpec("sign_flip", 0.375),
                     elastic=(ElasticEvent(4, 6, "pod loss"),),
                     plan=PlanSpec(bucket_bytes=6)),
        ScenarioSpec("h8/plan_hier_stale", n_workers=8, n_steps=5,
                     dim=100, strategy=S.HIERARCHICAL,
                     straggler_fraction=0.25,
                     adversary=AdversarySpec("random", 0.25),
                     plan=PlanSpec(bucket_bytes=5)),
    ]


# ---------------------------------------------------------------------------
# 1. compat shims on the 8-device mesh
# ---------------------------------------------------------------------------


def check_compat_shims_8dev():
    mesh = compat.make_mesh((8, 1), ("data", "model"),
                            axis_types=(AxisType.Auto,) * 2)

    def f(x):
        idx = compat.axis_index("data", like=x)
        g = compat.all_gather(x[0], "data", tiled=False)
        mask = ft.straggler_mask_for(("data",), 3, like=x)
        return (jnp.full((1,), idx, jnp.int32),
                g[None],
                jnp.full((1,), mask))

    sh = compat.shard_map(f, mesh=mesh, in_specs=(P("data"),),
                          out_specs=(P("data"), P("data"), P("data")),
                          axis_names={"data"}, check_vma=False)
    x = jnp.asarray(RNG.normal(size=(8, 12)).astype(np.float32))
    idx, gathered, mask = jax.jit(sh)(x)
    np.testing.assert_array_equal(np.asarray(idx), np.arange(8))
    for r in range(8):
        np.testing.assert_array_equal(np.asarray(gathered)[r],
                                      np.asarray(x))
    np.testing.assert_array_equal(np.asarray(mask),
                                  np.arange(8) < 3)
    print("OK compat shims on 8 devices (axis_index/all_gather/mask)")


def check_adversary_mesh_equals_stacked():
    """apply_adversary on 8 real replicas == apply_adversary_stacked on
    the stacked tensor — the lemma behind mesh==virtual, directly."""
    mesh = compat.make_mesh((8, 1), ("data", "model"),
                            axis_types=(AxisType.Auto,) * 2)
    signs = jnp.asarray(
        RNG.integers(-1, 2, size=(8, 77)).astype(np.int8))
    for mode in ("sign_flip", "zero", "random", "colluding", "blind"):
        cfg = ByzantineConfig(mode=mode, num_adversaries=3, seed=5,
                              flip_prob=0.7)

        def f(s, step):
            out = byzantine.apply_adversary(s[0], cfg, ("data",),
                                            step=step, salt=99)
            return out[None]

        sh = compat.shard_map(f, mesh=mesh, in_specs=(P("data"), P()),
                              out_specs=P("data"), axis_names={"data"},
                              check_vma=False)
        got = np.asarray(jax.jit(sh)(signs, jnp.int32(4)))
        want = np.asarray(byzantine.apply_adversary_stacked(
            signs, cfg, step=jnp.int32(4), salt=99))
        np.testing.assert_array_equal(got, want, err_msg=mode)
    print("OK apply_adversary mesh == stacked for every mode")


# ---------------------------------------------------------------------------
# 2./3. backend bit-identity
# ---------------------------------------------------------------------------


def check_backends(mesh_too: bool):
    for spec in harness_specs():
        tv = ScenarioRunner(spec, backend="virtual").run()
        print(f"VDIGEST {spec.name} {tv.digest}")
        if not mesh_too:
            continue
        styles = ("data_model", "data_only") \
            if spec.name == "h8/flip_stale" else ("data_model",)
        for style in styles:
            tm = ScenarioRunner(spec, backend="mesh",
                                mesh_style=style).run()
            assert tm.digest == tv.digest, (
                f"{spec.name} [{style}]: mesh != virtual "
                f"({tm.digest[:12]} vs {tv.digest[:12]})")
        print(f"OK mesh == virtual: {spec.name}")


def check_honest_mesh_strategy_identity():
    digests = {}
    for strategy in (VoteStrategy.PSUM_INT8, VoteStrategy.ALLGATHER_1BIT,
                     VoteStrategy.HIERARCHICAL):
        spec = ScenarioSpec("h8/honest_id", n_workers=7, n_steps=4, dim=96,
                            strategy=strategy)
        digests[strategy.value] = ScenarioRunner(
            spec, backend="mesh").run().digest
    assert len(set(digests.values())) == 1, digests
    print("OK honest path bit-identical across strategies on the mesh")


if __name__ == "__main__":
    virtual_only = len(sys.argv) > 1 and sys.argv[1] == "virtual-only"
    check_backends(mesh_too=not virtual_only)
    if not virtual_only:
        check_compat_shims_8dev()
        check_adversary_mesh_equals_stacked()
        check_honest_mesh_strategy_identity()
    print("ALL SCENARIO HARNESS CHECKS PASSED")
