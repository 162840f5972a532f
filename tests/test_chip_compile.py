"""Every Pallas entry point compiles for one TPU v5e chip at 2^24 elements,
with M=4 voters and, for the voting kernels, with the M of their callers
(8 and 15 in ``benchmarks/bench_comm.py``) and 32; the fused vote also
at M=64, where its 8-row blocks need more than the default scoped VMEM.

Interpret mode (what the CPU tests run) accepts kernels that the chip's
compiler refuses: a lane row reshaped in the kernel, a 1-D block that
does not match the output's tiling. Here each kernel is lowered for a
described, not attached, v5e chip and compiled by the TPU compiler; the
test asserts that the compiled program holds the kernel
(``tpu_custom_call``). Nothing runs, so results are checked elsewhere:
``tests/test_kernels.py`` in interpret mode, ``chip_smoke.py`` on the chip.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file. The tests skip only where the TPU compiler (libtpu) is
not installed; any other failure to describe the chip fails them.
"""
import importlib.util
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import (bitpack as bp, fused_vote as fv, signum_update as su,
                           ternary_pack as tp, vote as vt)

N = 1 << 24     # elements per kernel call
M = 4           # voters (the 4-chip host's replicas)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("no TPU compiler (libtpu) in this installation")
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one; keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# (kernel, static args, [(shape, dtype)]) — the shapes the public ops
# wrappers hand each kernel for an N-element tensor and M voters
CASES = {
    "bitpack_2d": (bp.bitpack_2d, (), [((N // 4096, 4096), jnp.float32)]),
    "bitunpack_2d": (bp.bitunpack_2d, (), [((N // 4096, 128), jnp.uint32)]),
    "ternary_pack_2d": (tp.ternary_pack_2d, (),
                        [((N // 2048, 2048), jnp.int32)]),
    "momentum_sign_pack": (su.momentum_sign_pack, (0.9,),
                           [((N // 4096, 4096), jnp.float32)] * 2),
    "apply_vote": (su.apply_vote, (1e-3, 0.0),
                   [((N // 4096, 4096), jnp.float32),
                    ((N // 4096, 128), jnp.uint32)]),
}
for m, sfx in ((M, ""), (8, "_m8"), (15, "_m15"), (32, "_m32")):
    CASES.update({
        f"fused_majority_2d_f32{sfx}": (fv.fused_majority_2d, (),
                                       [((m, N), jnp.float32)]),
        f"fused_majority_2d_bf16{sfx}": (fv.fused_majority_2d, (),
                                        [((m, N), jnp.bfloat16)]),
        f"majority_packed{sfx}": (vt.majority_packed, (),
                                  [((m, N // 32), jnp.uint32)]),
        f"ternary_tally_packed{sfx}": (tp.ternary_tally_packed, (),
                                       [((m, N // 16), jnp.uint32)]),
    })
CASES["fused_majority_2d_f32_m64"] = (fv.fused_majority_2d, (),
                                      [((64, N), jnp.float32)])


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, static, shapes = CASES[name]
    args = [_spec(s, d, one_chip) for s, d in shapes]
    compiled = fn.lower(*args, *static).compile()
    assert "tpu_custom_call" in compiled.as_text()
