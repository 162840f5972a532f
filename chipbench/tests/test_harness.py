"""The harness at test size on the CPU: a cell added as data alone is
found and run; the run is correct; the control and each fault that a
training cell can have make ``correct`` false."""
import json
import os
import subprocess
import sys
import time

import jax
import pytest

from chipbench import harness, readings
from chipbench.tests import faults, tiny
from chipbench.yardstick import compare

REPO = tiny.REPO


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench"))


def _run(root, cell, seed=1):
    return harness.run(root, cell, seed, 0.5, False, time.perf_counter(),
                       jax.devices())


def test_cell_added_as_data_alone(root):
    one = harness.load_cell(root, "zamba2-tiny.t4-s64")
    four = harness.load_cell(root, "zamba2-tiny.dp4-1bit-t4-s64")
    assert {m["name"] for m in one.end_to_end} == {"tokens_per_s", "setup_s"}
    assert one.chips == 1 and four.chips == 4 and four.rows == 16
    assert four.traffic["vote_strategy"] == "allgather_1bit"
    assert "replicas_differ" in four.limits
    out = _run(root, "zamba2-tiny.t4-s64")
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"tokens_per_s", "setup_s"}
    assert out["device"]["platform"] == "cpu"
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("cell", ["zamba2-tiny.t4-s64", "mamba2-tiny.t4-s64"])
@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_fault_makes_run_incorrect(root, cell, fault):
    with faults.FAULTS[fault]():
        out = _run(root, cell)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("cell", ["zamba2-tiny.t4-s64", "mamba2-tiny.t4-s64"])
def test_control_fails_the_limits(root, cell):
    nums, limits = readings.control_readings(root, cell, 1, jax.devices())
    assert not compare.verdict(nums, limits), nums


_FOUR = """
import json, sys, time
sys.path[:0] = [{repo!r}, {src!r}]
from pathlib import Path
import jax
from chipbench import harness
from chipbench.tests import faults
root = Path({root!r})
cell = "zamba2-tiny.dp4-1bit-t4-s64"
sound = harness.run(root, cell, 1, 0.5, False, time.perf_counter(),
                    jax.devices())
with faults.exchange_left_out():
    broken = harness.run(root, cell, 1, 0.5, False, time.perf_counter(),
                         jax.devices())
print(json.dumps([sound["correct"], broken["correct"], broken["checks"]]))
"""


def test_four_replicas_and_the_exchange_left_out(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = _FOUR.format(repo=str(REPO), src=str(REPO / "src"),
                        root=str(root))
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    sound, broken, checks = json.loads(res.stdout.strip().splitlines()[-1])
    assert sound and not broken, checks


_INT8 = """
import json, sys, time
sys.path[:0] = [{repo!r}, {src!r}]
from pathlib import Path
import jax
from chipbench import harness, readings
from chipbench.tests import faults
root = Path({root!r})
cell = "zamba2-tiny.dp4-int8-t4-s64"
def run():
    return harness.run(root, cell, 1, 0.5, False, time.perf_counter(),
                       jax.devices())
out = {{"sound": run()}}
for name in ("exchange_left_out", "state_unchanged", "half_batch"):
    with faults.FAULTS[name]():
        out[name] = run()
prog = harness.Program(harness.load_cell(root, cell), jax.devices())
gen = prog.tokens(1)
params, opt_state = prog.start(1)
_, _, mine = prog.check_steps(params, opt_state, gen, 1)
batches = [gen.batch(s) for s in range(harness.CHECK_STEPS)]
out["tie_plus_one"] = readings.wire_readings(
    mine, prog.family, prog.c, "allgather_1bit", prog.devices, 1, batches)
print(json.dumps(out))
"""


def test_int8_wire_on_four_replicas(root):
    """The int8 wire's cell is correct with every replica alike; each
    fault it can have makes it incorrect; and the reference voting by the
    1-bit rule (ties to +1) in place of the wire's (ties abstain) fails
    its limit on the change of the parameters."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = _INT8.format(repo=str(REPO), src=str(REPO / "src"),
                        root=str(root))
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    sound = out.pop("sound")
    assert sound["correct"], sound["checks"]
    assert sound["checks"]["replicas_differ"]["value"] == 0
    tie = out.pop("tie_plus_one")
    limits = harness.load_cell(root, "zamba2-tiny.dp4-int8-t4-s64").limits
    assert tie["delta_norm_gap"] > limits["delta_norm_gap"], tie
    assert tie["grad_norm_gap"] == sound["checks"]["grad_norm_gap"]["value"]
    for name, broken in out.items():
        assert not broken["correct"], (name, broken["checks"])


def test_no_tpu_exits_before_any_phase():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run(
        [sys.executable, str(REPO / "chipbench" / "run.py"), "--workload",
         "zamba2-1.2b.b4-s2048", "--seed", "1", "--seconds", "1"],
        env=env, capture_output=True, text=True, timeout=120, cwd=REPO)
    assert res.returncode == 2
    assert res.stdout == ""
