"""Device scopes and set-up phase counters (DESIGN.md §13).

The train step names its layers with ``jax.named_scope`` (``obs/scopes.py``):
each scope must reach the optimized HLO's ``op_name`` metadata, where a
device trace reads it, in the forward (``jvp(``) and in the backward
(``transpose(``). The compile watch counts JAX's set-up phases by their
exact event names: one backend compile per fresh ``jit``, nothing on a
call that hits the in-memory cache, a persistent-cache hit as a hit.
"""
from __future__ import annotations

import dataclasses
import io
import json
import os
import re
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.obs import recorder as obs

_REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
_WRAPPER = re.compile(r"^(?:jvp|transpose)\((.*)\)$")


def _op_names(hlo_text: str):
    return re.findall(r'op_name="([^"]*)"', hlo_text)


def _in_scope(op_name: str, scope: str) -> bool:
    """`scope` is a whole path segment of `op_name` once JAX's transform
    wrappers are taken off (never a substring of a segment)."""
    for seg in op_name.split("/"):
        m = _WRAPPER.match(seg)
        while m:
            seg = m.group(1)
            m = _WRAPPER.match(seg)
        if seg == scope:
            return True
    return False


def _step_hlo(arch: str) -> str:
    from repro.launch import train as LT
    from repro.train import train_step as TS
    cfg, tcfg = LT.build(arch, reduced=True, batch=2, seq=32,
                         microbatches=2)
    tcfg = dataclasses.replace(tcfg, remat="full")
    art = TS.make_train_step(cfg, tcfg)
    params, opt_state = TS.abstract_state(cfg, tcfg, art)
    batch = {"tokens": jax.ShapeDtypeStruct((2, 32), jnp.int32)}
    return art.step_fn.lower(params, opt_state, batch,
                             np.int32(0)).compile().as_text()


@pytest.mark.parametrize("arch,model_scopes", [
    ("zamba2-1.2b", ("mixer", "ssd", "shared_block", "lm_head")),
    ("mamba2-2.7b", ("mixer", "ssd", "lm_head")),
])
def test_train_step_scopes_reach_the_compiled_hlo(arch, model_scopes):
    names = _op_names(_step_hlo(arch))
    for scope in model_scopes:
        mine = [n for n in names if _in_scope(n, scope)]
        assert any("jvp(" in n and "transpose(" not in n for n in mine), \
            f"{scope}: no forward op"
        assert any("transpose(" in n for n in mine), \
            f"{scope}: no backward op"
    for scope in ("embed", "grad_accum", "sign_momentum", "sign_update"):
        assert any(_in_scope(n, scope) for n in names), scope
    # full remat: the recompute is named below the scopes' own names
    assert any("rematted_computation" in n and _in_scope(n, "mixer")
               for n in names)
    # the classes the chip benchmark reads survive: no scope holds a
    # transform name, so forward and backward still read as the model
    assert not any(_in_scope(n, "jvp") for n in names)


_FOUR = textwrap.dedent("""
    import json, os, re, sys
    sys.path.insert(0, {src!r})
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs.base import VoteStrategy
    from repro.launch import train as LT
    from repro.launch.mesh import make_replica_mesh
    from repro.train import train_step as TS
    cfg, tcfg = LT.build("zamba2-1.2b", reduced=True, batch=4, seq=32,
                         microbatches=1)
    opt = dataclasses.replace(tcfg.optimizer,
                              vote_strategy=VoteStrategy.ALLGATHER_1BIT)
    tcfg = dataclasses.replace(tcfg, optimizer=opt)
    mesh = make_replica_mesh(jax.devices()[:4])
    art = TS.make_train_step(cfg, tcfg, mesh=mesh)
    params, opt_state = TS.abstract_state(cfg, tcfg, art, mesh)
    batch = {{"tokens": jax.ShapeDtypeStruct(
        (4, 32), jnp.int32, sharding=NamedSharding(mesh, P("data")))}}
    text = art.step_fn.lower(params, opt_state, batch,
                             np.int32(0)).compile().as_text()
    ops = []
    for line in text.splitlines():
        m = re.search(r"= \\S+ ([a-z][\\w-]*)\\(", line)
        n = re.search(r'op_name="([^"]*)"', line)
        if m and n:
            ops.append([m.group(1), n.group(1)])
    print(json.dumps([art.vote_strategy.value, ops]))
""")


def test_vote_stage_scopes_on_four_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    res = subprocess.run(
        [sys.executable, "-c", _FOUR.format(src=os.path.join(_REPO, "src"))],
        env=env, capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    strategy, ops = json.loads(res.stdout.strip().splitlines()[-1])
    assert strategy == "allgather_1bit"
    for stage in ("vote_pack", "vote_exchange", "vote_tally", "vote_unpack"):
        assert any(_in_scope(n, stage) for _, n in ops), stage
    gathers = [n for opcode, n in ops if opcode.startswith("all-gather")]
    assert gathers and all(_in_scope(n, "vote_exchange") for n in gathers)


# ---------------------------------------------------------------------------
# the compile watch
# ---------------------------------------------------------------------------


def test_compile_watch_counts_each_phase_once_per_compile():
    assert obs.install_compile_watch()
    x = jnp.arange(7.0)

    @jax.jit
    def f(x):
        return jnp.sin(x) * 2 + jax.nn.silu(x)   # silu: a jit traced inside

    before = obs.COUNTERS.snapshot("jit.")
    f(x).block_until_ready()
    d = obs.COUNTERS.delta_since(before, "jit.")
    assert d.get("jit.compiles") == 1, d
    for k in ("jit.trace_ns", "jit.lower_ns", "jit.compile_ns"):
        assert d.get(k, 0) > 0, (k, d)
    assert not any("saved" in k for k in obs.COUNTERS.snapshot())
    again = obs.COUNTERS.snapshot("jit.")
    f(x).block_until_ready()
    assert obs.COUNTERS.delta_since(again, "jit.") == {}


def test_compile_watch_counts_a_persistent_cache_hit(tmp_path):
    from jax.experimental.compilation_cache import compilation_cache as cc
    assert obs.install_compile_watch()
    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        cc.reset_cache()

        def g(x):
            return jnp.cos(x) * 3.0 - x

        x = jnp.arange(11.0)
        jax.jit(g)(x).block_until_ready()            # compiles, writes
        jax.clear_caches()
        before = obs.COUNTERS.snapshot("jit.")
        jax.jit(g)(x).block_until_ready()            # loads from the cache
        d = obs.COUNTERS.delta_since(before, "jit.")
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        cc.reset_cache()
    assert d.get("jit.cache_hits") == 1, d
    assert d.get("jit.compiles") == 1, d
    assert not any("saved" in k for k in obs.COUNTERS.snapshot())


def test_jit_counters_split_at_a_time():
    assert obs.install_compile_watch()
    x = jnp.arange(5.0)
    jax.jit(lambda x: x * 5 + 1)(x).block_until_ready()
    t = time.perf_counter()
    upto = obs.jit_counters_at(t)
    jax.jit(lambda x: x * 7 - 2)(x).block_until_ready()
    assert obs.jit_counters_at(t) == upto
    later = obs.jit_counters_at(time.perf_counter())
    assert later["jit.compiles"] == upto["jit.compiles"] + 1


def test_live_span_is_a_profiler_annotation(tmp_path):
    from jax.profiler import ProfileData
    rec = obs.TraceRecorder(io.StringIO())
    jax.profiler.start_trace(str(tmp_path))
    with rec.span("train.dispatch"):
        jnp.ones(4).block_until_ready()
    jax.profiler.stop_trace()
    paths = [os.path.join(d, f) for d, _, fs in os.walk(tmp_path)
             for f in fs if f.endswith(".xplane.pb")]
    data = ProfileData.from_file(paths[0])
    names = {ev.name for plane in data.planes for line in plane.lines
             for ev in line.events}
    assert "train.dispatch" in names


def test_vote_writes_no_stage_spans():
    # the stages are device scopes now: a recorder sees no stage.* span
    from repro import compat
    from repro.core.vote_engine import STRATEGIES
    from repro.configs.base import VoteStrategy
    from jax.sharding import PartitionSpec as P
    buf = io.StringIO()
    rec = obs.TraceRecorder(buf)
    mesh = compat.make_mesh((1,), ("data",))
    impl = STRATEGIES[VoteStrategy.ALLGATHER_1BIT]
    f = compat.shard_map(lambda s: impl.vote(s, ("data",)), mesh=mesh,
                         in_specs=P(), out_specs=P(), axis_names={"data"},
                         check_vma=False)
    signs = jnp.asarray(np.sign(np.arange(-20, 20)).astype(np.int8))
    with obs.recording(rec):
        out = jax.jit(f)(signs)
    rec.close()
    np.testing.assert_array_equal(np.asarray(out),
                                  np.where(np.asarray(signs) < 0, -1, 1))
    rows = [json.loads(line) for line in buf.getvalue().splitlines()]
    assert not any(r.get("name", "").startswith("stage.") for r in rows)
