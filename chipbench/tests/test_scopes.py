"""Scope and set-up readers: scope matching by hand on HLO text, each new
reader on a hand-made reduction and on the recorded chip trace, and the
set-up split of the program's compile counters."""
import sys
import time
import types
from pathlib import Path

import pytest

from chipbench import harness
from chipbench.yardstick import scopes as S
from chipbench.yardstick import trace as T

DATA = Path(__file__).resolve().parent / "data" / "trace"
METRICS = ("mixer_ms", "ssd_ms", "shared_block_ms", "embed_head_ms",
           "grad_accum_ms", "recompute_ms", "update_ms", "vote_pack_ms",
           "vote_tally_ms", "vote_unpack_ms")


def _op(name, op_name):
    return (f'  %{name} = f32[8]{{0}} multiply(f32[8]{{0}} %a, f32[8]{{0}} '
            f'%b), metadata={{op_name="{op_name}"}}\n')


def test_scopes_match_whole_segments_of_hlo_op_names():
    text = (_op("m.1", "jit(f)/while/body/jvp(mixer)/ssd/mul")
            + _op("m.2", "jit(f)/transpose(jvp(mixer))/mul")
            + _op("m.3", "jit(f)/while/body/closed_call/transpose(jvp())/"
                  "checkpoint/rematted_computation/mixer/ssd/mul")
            + _op("m.4", "jit(f)/transpose(jvp(jvp(shared_block)))/mul")
            + _op("u.1", "jit(f)/dynamic_update_slice")
            + _op("u.2", "jit(f)/sign_update/sub"))
    hlo = T.parse_hlo(text)
    names = {k: v[1] for k, v in hlo.items()}
    assert S.in_scope(names["m.1"], {"mixer"})
    assert S.in_scope(names["m.1"], {"ssd"})
    assert S.in_scope(names["m.2"], {"mixer"})
    assert not S.in_scope(names["m.2"], {"ssd"})
    assert S.in_scope(names["m.3"], {"ssd"})
    assert S.in_scope(names["m.3"], {S.REMAT})
    assert S.in_scope(names["m.4"], {"shared_block"})
    assert not S.in_scope(names["u.1"], {"sign_update"})
    assert S.in_scope(names["u.2"], {"sign_update"})
    assert not S.in_scope("", {"mixer"})
    # the model class still reads these as forward and backward
    assert {T.classify("multiply", names[k]) for k in
            ("m.1", "m.2", "m.3", "m.4")} == {"model"}


def test_unnamed_fusion_takes_a_scope_from_what_it_calls():
    # the update of a large leaf: the fusion has no op_name; the
    # computation it calls is all optimizer, and its first op name (the
    # one parse_hlo gives the fusion) carries the scope
    text = (
        '%fused_computation.7 (param_0: f32[1,38,8], param_1: bf16[38,8]) '
        '-> bf16[38,8] {\n'
        '  %param_0 = f32[1,38,8]{2,1,0} parameter(0)\n'
        + _op("add.1", "jit(local_step)/sign_momentum/add")
        + _op("sign.1", "jit(local_step)/sign")
        + _op("sub.2", "jit(local_step)/sign_update/sub")
        + _op("sub.3", "jit(local_step)/sign_update/convert_element_type")
        + '}\n\n'
        'ENTRY %main.9 (opt_state__m: f32[1,38,8]) -> bf16[38,8] {\n'
        '  ROOT %fusion.3200 = bf16[38,8]{1,0} fusion(f32[1,38,8]{2,1,0} '
        '%opt_state__m, bf16[38,8]{1,0} %p), kind=kLoop, '
        'calls=%fused_computation.7\n'
        '}\n')
    opcode, op_name = T.parse_hlo(text)["fusion.3200"]
    assert T.classify(opcode, op_name) == "optimizer"
    assert S.in_scope(op_name, {"sign_momentum", "sign_update"})
    assert not S.in_scope(op_name, {"vote_pack"})


def _ctx(events, steps=2, chips=1):
    devs = {}
    for c in range(chips):
        devs[f"/device:TPU:{c}"] = [
            T.OpEvent(f"op{i}", s, e, e - s, "model", n)
            for i, (s, e, n) in enumerate(events)]
    red = T.Reduction((0, 1000), devs, [])
    return types.SimpleNamespace(trace=red, window_steps=steps, setup_s=1.0)


def test_readers_by_hand():
    ctx = _ctx([(0, 100, "jit(f)/jvp(mixer)/ssd/mul"),
                (100, 300, "jit(f)/transpose(jvp(mixer))/mul"),
                (300, 340, "jit(f)/checkpoint/rematted_computation/mixer/"
                 "ssd/mul"),
                (340, 400, "jit(f)/jvp(lm_head)/dot_general"),
                (400, 420, "jit(f)/jvp(embed)/gather"),
                (420, 460, "jit(f)/sign_update/sub"),
                (460, 470, "jit(f)/sign_momentum/add"),
                (470, 500, "jit(f)/dynamic_update_slice"),
                (990, 1010, "jit(f)/sign_update/sub")], chips=2)
    read = {m: harness.load_reader(harness.ROOT, m)(ctx) for m in METRICS}
    ns_to_ms_per_step = 1e-6 / 2
    assert read["mixer_ms"] == pytest.approx(340 * ns_to_ms_per_step)
    assert read["ssd_ms"] == pytest.approx(140 * ns_to_ms_per_step)
    assert read["recompute_ms"] == pytest.approx(40 * ns_to_ms_per_step)
    assert read["embed_head_ms"] == pytest.approx(80 * ns_to_ms_per_step)
    # the last op is half inside the window
    assert read["update_ms"] == pytest.approx(60 * ns_to_ms_per_step)
    for m in ("shared_block_ms", "grad_accum_ms", "vote_pack_ms",
              "vote_tally_ms", "vote_unpack_ms"):
        assert read[m] is None, m


@pytest.mark.parametrize("metric", METRICS)
def test_reader_absent_scope_reads_none(metric):
    # a one-chip trace has no vote scopes; a parent program has none
    ctx = _ctx([(0, 100, "jit(f)/jvp()/dot_general"),
                (100, 200, "jit(f)/sign")])
    assert harness.load_reader(harness.ROOT, metric)(ctx) is None
    assert harness.load_reader(harness.ROOT, metric)(
        types.SimpleNamespace(trace=None)) is None


def test_recorded_chip_trace_classes_unchanged():
    hlo = (DATA / "small.hlo.txt").read_text()
    r = T.reduce_trace(str(DATA / "small.xplane.pb"), hlo, n_devices=1)
    assert r.class_s("model") == pytest.approx(0.000917989, abs=1e-12)
    assert r.class_s("optimizer") == 0.0
    ctx = types.SimpleNamespace(trace=r, window_steps=3)
    assert harness.load_reader(harness.ROOT, "model_ms")(ctx) == \
        pytest.approx(0.917989 / 3)
    assert harness.load_reader(harness.ROOT, "optimizer_ms")(ctx) is None
    # recorded before the program had scopes: every scope reader is silent
    for m in METRICS:
        assert harness.load_reader(harness.ROOT, m)(ctx) is None, m


def test_setup_readers_split_the_compile_log(monkeypatch):
    import jax
    import jax.numpy as jnp

    from repro.obs import recorder as obs
    assert obs.install_compile_watch()
    x = jnp.arange(9.0)
    main = types.SimpleNamespace(T_START=time.perf_counter())
    monkeypatch.setitem(sys.modules, "__main__", main)
    jax.jit(lambda v: v * 3 - 1)(x).block_until_ready()
    setup_s = time.perf_counter() - main.T_START
    ctx = types.SimpleNamespace(trace=None, setup_s=setup_s)
    lower = harness.load_reader(harness.ROOT, "setup_lower_s")(ctx)
    compile_ = harness.load_reader(harness.ROOT, "setup_compile_s")(ctx)
    assert lower > 0 and compile_ > 0
    assert lower + compile_ <= setup_s
    # a compile after set-up (the traced run's HLO text) is left out
    jax.jit(lambda v: v * 5 + 2)(x).block_until_ready()
    assert harness.load_reader(harness.ROOT, "setup_compile_s")(ctx) == \
        compile_
    # a run not started by chipbench/run.py reads nothing
    monkeypatch.setitem(sys.modules, "__main__", types.SimpleNamespace())
    assert harness.load_reader(harness.ROOT, "setup_lower_s")(ctx) is None
