"""The trace reduction: interval arithmetic by hand, HLO parsing, and the
small trace recorded on one v5e by ``record_trace.py``."""
from pathlib import Path

import pytest

from chipbench.yardstick import trace as T

DATA = Path(__file__).resolve().parent / "data" / "trace"


def test_intervals_by_hand():
    assert T._union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]
    assert T._subtract([(0, 10)], [(2, 3), (5, 6)]) == [(0, 2), (3, 5),
                                                         (6, 10)]
    assert T._subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert T._clip([(0, 5), (8, 12)], 3, 10) == [(3, 5), (8, 10)]


def test_self_time_of_nested_events():
    # a while op [0, 10) holding two ops [1, 3) and [4, 8)
    assert T._self_times([(0, 10, "w"), (1, 3, "a"), (4, 8, "b")]) == \
        [4, 2, 4]


def test_classes():
    assert T.classify("all-gather-start", "jit(f)/x") == "collective"
    assert T.classify("fusion", "jit(f)/jvp(model)/dot_general") == "model"
    assert T.classify("fusion",
                      "jit(f)/transpose(jvp(model))/dot_general") == "model"
    assert T.classify("add", "jit(f)/while/body/add") == "model"
    assert T.classify("fusion", "jit(f)/sign") == "optimizer"
    assert T.classify("copy", "") == "other"


def test_parse_hlo():
    text = ('  %fusion.3 = f32[8,128]{1,0} fusion(f32[8,128]{1,0} %p), '
            'kind=kLoop, calls=%fc, metadata={op_name="jit(f)/jvp(g)/mul"}\n'
            '  ROOT %all-gather.1 = (u32[4,8]{1,0}) all-gather(u32[8]{0} '
            '%w), dimensions={0}\n')
    assert T.parse_hlo(text) == {"fusion.3": ("fusion", "jit(f)/jvp(g)/mul"),
                                 "all-gather.1": ("all-gather", "")}


def test_unnamed_fusion_takes_the_class_of_what_it_calls():
    # the update of a large leaf: reads its momentum, writes the param;
    # the fusion has no op_name, the computation it calls has
    text = (
        '%fused_computation.7 (param_0: f32[1,38,8], param_1: bf16[38,8]) '
        '-> bf16[38,8] {\n'
        '  %param_0 = f32[1,38,8]{2,1,0} parameter(0)\n'
        '  %sign.1 = f32[38,8]{1,0} sign(f32[38,8]{1,0} %b), '
        'metadata={op_name="jit(local_step)/sign"}\n'
        '  ROOT %sub.2 = bf16[38,8]{1,0} subtract(bf16[38,8]{1,0} %c, '
        'bf16[38,8]{1,0} %d), metadata={op_name="jit(local_step)/sub"}\n'
        '}\n\n'
        'ENTRY %main.9 (opt_state__m: f32[1,38,8]) -> bf16[38,8] {\n'
        '  ROOT %fusion.3200 = bf16[38,8]{1,0} fusion(f32[1,38,8]{2,1,0} '
        '%opt_state__m, bf16[38,8]{1,0} %p), kind=kLoop, '
        'calls=%fused_computation.7\n'
        '}\n')
    hlo = T.parse_hlo(text)
    opcode, op_name = hlo["fusion.3200"]
    assert opcode == "fusion" and op_name == "jit(local_step)/sign"
    assert T.classify(opcode, op_name) == "optimizer"


def _reduction(events, spans, window=(0, 100)):
    evs = [T.OpEvent(n, s, e, e - s, c, "") for n, s, e, c in events]
    return T.Reduction(window, {"/device:TPU:0": evs}, spans)


def test_busy_idle_and_exposed_by_hand():
    r = _reduction([("m", 10, 40, "model"), ("c", 30, 60, "collective"),
                    ("o", 70, 80, "optimizer")],
                   [(0, 12, "batch"), (60, 75, "sync")])
    assert r.busy_s() == pytest.approx(60e-9)       # [10,60) + [70,80)
    assert r.class_s("collective") == pytest.approx(30e-9)
    assert r.exposed_s() == pytest.approx(20e-9)    # [40,60)
    gaps = dict(r.idle_gaps())
    assert gaps["none"] == pytest.approx(20e-9)     # [80,100)
    assert gaps["batch"] == pytest.approx(10e-9)    # [0,10)
    assert gaps["sync"] == pytest.approx(10e-9)     # [60,70)


def test_recorded_chip_trace():
    # recorded on one v5e: the sign update is fused into the backward's
    # dot fusions there, so the step holds model ops and a few copies
    hlo = (DATA / "small.hlo.txt").read_text()
    r = T.reduce_trace(str(DATA / "small.xplane.pb"), hlo, n_devices=1)
    assert list(r.devices) == ["/device:TPU:0"]
    assert 0 < r.busy_s() <= r.window_s
    assert r.class_s("model") > 0
    assert r.class_s("collective") == 0
    assert r.class_s("model") <= r.busy_s()
    top = r.top_ops(5)
    assert top[0][0].startswith("model:") and "dot_general" in top[0][0]
    assert r.class_s("model") > 0.9 * r.busy_s()
    names = {n for n, _ in r.idle_gaps()}
    assert names <= {"dispatch", "sync", "none"} and "sync" in names
