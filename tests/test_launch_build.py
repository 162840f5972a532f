"""``repro.launch.train.build``: the arch preset, overridden only by the
flags a user passes."""
import pytest

from repro.configs.base import MomentumMode
from repro.configs.presets import MODE_B_ARCHS, default_optimizer
from repro.launch.train import OPT_KINDS, build

ARCH = "zamba2-1.2b"


def test_build_defaults_to_the_arch_preset():
    _, tcfg = build(ARCH, reduced=True, batch=8, seq=64)
    assert tcfg.optimizer == default_optimizer(ARCH)
    assert (tcfg.microbatches, tcfg.remat) == (4, "full")


@pytest.mark.parametrize("kind", OPT_KINDS)
def test_build_opt_kind_overrides_the_preset(kind):
    _, tcfg = build(ARCH, reduced=True, batch=8, seq=64, opt_kind=kind)
    assert tcfg.optimizer.kind == kind


def test_build_keeps_preset_fields_a_flag_does_not_name():
    _, tcfg = build(ARCH, reduced=True, batch=8, seq=64,
                    opt_kind="signsgd_vote", lr=3e-3, microbatches=2)
    preset = default_optimizer(ARCH)
    assert (tcfg.optimizer.learning_rate, tcfg.microbatches) == (3e-3, 2)
    assert tcfg.optimizer.momentum == preset.momentum
    assert tcfg.optimizer.momentum_mode == MomentumMode.PER_WORKER
    assert tcfg.optimizer.momentum_dtype == preset.momentum_dtype


def test_build_rejects_an_unknown_kind():
    with pytest.raises(ValueError, match="unknown optimizer kind"):
        build(ARCH, reduced=True, batch=8, seq=64, opt_kind="signum")


def test_build_rejects_a_kind_that_conflicts_with_mode_b():
    with pytest.raises(ValueError, match="Mode B"):
        build(MODE_B_ARCHS[0], reduced=True, batch=8, seq=64,
              opt_kind="signum_vote")


def test_build_rejects_a_batch_the_microbatches_do_not_split():
    with pytest.raises(ValueError, match="microbatches"):
        build(ARCH, reduced=True, batch=6, seq=64)
