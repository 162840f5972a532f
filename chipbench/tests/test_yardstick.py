"""The benchmark's arithmetic against counts made by hand."""
import numpy as np
import pytest

from chipbench.yardstick import flops, opt_bytes, peaks
from chipbench.yardstick.tokens import MarkovTokens


def test_peaks_table():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["bf16_flops"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")


def test_mamba2_mixer_flops_by_hand():
    # d=4, N=2, P=2, expand 2 -> d_inner 8, 4 heads; seq 4 in chunks of 2
    # projections 2*4*4*(8 + 12 + 4) = 768; out 2*4*8*4 = 256
    # per chunk (2 chunks): CB^T 2*2*2*2 = 16, mix 2*4*2*2*2 = 64,
    # state 2*2*4*2*2 = 64, carried 64
    assert flops.mamba2_mixer(4, 2, 2, 2, 2, 4) == 768 + 256 + 2 * (
        16 + 64 + 64 + 64)


def test_attention_block_flops_by_hand():
    # d=4, 2 heads, 1 kv head, head_dim 2, d_ff 8, seq 3
    # qkvo 2*3*4*2*(4+2) = 288; causal pairs 6: 2 * 2*6*2*2 = 96;
    # mlp 2*3*4*8*3 = 576
    assert flops.attention_mlp_block(4, 2, 1, 2, 8, 3) == 288 + 96 + 576


def test_head_and_step():
    assert flops.lm_head(4, 10, 3) == 240
    assert flops.train_step(100.0, 2) == 600.0


def test_family_flops_sum_their_blocks():
    from chipbench.reference import mamba2, zamba2
    z = {"hidden_size": 4, "num_hidden_layers": 5, "mamba_d_state": 2,
         "mamba_headdim": 2, "mamba_expand": 2, "chunk_size": 2,
         "num_attention_heads": 2, "num_key_value_heads": 1,
         "attention_head_dim": 2, "ffn_hidden_size": 8,
         "shared_block_every": 2, "vocab_size": 10}
    mixer = flops.mamba2_mixer(4, 2, 2, 2, 2, 4)
    block = flops.attention_mlp_block(4, 2, 1, 2, 8, 4)
    want = 3 * 2 * (5 * mixer + 2 * block + 2 * 4 * 4 * 10)
    assert zamba2.train_flops_per_step(z, 2, 4) == want
    m = {"d_model": 4, "n_layer": 3, "d_state": 2, "headdim": 2, "expand": 2,
         "chunk_size": 2, "vocab_size": 10}
    assert mamba2.train_flops_per_step(m, 1, 4) == 3 * (3 * mixer + 320)


def test_optimizer_bytes_by_hand():
    leaves = [(10, 2), (3, 4)]
    # one replica: 10*(2 + 8 + 4) + 3*(4 + 8 + 8) = 200
    assert opt_bytes.optimizer_bytes(leaves, 4, 1) == 200
    # four: + 13 * (1 + 3 + 4) / 8 = 13
    assert opt_bytes.optimizer_bytes(leaves, 4, 4) == 213


def test_tokens_are_a_function_of_seed_and_step():
    big = 2 ** 31 + 12345
    a = MarkovTokens(1000, 4, 256, big).batch(3)
    b = MarkovTokens(1000, 4, 256, big).batch(3)
    assert a.shape == (4, 256) and a.dtype == np.int32
    assert np.array_equal(a, b)
    assert a.min() >= 0 and a.max() < 1000
    assert not np.array_equal(a, MarkovTokens(1000, 4, 256, big).batch(4))
    assert not np.array_equal(a, MarkovTokens(1000, 4, 256, big + 1).batch(3))
    assert len({r.tobytes() for r in a}) == 4


def test_tokens_follow_the_class_chain():
    # one class: tokens uniform over the vocab; with a chain, a token's
    # class is drawn from its predecessor's row of the transition matrix
    g = MarkovTokens(50, 2, 4000, 7, classes=1)
    t = g.batch(0)
    assert np.bincount(t.ravel(), minlength=50).min() > 0
    g = MarkovTokens(300, 8, 2000, 9, classes=4, alpha=0.3)
    rng = np.random.default_rng([9, 0])
    trans = rng.dirichlet(np.full(4, 0.3), size=4)
    class_of = rng.integers(0, 4, size=300)
    c = class_of[g.batch(1)]
    counts = np.zeros((4, 4))
    np.add.at(counts, (c[:, :-1].ravel(), c[:, 1:].ravel()), 1)
    emp = counts / counts.sum(1, keepdims=True)
    assert np.abs(emp - trans).max() < 0.05


_NORMAL = {"linear": "normal", "conv": "normal", "std": 0.02,
           "embed_std": 0.02, "A": "arange", "dt_min": 0.001,
           "dt_max": 0.1, "dt_floor": 0.0001}
_KAIMING = {"linear": "kaiming_uniform", "conv": "kaiming_uniform",
            "conv_fan_in": 4, "embed_std": 0.02, "out_proj_rescale": 64,
            "A": [1.0, 16.0], "dt_min": 0.001, "dt_max": 0.1,
            "dt_floor": 0.0001}


@pytest.mark.parametrize("rule", ["normal", "kaiming_uniform"])
def test_init_rules(rule):
    import jax
    import jax.numpy as jnp

    from chipbench.yardstick import init as yinit
    c = {"dtype": "bfloat16",
         "init": _NORMAL if rule == "normal" else _KAIMING}
    shapes = {"layers.norm1_scale": (2, 8), "final_norm.scale": (8,),
              "layers.mamba_conv_w": (2, 4, 4096),
              "layers.mamba_conv_b": (2, 4096),
              "layers.mamba_dt_bias": (2, 4096),
              "layers.mamba_A_log": (2, 4096), "layers.mamba_D": (2, 4),
              "layers.mamba_out_proj": (2, 1024, 64),
              "embed.table": (512, 64), "layers.w": (2, 4096, 64)}
    P = yinit.init_params(shapes, 2 ** 33 + 5, c, jax.devices()[0])
    f = {k: np.asarray(v, np.float32) for k, v in P.items()}
    assert (f["layers.norm1_scale"] == 1).all()
    assert (f["final_norm.scale"] == 1).all()
    assert P["layers.mamba_D"].dtype == jnp.float32
    assert (f["layers.mamba_D"] == 1).all()
    assert P["layers.mamba_A_log"].dtype == jnp.float32
    assert P["layers.w"].dtype == jnp.bfloat16
    # dt = softplus(dt_bias) lies in [0.001, 0.1], log-uniform (dt_bias
    # is held in bf16, so within its rounding)
    dt = np.log1p(np.exp(f["layers.mamba_dt_bias"]))
    assert dt.min() > 0.001 * (1 - 2 ** -7) and dt.max() < 0.1 * (1 + 2 ** -7)
    assert abs(np.median(np.log(dt)) - np.log(0.01)) < 0.1
    assert abs(f["embed.table"].std() - 0.02) < 0.001
    if rule == "normal":
        assert np.allclose(f["layers.mamba_A_log"][1],
                           np.log(np.arange(1, 4097)))
        assert (f["layers.mamba_conv_b"] == 0).all()
        for k in ("layers.w", "layers.mamba_conv_w",
                  "layers.mamba_out_proj"):
            assert abs(f[k].std() - 0.02) < 0.001, k
    else:
        a = np.exp(f["layers.mamba_A_log"])
        assert a.min() >= 1 and a.max() <= 16 and abs(a.mean() - 8.5) < 0.2
        # U(+-1/sqrt(fan_in)): std 1/sqrt(3 fan_in)
        assert abs(f["layers.w"].std() * np.sqrt(3 * 4096) - 1) < 0.02
        assert np.abs(f["layers.w"]).max() <= 1 / 64
        assert abs(f["layers.mamba_conv_w"].std() * np.sqrt(12) - 1) < 0.02
        assert abs(f["layers.mamba_conv_b"].std() * np.sqrt(12) - 1) < 0.02
        # out_proj over sqrt(64 layers)
        assert abs(f["layers.mamba_out_proj"].std() * np.sqrt(3 * 1024)
                   * 8 - 1) < 0.02
