"""The reference's vote by wire and its model ends: the vote on hand-made
momenta, an untied head with scaled embedding and logits against the
gradient of a plain loss, and the readings of the tiny cells against
those recorded before wires and untied heads were added."""
import json
import os
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import common
from chipbench.tests import tiny

REPO = tiny.REPO
RECORDED = tiny.DATA / "reference_readings.json"


def _vote(wire, *momenta):
    ballot, tally = common.vote_rule(wire)
    return np.asarray(tally([ballot(jnp.asarray(m, jnp.float32))
                             for m in momenta]))


# each column is one coordinate voted on by four replicas
_TIE = ([1.0, -2.0], [3.0, -1.0], [-1.0, 5.0], [-4.0, 2.0])
_ZEROS = ([0.0], [0.0], [0.0], [0.0])
_THREE_ONE = ([1.0, -1.0], [2.0, -3.0], [0.5, -0.5], [-7.0, 9.0])
_ABSTAIN = ([2.0, 0.0], [0.0, -1.0], [0.0, 0.0], [0.0, 0.0])


@pytest.mark.parametrize("momenta,want", [
    (_TIE, [0, 0]), (_ZEROS, [0]), (_THREE_ONE, [1, -1]),
    (_ABSTAIN, [1, -1])])
def test_int8_wire_votes_the_sign_of_the_ternary_count(momenta, want):
    assert _vote("psum_int8", *momenta).tolist() == want


@pytest.mark.parametrize("momenta,want", [
    (_TIE, [1, 1]), (_ZEROS, [1]), (_THREE_ONE, [1, -1]),
    (_ABSTAIN, [1, 1])])
def test_1bit_wire_votes_the_binary_majority_ties_plus_one(momenta, want):
    assert _vote("allgather_1bit", *momenta).tolist() == want


@pytest.mark.parametrize("wire", [None, "psum_bf16", "allgather_2bit"])
def test_any_other_wire_raises(wire):
    with pytest.raises(ValueError, match=repr(wire)):
        common.vote_rule(wire)
    c = {**_untied_config(), "vote_strategy": wire}
    with pytest.raises(ValueError, match=repr(wire)):
        common.Reference(_untied_family(), c, "f32", [jax.devices()[0]] * 4)


# ---- an untied head with both head scalars ----

V, D, F, L = 48, 16, 32, 2


def _block(prec, c, lp, h):
    x = common.rms_norm(h, lp["layers.norm_scale"], c["rms_norm_eps"])
    u = jax.nn.relu(common.mm(prec, "bsd,df->bsf", x, lp["layers.w_in"]))
    return h + common.mm(prec, "bsf,fd->bsd", u, lp["layers.w_out"])


def _untied_family():
    return types.SimpleNamespace(
        param_shapes=lambda c: {
            "embed.table": (V, D), "unembed.table": (V, D),
            "final_norm.scale": (D,), "layers.norm_scale": (L, D),
            "layers.w_in": (L, D, F), "layers.w_out": (L, F, D)},
        schedule=lambda c: [("mlp", i) for i in range(L)],
        BLOCKS={"mlp": common.Block(_block, "layers.", stacked=True)})


def _untied_config():
    return {"dtype": "float32", "momentum_dtype": "float32", "momentum": 0.9,
            "learning_rate": 1e-3, "rms_norm_eps": 1e-5,
            "embedding_multiplier": 12.0, "logits_scaling": 16.0,
            "init": {"linear": "kaiming_uniform", "conv": "normal",
                     "embed_std": 0.5}}


def _plain_loss(P, tokens, c):
    """The model written out in float32: scaled embedding, the blocks,
    the final norm, the untied head, scaled logits, next-token loss."""
    h = P["embed.table"][tokens] * c["embedding_multiplier"]
    for i in range(L):
        x = h * jax.lax.rsqrt(jnp.mean(h * h, -1, keepdims=True)
                              + c["rms_norm_eps"]) * P["layers.norm_scale"][i]
        u = jax.nn.relu(jnp.dot(x, P["layers.w_in"][i],
                                precision=jax.lax.Precision.HIGHEST))
        h = h + jnp.dot(u, P["layers.w_out"][i],
                        precision=jax.lax.Precision.HIGHEST)
    x = h * jax.lax.rsqrt(jnp.mean(h * h, -1, keepdims=True)
                          + c["rms_norm_eps"]) * P["final_norm.scale"]
    logits = jnp.einsum("bsd,vd->bsv", x, P["unembed.table"],
                        precision=jax.lax.Precision.HIGHEST)
    logits = logits / c["logits_scaling"]
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    gold = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return -jnp.mean(gold)


def test_untied_scaled_head_momentum_is_the_plain_gradient():
    c = _untied_config()
    ref = common.Reference(_untied_family(), c, "f32", jax.devices()[:1])
    assert ref.top_names == ("final_norm.scale", "unembed.table")
    (P,), (M,) = ref.init_state(2 ** 31 + 3)
    tokens = np.random.default_rng(0).integers(0, V, (2, 24)).astype(np.int32)
    M, loss = ref._accumulate(P, M, tokens, 1, jax.devices()[0])
    want_loss, grad = jax.value_and_grad(_plain_loss)(P, jnp.asarray(tokens),
                                                      c)
    assert abs(float(loss) - float(want_loss)) < 1e-5
    for k, g in grad.items():
        want = (1.0 - c["momentum"]) * np.asarray(g)
        got = np.asarray(M[k])
        scale = np.abs(want).max()
        assert scale > 0, k
        assert np.abs(got - want).max() <= 1e-4 * scale, k
    # the embedding's gradient reaches only the rows of the tokens seen
    unseen = np.setdiff1d(np.arange(V), tokens)
    assert len(unseen) and not np.asarray(M["embed.table"])[unseen].any()


_RECORD = """
import importlib, json, sys, tempfile
sys.path[:0] = [{repo!r}, {src!r}]
from pathlib import Path
import jax
from chipbench import harness
from chipbench.reference import common
from chipbench.tests import tiny
root = tiny.make_root(Path(tempfile.mkdtemp()) / "bench")
out = {{}}
for cell in {cells!r}:
    cl = harness.load_cell(root, cell)
    c = harness.reference_config(cl)
    family = importlib.import_module(f"chipbench.reference.{{c['reference']}}")
    gen = harness.tokens(cl, c, {seed})
    batches = [gen.batch(s) for s in range(harness.CHECK_STEPS)]
    for prec in ("f32", "fp8"):
        out[f"{{cell}} {{prec}}"] = common.run(
            family, c, prec, jax.devices()[:cl.chips], {seed}, batches, 1)
print(json.dumps(out, sort_keys=True))
"""


def test_tied_cells_read_as_recorded_bit_for_bit():
    """Losses, first-gradient norms and parameter changes of the float32
    reference and of the control, on the three tiny cells (one of them on
    four replicas voting by the 1-bit rule), equal those that the
    reference gave before it voted by wire and took untied heads."""
    cells = ["zamba2-tiny.t4-s64", "mamba2-tiny.t4-s64",
             "zamba2-tiny.dp4-1bit-t4-s64"]
    code = _RECORD.format(repo=str(REPO), src=str(REPO / "src"), cells=cells,
                          seed=2 ** 31 + 99)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    got = json.loads(res.stdout.strip().splitlines()[-1])
    assert got == json.loads(RECORDED.read_text())
