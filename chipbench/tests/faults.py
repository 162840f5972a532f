"""Faults planted in the program under test, for the tests that see
``correct`` come out false. Each is a context manager that patches the
program (never the benchmark) and undoes the patch on exit."""
from __future__ import annotations

import contextlib


@contextlib.contextmanager
def _patched(obj, name, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


def state_unchanged():
    """The optimizer hands back the parameters and its state unchanged."""
    from repro.core import signum
    from repro.train import train_step as TS
    real = signum.build_optimizer

    def build(*a, **k):
        opt = real(*a, **k)
        return signum.Optimizer(
            opt.init, lambda grads, state, params, step: (params, state, {}))
    return _patched(TS, "build_optimizer", build)


def half_batch():
    """The loss (and so the gradient) is taken over half of the tokens
    only, the mean over the rest: the first half of the rows of each call
    of the loss, or, where a call holds one row (one row per microbatch),
    the first half of its positions."""
    from repro.models import model as M
    real = M.loss_fn

    def loss_fn(cfg, params, batch, **k):
        t = batch["tokens"]
        t = t[:t.shape[0] // 2] if t.shape[0] > 1 else t[:, :t.shape[1] // 2]
        return real(cfg, params, {**batch, "tokens": t}, **k)
    return _patched(M, "loss_fn", loss_fn)


def exchange_left_out():
    """The 1-bit wire's all-gather is skipped: each replica tallies its
    own packed signs in place of everyone's."""
    import jax.numpy as jnp

    from repro.configs.base import VoteStrategy
    from repro.core import vote_engine as ve
    impl = ve.STRATEGIES[VoteStrategy.ALLGATHER_1BIT]

    def exchange(wire, axes):
        return jnp.repeat(wire[None], ve.num_voters(axes), axis=0)
    return _patched(impl, "exchange", exchange)


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
          "exchange_left_out": exchange_left_out}
