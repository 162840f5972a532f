"""Device scopes: names the compiled step carries in its HLO ``op_name``
metadata, so a profiler trace can total device time per layer
(DESIGN.md §13).

A ``jax.named_scope`` changes only metadata: it adds no op and no call
boundary, so the compiled program is the same with or without it. Each
scope is opened once, in the function that does the work:

* model: ``embed`` and ``lm_head`` (``models/model.py``), ``mixer`` and
  ``ssd`` (``models/mamba2.py``), ``shared_block`` (``models/hybrid.py``);
* train step: ``grad_accum`` (``train/train_step.py``);
* sign optimizer: ``sign_momentum``, ``sign_update`` (``core/signum.py``);
* vote: ``vote_pack``, ``vote_exchange``, ``vote_tally``, ``vote_unpack``,
  on the stage methods of every wire (:func:`scope_stages`).

Under the gradient a scope appears as ``jvp(name)`` in the forward and
``transpose(jvp(name))`` in the backward; under ``jax.checkpoint`` the
recompute sits below ``rematted_computation``.
"""
from __future__ import annotations

import functools
from typing import Callable

#: the vote's four pipeline stages (``core/vote_engine.py``)
VOTE_STAGES = ("pack", "exchange", "tally", "unpack")


def _in_scope(name: str, fn: Callable) -> Callable:
    import jax

    @functools.wraps(fn)
    def scoped(*args, **kwargs):
        with jax.named_scope(name):
            return fn(*args, **kwargs)
    return scoped


def scope_stages(cls):
    """Class decorator: run each stage method that `cls` defines itself
    under its ``vote_<stage>`` scope, so every caller of a stage (a
    strategy's ``vote``, the vote API's wire paths, the plan's bucket
    walk) is named."""
    for stage in VOTE_STAGES:
        fn = cls.__dict__.get(stage)
        if fn is not None:
            setattr(cls, stage, _in_scope(f"vote_{stage}", fn))
    return cls
