"""Layer-by-layer forward and backward, the signum update and the vote.

The step that the reference follows is the paper's Algorithm 1 with the
configuration's settings: per replica r, momentum m_r <- beta m_r +
(1 - beta) g_r, where g_r is the gradient of the mean next-token cross
entropy over replica r's rows; the vote is sign(m) on one replica (0 at
0), and on M > 1 replicas the majority of the binary signs (m >= 0 counts
as +1) with ties going to +1; every replica then sets
p <- round(p - lr * vote) to the parameter dtype.

Memory is bounded by working in blocks: one block of rows at a time,
and within it one layer at a time. The forward keeps each layer's input;
the backward takes the vector-Jacobian product of one layer at a time and
adds (1 - beta) / blocks times its gradient straight into the momentum,
so no gradient of the whole model is ever held. Parameters are held at
their own dtype and computed on in float32.

``prec`` selects the arithmetic: ``"f32"`` is float32 throughout, at
the highest matmul precision; ``"fp8"`` (the control) gives every
contraction, forward and backward, float8_e4m3fn operands under
per-tensor scales and holds the residual stream between blocks (and its
cotangent) in scaled float8_e4m3fn; ``"bf16"`` (a witness) rounds only
the contractions' operands to bfloat16, as the chip's default precision
does.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.yardstick import init as yinit
from chipbench.yardstick import probes

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _round8_scaled(x: jax.Array) -> jax.Array:
    """x rounded to float8_e4m3fn under a per-tensor scale (max |x| to 448)."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(F8).astype(jnp.float32) * s


def _fp8(rnd):
    """(operand, cotangent, both): round a contraction's operand going
    forward, a result's cotangent coming back, or a value both ways."""
    @jax.custom_vjp
    def operand(x):
        return rnd(x)
    operand.defvjp(lambda x: (rnd(x), None), lambda _, g: (g,))

    @jax.custom_vjp
    def cotangent(y):
        return y
    cotangent.defvjp(lambda y: (y, None), lambda _, g: (rnd(g),))

    @jax.custom_vjp
    def both(y):
        return rnd(y)
    both.defvjp(lambda y: (rnd(y), None), lambda _, g: (rnd(g),))
    return operand, cotangent, both


FP8 = {"fp8": _fp8(_round8_scaled)}


def stream(prec: str, h: jax.Array) -> jax.Array:
    """The residual stream between blocks at the reference's precision:
    under ``fp8`` rounded to fp8 going forward and its
    cotangent coming back; otherwise untouched."""
    return FP8[prec][2](h) if prec in FP8 else h


def mm(prec: str, eq: str, a: jax.Array, b: jax.Array) -> jax.Array:
    """A contraction at the reference's precision. Under ``fp8`` both
    operands are rounded to fp8 in the forward and the
    result's cotangent in the backward, so the backward's contractions
    take fp8 operands too; accumulation is float32 throughout."""
    if prec == "f32":
        return jnp.einsum(eq, a, b, precision=HIGHEST,
                          preferred_element_type=jnp.float32)
    if prec == "bf16":
        return jnp.einsum(eq, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    if prec not in FP8:
        raise ValueError(f"unknown precision {prec!r}")
    operand, cotangent, _ = FP8[prec]
    out = jnp.einsum(eq, operand(a), operand(b), precision=HIGHEST,
                     preferred_element_type=jnp.float32)
    return cotangent(out)


def vocab_rows(c: Dict) -> int:
    """Rows of the embedding: the vocabulary padded up to a multiple of
    ``pad_vocab_size_multiple`` where the configuration gives one."""
    m = int(c.get("pad_vocab_size_multiple", 1))
    return -(-int(c["vocab_size"]) // m) * m


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def cross_entropy(logits: jax.Array, tokens: jax.Array) -> jax.Array:
    """Mean over positions of -log p(next token)."""
    lg = logits[:, :-1]
    gold = jnp.take_along_axis(lg, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(lg, axis=-1) - gold)


@dataclasses.dataclass(frozen=True)
class Block:
    """One kind of layer: `fn(prec, c, lp, h) -> h` with the residual
    inside; `prefix` selects its leaves; `stacked` leaves carry a
    leading layer axis that the schedule's index picks."""
    fn: Callable
    prefix: str
    stacked: bool


def _layer_params(P: Dict, i, stacked: bool) -> Dict:
    if stacked:
        return {k: v[i].astype(jnp.float32) for k, v in P.items()}
    return {k: v.astype(jnp.float32) for k, v in P.items()}


class Reference:
    """The reference of one configuration `c` (its file's keys) at one
    precision, over `replicas` replicas placed one per device."""

    def __init__(self, family, c: Dict, prec: str, devices: Sequence):
        self.f, self.c, self.prec = family, c, prec
        self.devices = list(devices)
        self.shapes = family.param_shapes(c)
        self.dtype = jnp.dtype(c["dtype"])
        self.beta = float(c["momentum"])
        self.lr = float(c["learning_rate"])
        self.mom_dtype = jnp.dtype(c["momentum_dtype"])
        self.schedule = family.schedule(c)
        eps = float(c["rms_norm_eps"])
        self._fwd, self._bwd = {}, {}
        for kind, blk in family.BLOCKS.items():
            def f(lp, h, fn=blk.fn):
                return stream(prec, fn(prec, c, lp, h))

            def fwd(P, i, h, f=f, st=blk.stacked):
                return f(_layer_params(P, i, st), h)

            def bwd(P, M, i, h, dh, coef, f=f, st=blk.stacked):
                _, vjp = jax.vjp(f, _layer_params(P, i, st), h)
                dlp, dh_in = vjp(dh)
                if st:
                    M = {k: M[k].at[i].add((coef * dlp[k]).astype(M[k].dtype))
                         for k in M}
                else:
                    M = {k: M[k] + (coef * dlp[k]).astype(M[k].dtype)
                         for k in M}
                return dh_in, M

            self._fwd[kind] = jax.jit(fwd)
            self._bwd[kind] = jax.jit(bwd, donate_argnums=(1,))

        def head(P, M, h, tokens, coef):
            def f(top, h_):
                x = rms_norm(h_, top["final_norm.scale"], eps)
                logits = mm(prec, "bsd,vd->bsv", x, top["embed.table"])
                return cross_entropy(logits, tokens)
            top = {k: P[k].astype(jnp.float32) for k in P}
            loss, vjp = jax.vjp(f, top, h)
            dtop, dh = vjp(jnp.float32(1.0))
            M = {k: M[k] + (coef * dtop[k]).astype(M[k].dtype) for k in M}
            return loss, dh, M

        def embed_bwd(M, tokens, dh, coef):
            return M.at[tokens].add((coef * dh).astype(M.dtype))

        self._embed = jax.jit(
            lambda T, tokens: stream(prec, T[tokens].astype(jnp.float32)))
        self._head = jax.jit(head, donate_argnums=(1,))
        self._embed_bwd = jax.jit(embed_bwd, donate_argnums=(0,))
        self._scale = jax.jit(
            lambda M, b: jax.tree.map(lambda m: m * b.astype(m.dtype), M),
            donate_argnums=(0,))
        self._bits = jax.jit(lambda m: (m >= 0).astype(jnp.int8))
        self._majority = jax.jit(
            lambda bits: jnp.where(
                2 * sum(b.astype(jnp.int32) for b in bits) >= len(bits),
                1, -1).astype(jnp.int8))
        self._sign = jax.jit(lambda M: {k: jnp.sign(v).astype(jnp.int8)
                                        for k, v in M.items()})
        self._update = jax.jit(self._update_fn, donate_argnums=(0,))
        self.norms_fn = probes.leaf_norms()
        self.delta_fn = probes.delta_norms(self.shapes, c)

    # ---- state ----
    def _leaf_group(self, T: Dict, prefix: str) -> Dict:
        return {k: v for k, v in T.items() if k.startswith(prefix)}

    def _update_fn(self, P, V):
        lr = jnp.float32(self.lr)
        return {k: (P[k].astype(jnp.float32)
                    - lr * V[k].astype(jnp.float32)).astype(P[k].dtype)
                for k in P}

    def init_state(self, seed: int):
        Ps, Ms = [], []
        for d in self.devices:
            P = yinit.init_params(self.shapes, seed, self.c, d)
            Ps.append(P)
            Ms.append({k: jnp.zeros(v.shape, self.mom_dtype, device=d)
                       for k, v in P.items()})
        return Ps, Ms

    # ---- one replica's gradient, folded into its momentum ----
    def _accumulate(self, P, M, rows: np.ndarray, block_rows: int, device):
        n_blocks = rows.shape[0] // block_rows
        coef = np.float32((1.0 - self.beta) / n_blocks)
        M = self._scale(M, np.float32(self.beta))
        losses = []
        groups = {kind: (self._leaf_group(P, blk.prefix), blk.prefix)
                  for kind, blk in self.f.BLOCKS.items()}
        top_names = ("final_norm.scale", "embed.table")
        for b in range(n_blocks):
            tok = jax.device_put(rows[b * block_rows:(b + 1) * block_rows],
                                 device)
            i32 = np.int32
            h = self._embed(P["embed.table"], tok)
            ins = []
            for kind, i in self.schedule:
                ins.append(h)
                h = self._fwd[kind](groups[kind][0], i32(i or 0), h)
            Mtop = {k: M.pop(k) for k in top_names}
            loss, dh, Mtop = self._head({k: P[k] for k in top_names}, Mtop,
                                        h, tok, coef)
            M.update(Mtop)
            losses.append(loss)
            for (kind, i), h_in in zip(reversed(self.schedule),
                                       reversed(ins)):
                prefix = groups[kind][1]
                Mk = {k: M.pop(k) for k in list(M) if k.startswith(prefix)}
                dh, Mk = self._bwd[kind](groups[kind][0], Mk, i32(i or 0),
                                         h_in, dh, coef)
                M.update(Mk)
            del ins
            M["embed.table"] = self._embed_bwd(M["embed.table"], tok, dh, coef)
        return M, sum(losses) / n_blocks

    # ---- the whole step over all replicas ----
    def step(self, Ps, Ms, replica_rows: List[np.ndarray], block_rows: int):
        R = len(self.devices)
        losses = []
        for r in range(R):
            Ms[r], loss = self._accumulate(Ps[r], Ms[r], replica_rows[r],
                                           block_rows, self.devices[r])
            losses.append(loss)
        if R == 1:
            V = [self._sign(Ms[0])]
        else:
            V = [dict() for _ in range(R)]
            for k in Ms[0]:
                bits = [jax.device_put(self._bits(Ms[r][k]), self.devices[0])
                        for r in range(R)]
                vote = self._majority(bits)
                del bits
                for r in range(R):
                    V[r][k] = jax.device_put(vote, self.devices[r])
        for r in range(R):
            Ps[r] = self._update(Ps[r], V[r])
        del V
        return Ps, Ms, float(np.mean([float(x) for x in losses]))

    def norms(self, T: Dict) -> Dict[str, float]:
        return {k: float(v) for k, v in self.norms_fn(T).items()}


def run(family, c: Dict, prec: str, devices: Sequence, seed: int,
        batches: List[np.ndarray], block_rows: int) -> Dict:
    """Follow len(batches) steps from the seed's weights.

    `batches`: the global (rows, seq) tokens of each step; replica r takes
    the r-th equal slice of rows. Returns the readings the comparison
    takes: each step's loss, the first gradient's norm per replica and
    leaf, and the norm of the change of each leaf over all the steps.
    """
    ref = Reference(family, c, prec, devices)
    R = len(devices)
    Ps, Ms = ref.init_state(seed)
    out = {"loss": []}
    for step, rows in enumerate(batches):
        per = rows.shape[0] // R
        Ps, Ms, loss = ref.step(Ps, Ms, [rows[r * per:(r + 1) * per]
                                         for r in range(R)], block_rows)
        out["loss"].append(loss)
        if step == 0:
            g = [ref.norms(Ms[r]) for r in range(R)]
            out["grad_norm"] = {k: [v[k] / (1.0 - ref.beta) for v in g]
                                for k in g[0]}
    del Ms
    out["delta_norm"] = {k: float(v) for k, v in ref.delta_fn(
        Ps[0], jax.device_put(yinit.seed_key(seed), devices[0])).items()}
    return out
