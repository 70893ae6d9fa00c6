"""Plain float32 reference of a training step.

The architecture is ``reference.py``'s (the configuration's decoder layers,
``layer`` of its architecture module, between the embedding and a final
RMSNorm and the tied or untied unembedding), here over whole batches: the
mean next-token cross-entropy over every row and position, its gradient by
``jax.value_and_grad``, and AdamW as the configuration's
``train.optimizer`` states it: the gradient clipped to a global norm
(``clip / (norm + 1e-6)``, at most 1), moments with bias correction,
weight decay decoupled and scaled by the learning rate, a linear warm-up
to ``lr`` over ``warmup_steps`` (``lr * (k + 1) / warmup_steps`` at step
``k``) and then a cosine decay to 0 at ``total_steps``. It imports nothing
of the program: weights are redrawn from the seed (``model.build_params``)
and batches from the job (``train.batch``).

Every matrix product, attention's included, runs at
``precision="highest"``. With ``quant="high"`` every product, forward and
backward, is the three-pass bf16 product (the high part of each factor
times the other's high part, plus the two cross terms): the control,
float32 one step below ``highest``. Each layer is recomputed in the
backward pass (``jax.checkpoint``) and the cross-entropy is taken one row
at a time, so that the reference fits beside its float32 weights and
moments.
"""
from __future__ import annotations

from typing import Dict, Optional

import numpy as np

import model as bench_model
from reference import _rms

QUANTS = (None, "high")


def _three_pass(jnp, a, b):
    f32, bf16 = jnp.float32, jnp.bfloat16
    ah = a.astype(bf16).astype(f32)
    al = (a - ah).astype(bf16).astype(f32)
    bh = b.astype(bf16).astype(f32)
    bl = (b - bh).astype(bf16).astype(f32)

    def mm(x, y):
        return jnp.matmul(x, y, precision="highest")
    return mm(ah, bh) + (mm(ah, bl) + mm(al, bh))


def _make_mm3():
    import jax
    import jax.numpy as jnp

    @jax.custom_vjp
    def mm3(a, b):
        return _three_pass(jnp, a, b)

    def fwd(a, b):
        return _three_pass(jnp, a, b), (a, b)

    def bwd(res, g):
        a, b = res
        if b.ndim == 2:          # activations (..., K) times weights (K, N)
            a2 = a.reshape(-1, a.shape[-1])
            g2 = g.reshape(-1, g.shape[-1])
            return (_three_pass(jnp, g, b.T),
                    _three_pass(jnp, a2.T, g2))
        return (_three_pass(jnp, g, jnp.swapaxes(b, -1, -2)),
                _three_pass(jnp, jnp.swapaxes(a, -1, -2), g))

    mm3.defvjp(fwd, bwd)
    return mm3


def matmul(quant: Optional[str]):
    """(a, b) -> a @ b in float32 at ``highest``, or in three passes."""
    import jax.numpy as jnp
    if quant is None:
        return lambda a, b: jnp.matmul(a, b, precision="highest")
    if quant == "high":
        return _make_mm3()
    raise ValueError(quant)


def loss_fn(c: dict, quant: Optional[str]):
    """(params, tokens (B, S), targets (B, S)) -> mean cross-entropy."""
    import jax
    import jax.numpy as jnp
    mm = matmul(quant)
    arch = bench_model.arch(c)
    eps = float(c["rms_norm_eps"])

    def layer(x, w):
        return arch.layer(c, w, x, mm, mm), None

    def loss(params, tokens, targets):
        x = params["embed"][tokens]
        x, _ = jax.lax.scan(jax.checkpoint(layer), x, params["layers"])
        h = _rms(jnp, x, params["ln_final"]["scale"], eps)
        head = (params["embed"].T if c["tie_word_embeddings"]
                else params["lm_head"])

        def row(total, xs):
            hr, tr = xs
            lg = mm(hr, head)
            lse = jax.scipy.special.logsumexp(lg, axis=-1)
            tgt = jnp.take_along_axis(lg, tr[:, None], -1)[:, 0]
            return total + jnp.sum(lse - tgt), None

        total, _ = jax.lax.scan(jax.checkpoint(row), jnp.float32(0.0),
                                (h, targets))
        return total / tokens.size

    return loss


def lr_at(o: dict, k):
    """Learning rate of step ``k`` (0-based)."""
    import jax.numpy as jnp
    warm = o["lr"] * (k + 1) / o["warmup_steps"]
    t = jnp.clip((k - o["warmup_steps"])
                 / max(1, o["total_steps"] - o["warmup_steps"]), 0.0, 1.0)
    return jnp.where(k < o["warmup_steps"], warm,
                     0.5 * o["lr"] * (1.0 + jnp.cos(jnp.pi * t)))


def step_fn(c: dict, quant: Optional[str]):
    """Jitted (params, mu, nu, tokens, targets, k) -> (params, mu, nu,
    loss, per-leaf norms of the clipped gradient), the state donated."""
    import jax
    import jax.numpy as jnp
    import train
    o = c["train"]["optimizer"]
    b1, b2 = o["betas"]
    loss = loss_fn(c, quant)

    def step(params, mu, nu, tokens, targets, k):
        value, g = jax.value_and_grad(loss)(params, tokens, targets)
        norm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
        g = jax.tree.map(
            lambda x: x * jnp.minimum(1.0, o["grad_clip"] / (norm + 1e-6)),
            g)
        mu = jax.tree.map(lambda m, x: b1 * m + (1 - b1) * x, mu, g)
        nu = jax.tree.map(lambda v, x: b2 * v + (1 - b2) * x * x, nu, g)
        kf = k.astype(jnp.float32)
        c1, c2 = 1 - b1 ** (kf + 1), 1 - b2 ** (kf + 1)
        lr = lr_at(o, kf)
        params = jax.tree.map(
            lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2)
                                                  + o["eps"])
                                      + o["weight_decay"] * p),
            params, mu, nu)
        return params, mu, nu, value, train.leaf_norms(g)

    return jax.jit(step, donate_argnums=(0, 1, 2))


_STEPS: Dict[tuple, object] = {}


def readings(c: dict, job: dict, seed: int, quant: Optional[str] = None,
             steps: int = 3) -> dict:
    """The reference's readings of ``steps`` steps from the seed: each
    step's loss, the per-leaf norms of the first step's clipped gradient
    and of the weights' change after the last step."""
    import jax
    import jax.numpy as jnp
    import train
    tag = (id(c), quant)
    if tag not in _STEPS:
        _STEPS[tag] = step_fn(c, quant)
    step = _STEPS[tag]
    params = bench_model.make_params(c, seed, jnp.float32)
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    losses, grad = [], None
    for k in range(steps):
        toks, tgts = train.batch(job, c["vocab_size"], seed, k)
        params, mu, nu, loss, norms = step(
            params, mu, nu, jnp.asarray(toks), jnp.asarray(tgts),
            jnp.asarray(k, jnp.int32))
        losses.append(float(loss))
        if k == 0:
            grad = np.asarray(norms)
    del mu, nu
    change = np.asarray(train.change_norms(c)(params,
                                              bench_model.root_key(seed)))
    return {"loss": losses, "grad": grad, "change": change}
