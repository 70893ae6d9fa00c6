"""AdamW + cosine schedule (hand-rolled, pytree-native).

Moments are kept in f32 and inherit the parameter shardings (ZeRO-free:
with TP+EP most state is already sharded; a 'zero_dp' flag additionally
shards moments over the data axis for dense-replicated params — the
distributed-optimizer trick for 1000+ node scale)."""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.obs import scopes

F32 = jnp.float32


@dataclass(frozen=True)
class OptConfig:
    lr: float = 3e-4
    betas: tuple = (0.9, 0.95)
    eps: float = 1e-8
    weight_decay: float = 0.1
    warmup_steps: int = 100
    total_steps: int = 10_000
    grad_clip: float = 1.0


class OptState(NamedTuple):
    step: jax.Array
    mu: Any
    nu: Any


def init_opt(params) -> OptState:
    zeros = lambda p: jnp.zeros(p.shape, F32)
    return OptState(jnp.zeros((), jnp.int32),
                    jax.tree.map(zeros, params),
                    jax.tree.map(zeros, params))


def lr_at(cfg: OptConfig, step):
    warm = cfg.lr * (step + 1) / max(1, cfg.warmup_steps)
    t = jnp.clip((step - cfg.warmup_steps)
                 / max(1, cfg.total_steps - cfg.warmup_steps), 0.0, 1.0)
    cos = 0.5 * cfg.lr * (1.0 + jnp.cos(jnp.pi * t))
    return jnp.where(step < cfg.warmup_steps, warm, cos)


def global_norm(tree) -> jax.Array:
    sq = sum(jnp.sum(jnp.square(g.astype(F32)))
             for g in jax.tree.leaves(tree))
    return jnp.sqrt(sq)


def adamw_update(cfg: OptConfig, params, grads, state: OptState):
    """Returns (new_params, new_state, metrics)."""
    with jax.named_scope(scopes.ADAMW):
        step = state.step
        gnorm = global_norm(grads)
        scale = jnp.minimum(1.0, cfg.grad_clip / (gnorm + 1e-9))
        b1, b2 = cfg.betas
        lr = lr_at(cfg, step)
        bc1 = 1.0 - b1 ** (step.astype(F32) + 1.0)
        bc2 = 1.0 - b2 ** (step.astype(F32) + 1.0)

        def upd(p, g, m, v):
            g = g.astype(F32) * scale
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * jnp.square(g)
            mh = m / bc1
            vh = v / bc2
            step_ = (mh / (jnp.sqrt(vh) + cfg.eps)
                     + cfg.weight_decay * p.astype(F32))
            return (p.astype(F32) - lr * step_).astype(p.dtype), m, v

        out = jax.tree.map(upd, params, grads, state.mu, state.nu)
        new_params = jax.tree.map(lambda t: t[0], out,
                                  is_leaf=lambda x: isinstance(x, tuple))
        new_mu = jax.tree.map(lambda t: t[1], out,
                              is_leaf=lambda x: isinstance(x, tuple))
        new_nu = jax.tree.map(lambda t: t[2], out,
                              is_leaf=lambda x: isinstance(x, tuple))
        return new_params, OptState(step + 1, new_mu, new_nu), \
            {"grad_norm": gnorm, "lr": lr}
