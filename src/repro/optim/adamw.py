"""AdamW with decoupled weight decay, f32 moments, global-norm clipping,
and schedule support. Pure-pytree (no optax dependency)."""
from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.01
    global_clip: Optional[float] = 1.0
    schedule: Optional[Callable[[jax.Array], jax.Array]] = None


class AdamWState(NamedTuple):
    step: jax.Array
    mu: object
    nu: object


def init(params) -> AdamWState:
    zeros = lambda p: jax.tree_util.tree_map(
        lambda x: jnp.zeros(x.shape, jnp.float32), p)
    return AdamWState(jnp.zeros((), jnp.int32), zeros(params), zeros(params))


def global_norm(tree) -> jax.Array:
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree_util.tree_leaves(tree)))


#: ``jax.named_scope`` of the global clip and the apply, for the trace
#: reduction's phase split
SCOPE = "optim.adamw"


def update(cfg: AdamWConfig, state: AdamWState, params, grads):
    with jax.named_scope(SCOPE):
        return _update(cfg, state, params, grads)


def _update(cfg: AdamWConfig, state: AdamWState, params, grads):
    step = state.step + 1
    if cfg.global_clip is not None:
        gn = global_norm(grads)
        scale = jnp.minimum(1.0, cfg.global_clip / (gn + 1e-9))
        grads = jax.tree_util.tree_map(
            lambda g: (g.astype(jnp.float32) * scale), grads)
    else:
        grads = jax.tree_util.tree_map(
            lambda g: g.astype(jnp.float32), grads)

    lr = cfg.lr if cfg.schedule is None else cfg.lr * cfg.schedule(step)
    b1c = 1.0 - cfg.b1 ** step.astype(jnp.float32)
    b2c = 1.0 - cfg.b2 ** step.astype(jnp.float32)

    def upd(p, g, m, v):
        m = cfg.b1 * m + (1 - cfg.b1) * g
        v = cfg.b2 * v + (1 - cfg.b2) * jnp.square(g)
        mhat = m / b1c
        vhat = v / b2c
        delta = mhat / (jnp.sqrt(vhat) + cfg.eps) + \
            cfg.weight_decay * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - lr * delta).astype(p.dtype), m, v

    out = jax.tree_util.tree_map(upd, params, grads, state.mu, state.nu)
    new_params = jax.tree_util.tree_map(lambda t: t[0], out,
                                        is_leaf=lambda t: isinstance(t, tuple))
    new_mu = jax.tree_util.tree_map(lambda t: t[1], out,
                                    is_leaf=lambda t: isinstance(t, tuple))
    new_nu = jax.tree_util.tree_map(lambda t: t[2], out,
                                    is_leaf=lambda t: isinstance(t, tuple))
    return new_params, AdamWState(step, new_mu, new_nu)
