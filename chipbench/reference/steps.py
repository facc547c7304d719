"""Reference training and scoring steps, in float32, one example at a
time.

A model reference supplies ``example_loss(params, ids, labels, mode)``
— one example's summed token cross-entropy, computed in float32 from
parameters that may still be stored in the configuration's dtype (every
use promotes them; the conversion is exact) — and its parameters as a
flat ``{path: array}`` dict in that dtype. Everything below is the
textbook definition, computed plainly:

  * per-example gradient ``g_j = ∇ℓ_j`` by autodiff of that one
    example's loss, in float32, and its squared norm;
  * DP-SGD: ``G = Σ_j min(1, C / (‖g_j‖ + 1e-6)) g_j + σ C ξ``,
    ``ξ ~ N(0, I)`` (Abadi et al. 2016); plain: ``G = Σ_j g_j``;
  * ``G`` is held in the configuration's dtype, as the gradient of
    parameters stored in it is: the sum is rounded to it, and the noise
    is drawn in float32, rounded to it and added there;
  * AdamW (Loshchilov & Hutter 2019) with global-norm clipping of
    ``G`` first, moments in float32, and the updated parameters stored
    in the configuration's dtype.

The noise ``ξ`` of one step is drawn from a key the caller gives, one
key a leaf, split from it in the order of ``leaf_order``.

Examples are taken one at a time (``lax.fori_loop``) so that only one
per-example gradient is live; on several devices (``over_devices``)
each takes its own rows so, and the sums are added.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class AdamW:
    lr: float
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.01
    global_clip: Optional[float] = 1.0


def _sq(tree) -> jax.Array:
    return sum(jnp.sum(jnp.square(x)) for x in jax.tree_util.tree_leaves(tree))


def example_grad(example_loss: Callable, params: dict, ids, labels, mode):
    """(ℓ_j, ∇ℓ_j) in float32."""
    def f(p32):
        return example_loss(p32, ids, labels, mode)

    return jax.value_and_grad(f)({k: v.astype(F32) for k, v in params.items()})


def score(example_loss: Callable, params: dict, ids, labels, *, mode: str):
    """Per-example losses (B,) and squared gradient norms (B,)."""
    def one(j, carry):
        losses, sq = carry
        loss, g = example_grad(example_loss, params, ids[j], labels[j], mode)
        return losses.at[j].set(loss), sq.at[j].set(_sq(g))

    b = ids.shape[0]
    return jax.lax.fori_loop(0, b, one, (jnp.zeros(b, F32), jnp.zeros(b, F32)))


def noise(key, shape, dtype, std: float):
    """One leaf's DP-SGD noise ``std · ξ``: ξ drawn in float32 and
    rounded to the gradient's ``dtype``."""
    return std * jax.random.normal(key, shape, F32).astype(dtype)


def noise_keys(key, leaf_order: Sequence[str]) -> dict:
    return dict(zip(leaf_order, jax.random.split(key, len(leaf_order))))


def clipped_sum(example_loss: Callable, params: dict, ids, labels,
                row_weights, *, mode: str, clip_norm: Optional[float],
                clip: bool = True):
    """(``Σ_j w_j c_j g_j`` in float32, losses (B,), squared norms (B,)),
    one example at a time; ``c_j`` is the clip coefficient, 1 where
    ``clip_norm`` is None or ``clip`` is False."""
    b = ids.shape[0]
    g0 = {k: jnp.zeros(v.shape, F32) for k, v in params.items()}

    def one(j, carry):
        acc, losses, sq = carry
        loss, g = example_grad(example_loss, params, ids[j], labels[j], mode)
        n = _sq(g)
        c = 1.0 if clip_norm is None or not clip else \
            jnp.minimum(1.0, clip_norm / (jnp.sqrt(n) + 1e-6))
        acc = {k: acc[k] + row_weights[j] * c * g[k] for k in acc}
        return acc, losses.at[j].set(loss), sq.at[j].set(n)

    return jax.lax.fori_loop(
        0, b, one, (g0, jnp.zeros(b, F32), jnp.zeros(b, F32)))


def over_devices(mesh, axis: str = "data") -> Callable:
    """``clipped_sum`` with the examples split over ``mesh``'s
    ``axis``: each device takes its own rows one at a time, and the
    devices' sums are added."""
    def run(example_loss, params, ids, labels, row_weights, **kw):
        def local(p, i, lab, w):
            g, losses, sq = clipped_sum(example_loss, p, i, lab, w, **kw)
            return jax.lax.psum(g, axis), losses, sq

        rows = P(axis)
        return jax.shard_map(local, mesh=mesh,
                             in_specs=(P(), rows, rows, rows),
                             out_specs=(P(), rows, rows), check_vma=False)(
            params, ids, labels, row_weights)

    return run


def train_step(example_loss: Callable, params: dict, mu: dict, nu: dict,
               t: int, ids, labels, key, row_weights, *, mode: str,
               opt: AdamW, clip_norm: Optional[float], noise_std: float,
               leaf_order: Sequence[str], clip: bool = True,
               summed: Callable = clipped_sum):
    """One step. Returns (params, mu, nu, losses (B,), sq_norms (B,),
    per-leaf norms of the gradient as AdamW's moments receive it, the
    global-norm clip's factor).

    ``row_weights`` (B,) weighs each example's clipped gradient in the
    sum; it is all ones, and ``clip`` is True, except where a test
    plants a fault. ``summed`` computes the sum (``clipped_sum``, or
    ``over_devices``' form of it)."""
    grads, losses, sq = summed(example_loss, params, ids, labels,
                               row_weights, mode=mode, clip_norm=clip_norm,
                               clip=clip)
    dtype = {k: p.dtype for k, p in params.items()}
    grads = {k: v.astype(dtype[k]) for k, v in grads.items()}
    if noise_std:
        keys = noise_keys(key, leaf_order)
        grads = {k: v + noise(keys[k], v.shape, v.dtype, noise_std * clip_norm)
                 for k, v in grads.items()}
    grads = {k: v.astype(F32) for k, v in grads.items()}
    s = jnp.float32(1.0)
    if opt.global_clip is not None:
        s = jnp.minimum(1.0, opt.global_clip / (jnp.sqrt(_sq(grads)) + 1e-9))
        grads = {k: v * s for k, v in grads.items()}
    leaf_norms = {k: jnp.sqrt(jnp.sum(jnp.square(v))) for k, v in grads.items()}

    t = jnp.asarray(t, F32)
    b1c, b2c = 1.0 - opt.b1 ** t, 1.0 - opt.b2 ** t
    new_p, new_mu, new_nu = {}, {}, {}
    for k, p in params.items():
        g = grads[k]
        m = opt.b1 * mu[k] + (1 - opt.b1) * g
        v = opt.b2 * nu[k] + (1 - opt.b2) * g * g
        p32 = p.astype(F32)
        delta = (m / b1c) / (jnp.sqrt(v / b2c) + opt.eps) \
            + opt.weight_decay * p32
        new_p[k] = (p32 - opt.lr * delta).astype(p.dtype)
        new_mu[k], new_nu[k] = m, v
    return new_p, new_mu, new_nu, losses, sq, leaf_norms, s


def leaf_change_norms(a: dict, b: dict) -> dict:
    return {k: jnp.sqrt(jnp.sum(jnp.square(a[k].astype(F32) - b[k].astype(F32))))
            for k in a}
