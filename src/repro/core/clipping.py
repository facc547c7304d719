"""Clipping — the coefficient math behind the ``Clip`` consumer, plus
the paper's §6 one-pass form kept as the faithful MLP-era oracle.

Production clipping is the ``Clip`` consumer of the plan layer
(``core.plan``): per-example coefficients ``min(1, C/‖g_j‖)`` — or
per-token coefficients from the (B, S) ``TokenLayout`` map — become
cotangent seeds of ONE reweighted backward shared with every other
gradient-demanding consumer (DESIGN.md §9). This module holds the
coefficient helpers that consumer folds into the weight product
(``core.passes.clip_coefficients`` for the example form,
``token_clip_coefficients`` below for the token form).

The rest of the file is paper §6's *one-pass* form: modify Z̄ and
re-run only the last step. This is the faithful rendering of the
paper's extension: after the norms are known, each example's Z̄ rows
are rescaled in place and the final backprop step
W̄⁽ⁱ⁾' = X⁽ⁱ⁾ᵀ Z̄⁽ⁱ⁾'  is recomputed — no second backward pass. It
requires materializing every (H, Z̄) pair, which is exactly what the
paper's MLP setting affords; for deep scanned LMs the plan layer runs
the same idea as the book (``core.book``: entries kept by the norms
backward, a bounded lifetime, a memory rule) and falls back to the
two-pass form (O(batch) memory) — see DESIGN.md §2.

Mechanism: "perturbation taps". The model forward is written as

    forward(params, taps, batch) -> (loss_vec, hs)

where each dense layer computes ``z = h @ W + taps[name]`` with
``taps[name]`` a zeros array of z's shape, and ``hs[name]`` is the
layer input it returns. ``jax.vjp`` w.r.t. ``taps`` then yields the
per-example Z̄ for every layer in one backward pass — the quantity the
paper says "standard backpropagation values allow us to compute".
"""
from __future__ import annotations

from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core.passes import clip_coefficients
from repro.dist.sharding import shard


def token_clip_coefficients(sq_norms: jax.Array, clip_norm: float,
                            eps: float = 1e-6) -> jax.Array:
    """c_{j,t} = min(1, C / ‖g_{j,t}‖) elementwise on the (B, S)
    ``TokenLayout`` norm map — the per-token analogue of
    ``passes.clip_coefficients`` (which sums group columns; the token
    map has none to sum)."""
    from repro.core.provenance import mark_clip
    c = jnp.minimum(1.0, clip_norm /
                    (jnp.sqrt(sq_norms.astype(jnp.float32)) + eps))
    return mark_clip(c, clip_norm=clip_norm, eps=eps, granularity="token")


def zero_taps(shapes: Dict[str, Tuple[int, ...]], dtype=jnp.float32):
    """Zero perturbation taps, batch-sharded under an active mesh: each
    tap (and its cotangent Z̄) leads with the example axis, so the whole
    one-pass pipeline stays shard-local like the accumulator form."""
    return {k: shard(jnp.zeros(s, dtype), "batch", *([None] * (len(s) - 1)))
            for k, s in shapes.items()}


def norms_from_taps(hs: Dict[str, jax.Array],
                    zbars: Dict[str, jax.Array]) -> jax.Array:
    """Paper §4: s_j = Σ_i ||z̄_j⁽ⁱ⁾||²·||h_j⁽ⁱ⁻¹⁾||² (rank-1 / MLP case)."""
    total = None
    for name, zb in zbars.items():
        h = hs[name]
        s = (jnp.sum(jnp.square(zb.astype(jnp.float32)), axis=-1) *
             jnp.sum(jnp.square(h.astype(jnp.float32)), axis=-1))
        while s.ndim > 1:  # fold any extra shared axes (exactness: MLP only)
            s = jnp.sum(s, axis=-1)
        total = s if total is None else total + s
    return total


def norms_from_taps_seq(hs: Dict[str, jax.Array],
                        zbars: Dict[str, jax.Array]) -> jax.Array:
    """Exact per-example norms from (H, Z̄) under sequence weight
    sharing: Σ_i ||H_i^(j)ᵀ Z̄_i^(j)||²_F via the Gram identity — the
    generalization of §4 this framework contributes (DESIGN.md §2)."""
    from repro.core import norms as N
    total = None
    for name, zb in zbars.items():
        s = N.stat_dense(hs[name], zb, method="auto")
        total = s if total is None else total + s
    return total


def onepass_clipped_weight_grads_seq(forward: Callable, params, batch,
                                     tap_shapes: Dict[str, Tuple[int, ...]],
                                     clip_norm: float):
    """§6 one-pass for sequence models: identical flow to the MLP form,
    but norms use the exact Gram estimator and the final step is
    W̄⁽ⁱ⁾' = Σ_t X_tᵀ (c ⊙ Z̄_t). One backward pass; the re-run is only
    the dW einsums (cheaper than the two-pass form, at the cost of
    storing every (H, Z̄) — the memory/compute trade both forms of §6
    offer; core.passes.clipped_value_and_grads is the O(batch)-memory
    alternative)."""
    taps = zero_taps(tap_shapes)

    def f(tp):
        loss_vec, hs = forward(params, tp, batch)
        return jnp.sum(loss_vec), (loss_vec, hs)

    total, vjp, (loss_vec, hs) = jax.vjp(f, taps, has_aux=True)
    (zbars,) = vjp(jnp.ones_like(total))
    sq_norms = norms_from_taps_seq(hs, zbars)
    c = clip_coefficients(sq_norms, clip_norm)

    wbar = {}
    for name, zb in zbars.items():
        zb_scaled = zb * c.reshape((-1,) + (1,) * (zb.ndim - 1))
        wbar[name] = jnp.einsum("b...i,b...o->io", hs[name], zb_scaled)
    return loss_vec, sq_norms, wbar


def onepass_clipped_weight_grads(forward: Callable, params, batch,
                                 tap_shapes: Dict[str, Tuple[int, ...]],
                                 clip_norm: float):
    """Run the full §6 pipeline once.

    Returns (loss_vec, sq_norms, wbar_prime) where ``wbar_prime`` maps
    layer name -> clipped-sum weight gradient  X⁽ⁱ⁾ᵀ (c ⊙ Z̄⁽ⁱ⁾).
    """
    taps = zero_taps(tap_shapes)

    def f(tp):
        loss_vec, hs = forward(params, tp, batch)
        return jnp.sum(loss_vec), (loss_vec, hs)

    total, vjp, (loss_vec, hs) = jax.vjp(f, taps, has_aux=True)
    (zbars,) = vjp(jnp.ones_like(total))
    sq_norms = norms_from_taps(hs, zbars)
    c = clip_coefficients(sq_norms, clip_norm)

    wbar = {}
    for name, zb in zbars.items():
        zb_scaled = zb * c.reshape((-1,) + (1,) * (zb.ndim - 1))
        wbar[name] = jnp.einsum("...i,...o->io", hs[name], zb_scaled)
    return loss_vec, sq_norms, wbar
