"""Mesh-sharded per-example-norm pipeline (DESIGN.md §4).

Lifts the ``core.passes`` transforms onto a device mesh with
``shard_map``: the batch is split over the data axes, each shard runs
the tap-instrumented model on its local examples, and only the
*parameter gradients* (and scalar loss) cross devices via ``psum``.
The per-example quantities — the (B,) loss vector and the (B, G)
squared norms — stay batch-sharded end to end, which is the whole
point: the accumulator technique adds no collective traffic (taps
docstring), and this module keeps that true on a mesh.

Clipping composes for free: the clip coefficient c_j depends only on
example j's own norm, so it is computed shard-locally and the clipped
gradients allreduce exactly like plain ones. DP-SGD noise is added
once, *after* the psum — adding it per-shard would inflate the noise
variance by the shard count. ``plan_step`` extends the same rules to
whole consumer plans (DESIGN.md §9): the fused norms→weights→
reweighted-backward core runs per shard inside one region, with only
the gradient psum crossing devices.

Parameters are replicated into every shard, so mesh axes not named in
``data_axes`` (e.g. "model") must have extent 1: the standard
``("data", "model")`` mesh from ``launch.mesh`` composes directly when
its model axis is a singleton. A model axis of extent > 1 is rejected
with an actionable error until the per-example pipeline shards
parameters — pure data parallelism (any extent, any number of data
axes) is fully supported.
"""
from __future__ import annotations

from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import passes as api
from repro.core import plan as plan_mod
from repro.core.passes import PexResult
from repro.core.taps import PexSpec
from repro.dist import sharding as shd

DataAxes = Tuple[str, ...]


def _norm_axes(data_axes) -> DataAxes:
    if isinstance(data_axes, str):
        return (data_axes,)
    return tuple(data_axes)


def _reject_aux(aux) -> None:
    """The sharded paths return PexResult.aux == {}: an arbitrary aux
    pytree has no inferable out_spec (per-example? replicated?). Fail
    loudly at trace time instead of silently dropping real metrics."""
    if jax.tree_util.tree_leaves(aux):
        raise NotImplementedError(
            "loss_fn returned a non-empty aux pytree, which the sharded "
            "per-example pipeline does not thread through; fold metrics "
            "into loss_vec/sq_norms or run single-device (mesh=None)")


def _check_data_only(mesh: Mesh, data_axes: DataAxes) -> None:
    big_rest = [a for a in mesh.axis_names
                if a not in data_axes and mesh.shape[a] > 1]
    if big_rest:
        raise NotImplementedError(
            f"mesh axes {big_rest} (extent > 1) outside data_axes="
            f"{data_axes}: the per-example pipeline replicates parameters "
            f"into every shard and does not yet combine per-example norms "
            f"across a model axis; run it data-parallel-only, or include "
            f"the axis in data_axes")


def _wrap(mesh: Mesh, data_axes: DataAxes, fn: Callable, n_out_sharded: int,
          n_out_replicated: int) -> Callable:
    """shard_map ``fn(params, batch) -> (*sharded, *replicated)``.

    ``sharded`` outputs carry a leading batch axis split over
    ``data_axes``; ``replicated`` outputs (scalar loss, psum'd grads)
    are identical on every shard. Axes outside ``data_axes`` stay auto
    so in-model tensor-parallel constraints keep working.
    """
    dp = P(data_axes)
    _check_data_only(mesh, data_axes)
    # extent-1 non-data axes are safely manual (replication over a
    # singleton is trivial)
    out_specs = tuple([dp] * n_out_sharded + [P()] * n_out_replicated)

    def body(params, batch):
        # inside the fully-manual region every placement is already
        # decided, so in-model rules-based constraints (dist.sharding
        # .shard) must go quiet — they reference manual mesh axes,
        # which with_sharding_constraint rejects
        with shd.use_rules(None, {}):
            return fn(params, batch)

    return jax.shard_map(body, mesh=mesh, in_specs=(P(), dp),
                         out_specs=out_specs, check_vma=False)


def value_and_norms(loss_fn: Callable, params, batch, spec: PexSpec,
                    batch_size: int, *, mesh: Optional[Mesh] = None,
                    data_axes: Sequence[str] = ("data",),
                    layout=None) -> PexResult:
    """Sharded norms-only pass. Single-device semantics when mesh=None.

    Returns the same PexResult as ``core.passes.value_and_norms``; the
    loss is the global scalar, ``loss_vec``/``sq_norms`` are the full
    (B,)/(B, G) arrays, laid out batch-sharded over ``data_axes``.
    ``aux`` is always {} on the mesh path (non-empty aux raises — see
    ``_reject_aux``); the grads/clipped variants share this contract.
    """
    if mesh is None:
        return api.value_and_norms(loss_fn, params, batch, spec, batch_size,
                                   layout=layout)
    data_axes = _norm_axes(data_axes)
    local_b = shd.local_batch(batch_size, data_axes, mesh)

    def run(p, b):
        r = api.value_and_norms(loss_fn, p, b, spec, local_b, layout=layout)
        _reject_aux(r.aux)
        return r.loss_vec, r.sq_norms, jax.lax.psum(r.loss, data_axes)

    loss_vec, sq, loss = _wrap(mesh, data_axes, run, 2, 1)(params, batch)
    return PexResult(loss, loss_vec, {}, sq)


def value_grads_and_norms(loss_fn: Callable, params, batch, spec: PexSpec,
                          batch_size: int, *, mesh: Optional[Mesh] = None,
                          data_axes: Sequence[str] = ("data",),
                          layout=None) -> PexResult:
    """Sharded headline pass: summed gradients (psum over the data
    axes) AND batch-sharded per-example norms in one backward."""
    if mesh is None:
        return api.value_grads_and_norms(loss_fn, params, batch, spec,
                                         batch_size, layout=layout)
    data_axes = _norm_axes(data_axes)
    local_b = shd.local_batch(batch_size, data_axes, mesh)

    def run(p, b):
        r = api.value_grads_and_norms(loss_fn, p, b, spec, local_b,
                                      layout=layout)
        _reject_aux(r.aux)
        return (r.loss_vec, r.sq_norms,
                jax.lax.psum(r.loss, data_axes),
                jax.lax.psum(r.grads, data_axes))

    loss_vec, sq, loss, grads = _wrap(mesh, data_axes, run, 2, 2)(
        params, batch)
    return PexResult(loss, loss_vec, {}, sq, grads)


def clipped_value_and_grads(loss_fn: Callable, params, batch, spec: PexSpec,
                            batch_size: int, clip_norm: float,
                            noise_std: float = 0.0,
                            noise_rng: Optional[jax.Array] = None, *,
                            mesh: Optional[Mesh] = None,
                            data_axes: Sequence[str] = ("data",),
                            layout=None) -> PexResult:
    """Sharded per-example clipping (paper §6, two-pass ghost form).

    c_j uses only example j's local norm, so both passes run entirely
    shard-local; one gradient psum at the end. Noise is added once to
    the reduced gradient (matching the single-device DP-SGD step), not
    per shard.
    """
    api.check_noise_args(noise_std, noise_rng)
    if mesh is None:
        return api.clipped_value_and_grads(loss_fn, params, batch, spec,
                                           batch_size, clip_norm,
                                           noise_std=noise_std,
                                           noise_rng=noise_rng, layout=layout)
    data_axes = _norm_axes(data_axes)
    local_b = shd.local_batch(batch_size, data_axes, mesh)

    def run(p, b):
        r = api.clipped_value_and_grads(loss_fn, p, b, spec, local_b,
                                        clip_norm, layout=layout)
        _reject_aux(r.aux)
        return (r.loss_vec, r.sq_norms,
                jax.lax.psum(r.loss, data_axes),
                jax.lax.psum(r.grads, data_axes))

    loss_vec, sq, loss, grads = _wrap(mesh, data_axes, run, 2, 2)(
        params, batch)
    if noise_std > 0.0:
        with jax.named_scope(plan_mod.NOISE):
            grads = api.add_grad_noise(grads, noise_std, clip_norm,
                                       noise_rng)
    return PexResult(loss, loss_vec, {}, sq, grads)


# --- consumer plans (DESIGN.md §9) -----------------------------------------

def plan_step(plan, acc_loss: Callable, params, batch, batch_size: int, *,
              mesh: Optional[Mesh], data_axes: Sequence[str] = ("data",),
              layout=None, loss_weights=None) -> plan_mod.StepResult:
    """Run a fused consumer plan under ``shard_map``.

    The single-region core (``plan.run_fused``) executes inside one
    shard_map body per fused stage: per-example losses, norms, and
    weights stay batch-sharded (a clip coefficient depends only on its
    own example's norm), gradients cross devices in ONE psum, and
    DP-SGD noise / GNS telemetry are applied by the shared driver
    *after* the region — noise once on the reduced gradient, GNS on
    the global arrays. ``Importance`` plans run two regions
    (norms-on-pool, reweighted grads on the gathered sub-batch) with
    the sampling and the gather on the global arrays between them —
    both the pool size and ``k`` must divide the data-shard count
    (``shd.local_batch`` raises otherwise).
    """
    if mesh is None:
        return plan_mod.execute(plan, acc_loss, params, batch, batch_size,
                                layout, loss_weights=loss_weights)
    data_axes = _norm_axes(data_axes)
    dp = P(data_axes)
    _check_data_only(mesh, data_axes)

    def fused_fn(sub, b, bs, lw):
        local_b = shd.local_batch(bs, data_axes, mesh)
        # presence of each optional output is static per sub-plan, so
        # the shard_map out_specs can be built up front
        # one sharded output slot per quantity the sub-plan produces
        # (static per plan): loss_vec, norms, the per-example weight
        # product, and the clip coefficients — which for a token clip
        # ARE the token weights, so they share a slot
        has_sq = sub.needs_norms
        has_w = lw is not None or (sub.clip is not None and
                                   sub.clip.granularity == "example")
        has_cc = sub.clip is not None
        has_grads = sub.needs_grads

        backwards = []      # traced once: the region's backward count

        def run(p, bb, *maybe_lw):
            with shd.use_rules(None, {}):
                lv, aux, sq, grads, w, tw, cc, n = plan_mod.run_fused(
                    sub, acc_loss, p, bb, local_b, layout,
                    loss_weights=maybe_lw[0] if maybe_lw else None)
            backwards.append(n)
            _reject_aux(aux)
            outs = [lv]
            if has_sq:
                outs.append(sq)
            if has_w:
                outs.append(w)
            if has_cc:
                outs.append(cc)
            if has_grads:
                outs.append(jax.lax.psum(grads, data_axes))
            return tuple(outs)

        n_sharded = 1 + has_sq + has_w + has_cc
        out_specs = tuple([dp] * n_sharded + [P()] * int(has_grads))
        in_specs = (P(), dp) + ((dp,) if lw is not None else ())
        args = (params, b) + ((lw,) if lw is not None else ())
        res = list(jax.shard_map(run, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False)(*args))

        lv = res.pop(0)
        sq = res.pop(0) if has_sq else None
        w = res.pop(0) if has_w else None
        cc = res.pop(0) if has_cc else None
        tw = cc if sub.token_weighted else None
        grads = res.pop(0) if has_grads else None
        return lv, {}, sq, grads, w, tw, cc, backwards[0]

    return plan_mod.execute(plan, acc_loss, params, batch, batch_size,
                            layout, loss_weights=loss_weights,
                            fused_fn=fused_fn)


# --- diagnostics -----------------------------------------------------------

def gradient_noise_scale(sq_norms: jax.Array, grads,
                         batch_size: Optional[int] = None) -> jax.Array:
    """B_simple = tr(Σ) / ||G||² — see ``core.plan.gradient_noise_scale``
    (the formula moved there with the plan layer; this re-export keeps
    the established dist-level entry point)."""
    return plan_mod.gradient_noise_scale(sq_norms, grads,
                                         batch_size=batch_size)
