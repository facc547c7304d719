"""Composable consumer plans — one fused pass for everything the
norms already pay for (DESIGN.md §9).

The paper's point is that per-example (and per-token) gradient norms
are a cheap *byproduct* of one backward pass. The fixed-function
surface (``value_and_norms`` / ``value_grads_and_norms`` /
``clipped_step`` / ``gradient_noise_scale``) hid that: a run wanting
clipping *and* GNS telemetry paid multiple compiled forwards and
backwards for statistics one pass already produces. This module makes
the consumer side declarative instead:

    res = eng.step(loss_fn, params, batch,
                   consumers=[Clip(1.0), Noise(0.5, rng), GNS()])

``analyze`` folds the consumer list into a ``Plan`` (does anything
need norms? a gradient at all? which weights reweight the backward?),
and ``execute`` compiles it into the minimal program:

  * one tapped forward (``jax.vjp`` — residuals shared by every
    backward application);
  * one activation-only backward seeded with ones when any consumer
    needs norms (the ``dW`` chains are dead code, paper §5);
  * at most one reweighted backward, seeded with the **product** of
    clip coefficients, importance weights, and user loss weights — so
    every gradient-demanding consumer shares it. With no weights the
    norms and gradients fold into a single backward (paper §4/§5);
    with no consumers the taps are never created and the program is
    the plain forward;
  * where the loss allows it, no second backward at all: the norms
    backward also keeps each tap site's ``(H, Z̄)`` (the book,
    ``core.book``) and the weighted gradient is the contraction
    ``Σ_j w_j H_jᵀ Z̄_j`` over it (Bu et al. 2023's book-keeping
    form). ``StepResult.backwards`` says which form was traced.

Per-example weights enter as the cotangent seed of the (B,) loss
vector. Per-token weights (``Clip(C, granularity="token")``) enter as
the seed of the **per-token loss map** the loss registers via
``tap.token_loss`` — the token-weighted loss reweighting pass: the
resulting gradient is exactly ``Σ_{j,t} w_{j,t} · ∂ℓ_{j,t}/∂θ`` by
linearity, with ``w`` derived from the ``TokenLayout`` (B, S) norm map
(exact for every tap, including MoE expert taps — DESIGN.md §8; the
token granularity follows Rochette et al. 2019's per-example
extensions, the telemetry use case Gray et al. 2024 / PAPERS.md).

``Importance(k, ...)`` splits the plan into norms-on-pool → sample →
gather → the *same* plan continuing on the sub-batch: the gathered
pool norms drive the clip coefficients, so the sub-batch pays no
second norms pass.

The mesh path (``dist.pex.plan_step``) wraps the same fused core in
``shard_map``: weights are shard-local (an example's clip coefficient
depends only on its own norm), gradients cross devices in one psum,
and DP-SGD noise is added once after it.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core import book as book_mod
from repro.core import importance as imp
from repro.core.clipping import token_clip_coefficients
from repro.core.passes import (add_grad_noise, add_grad_noise_segmented,
                               check_noise_args, clip_coefficients)
from repro.core.provenance import mark_grad_tree, mark_seed

#: ``jax.named_scope`` names of the step's phases. They reach the
#: compiled HLO's ``op_name`` metadata, and a profiler trace's
#: reduction charges each device operation to the innermost one it
#: lies in; they change nothing else in the program.
FORWARD = "pex.forward"
NORMS_BACKWARD = "pex.norms_backward"
WEIGHT_BACKWARD = "pex.weight_backward"
NOISE = "pex.noise"


# ---------------------------------------------------------------------------
# consumers — the declarative surface
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Norms:
    """Demand the per-example (B, G) — or per-token (B, S) — squared
    norms in the result. Free whenever any other consumer already
    needs them."""


@dataclasses.dataclass(frozen=True)
class Grads:
    """Demand the summed gradient. If the plan carries weights (clip /
    importance / user loss weights) this is the *reweighted* gradient —
    a plan produces exactly one."""


@dataclasses.dataclass(frozen=True)
class Clip:
    """Per-example (or per-token) gradient clipping, two-pass ghost
    form (paper §6): contribute ``min(1, C/‖g‖)`` factors to the
    reweighted backward. ``granularity="token"`` clips every token's
    loss term by its (B, S) contribution norm — the token-weighted
    loss reweighting pass (DESIGN.md §9)."""
    clip_norm: float
    granularity: str = "example"
    eps: float = 1e-6

    def __post_init__(self):
        if self.granularity not in ("example", "token"):
            raise ValueError(f"Clip granularity must be 'example' or "
                             f"'token', got {self.granularity!r}")


@dataclasses.dataclass(frozen=True, eq=False)
class Noise:
    """Gaussian DP-SGD noise σ·scale added once to the summed gradient
    (after the psum on a mesh). ``scale`` defaults to the plan's Clip
    threshold C — standalone Noise needs it explicitly.

    ``segments`` (optional, int (S,) array) switches to *per-segment*
    noise for gradient trees stacked on a leading segment axis — the
    multi-tenant adapter case: row s of every leaf gets an independent
    draw keyed by ``fold_in(rng, segments[s])``, bit-identical to
    noising each tenant's tree alone with its folded key. That makes
    each tenant's DP guarantee independent of co-batched tenants."""
    noise_std: float
    rng: Any = None
    scale: Optional[float] = None
    segments: Any = None


@dataclasses.dataclass(frozen=True, eq=False)
class Importance:
    """Importance-sampled sub-batch (Zhao & Zhang; paper §1): norms on
    the candidate pool, sample ``k`` examples ∝ ‖∇L_j‖, continue the
    plan on the gathered sub-batch with unbiased 1/(k·p_j) weights
    folded into the reweighted backward."""
    k: int
    smoothing: float = 0.1
    rng: Any = None
    replace: bool = True


@dataclasses.dataclass(frozen=True)
class GNS:
    """Gradient-noise-scale telemetry B_simple = tr(Σ)/‖G‖² (Gray et
    al. 2024 / McCandlish et al. 2018) of the gradient estimator the
    plan actually produces: with weights active the per-example norms
    are ``w_j²·s_j`` and G is the reweighted sum."""


_KNOWN = (Norms, Grads, Clip, Noise, Importance, GNS)


# ---------------------------------------------------------------------------
# plan analysis
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True, eq=False)
class Plan:
    """Static description of the fused pass a consumer list compiles
    to. ``token_norms`` selects the (B, S) accumulator for the norms
    backward; ``token_weighted`` seeds the gradient backward through
    the registered per-token loss map."""
    clip: Optional[Clip] = None
    noise: Optional[Noise] = None
    importance: Optional[Importance] = None
    gns: bool = False
    wants_norms: bool = False
    wants_grads: bool = False
    needs_norms: bool = False
    needs_grads: bool = False
    token_norms: bool = False
    token_weighted: bool = False
    #: bytes the book (``core.book``) may take: what the caller has
    #: measured its compiled step to leave (``Engine`` sets it); None
    #: for the device rule, ``core.book.default_room``
    book_room: Optional[int] = None

    @property
    def weighted(self) -> bool:
        """Does any consumer reweight the backward? (User loss_weights
        add to this at execute time.)"""
        return self.clip is not None or self.importance is not None

    @property
    def n_backwards(self) -> int:
        """Backward applications over the fused region's shared
        residuals (without user loss_weights): 0 for the plain
        forward, 1 when norms and the unweighted gradient fold into
        one seed, 2 when a reweighted backward follows the norms pass.
        Never more — the structural claim the one-forward budget
        (``analysis.plan_invariants``) pins on compiled HLO. An upper
        bound: where the loss takes the book, the reweighted plan runs
        one (``StepResult.backwards``)."""
        if not self.needs_norms and not self.needs_grads:
            return 0
        if not self.needs_norms:
            return 1
        if self.needs_grads and (self.weighted or self.token_weighted):
            return 2
        return 1

    def static_cost(self, *, fwd_flops: Optional[float] = None,
                    param_bytes: Optional[float] = None) -> dict:
        """Structural step-budget estimate from the plan shape alone —
        no trace. Flops follow the classic 1-forward/2-backward rule
        per region; gradient HBM reads count the plan-side full-tree
        passes (write + noise add; the optimizer apply adds its own —
        ``analysis.traffic.expected_streams`` has the full count).
        The traced numbers live on ``analysis.cost.CostReport``; this
        is the zero-trace approximation ``describe()`` renders."""
        regions = 1 if self.importance is None else 2
        grad_reads = int(self.needs_grads) * (
            1 + (1 if self.noise is not None else 0))
        out = {"regions": regions, "backwards": self.n_backwards,
               "grad_stream_reads": grad_reads}
        if fwd_flops is not None:
            out["flops_est"] = float(fwd_flops) * (
                regions + 2.0 * self.n_backwards)
        if param_bytes is not None:
            out["grad_bytes_est"] = float(param_bytes) * (1 + grad_reads)
        return out

    def describe(self, *, fwd_flops: Optional[float] = None,
                 param_bytes: Optional[float] = None) -> str:
        """One-line static cost shape of the pass this plan compiles
        to — consumed by ``Engine.verify`` and the pexlint CLI. With
        ``fwd_flops``/``param_bytes`` the line carries the
        ``static_cost`` flop/byte estimates too."""
        regions = 1 if self.importance is None else 2
        parts = [f"regions={regions}", f"backwards={self.n_backwards}",
                 "acc=(B,S)" if self.token_norms else
                 ("acc=(B,G)" if self.needs_norms else "acc=none")]
        if self.clip is not None:
            parts.append(f"clip[{self.clip.granularity}]")
        if self.noise is not None:
            parts.append("noise")
        if self.gns:
            parts.append("gns")
        if self.importance is not None:
            parts.append(f"importance(k={self.importance.k})")
        est = self.static_cost(fwd_flops=fwd_flops,
                               param_bytes=param_bytes)
        if "flops_est" in est:
            parts.append(f"flops≈{est['flops_est']:.3g}")
        if "grad_bytes_est" in est:
            parts.append(f"grad_bytes≈{est['grad_bytes_est']:.3g}")
        return " ".join(parts)


def analyze(consumers: Sequence, *,
            engine_granularity: str = "example") -> Plan:
    """Fold a consumer list into a validated Plan."""
    seen = {}
    for c in consumers:
        if not isinstance(c, _KNOWN):
            raise TypeError(
                f"unknown consumer {c!r}; expected instances of "
                f"{', '.join(k.__name__ for k in _KNOWN)}")
        if type(c) in seen:
            raise ValueError(f"duplicate consumer {type(c).__name__}; a "
                             f"plan carries at most one of each kind")
        seen[type(c)] = c

    clip: Optional[Clip] = seen.get(Clip)
    noise: Optional[Noise] = seen.get(Noise)
    importance: Optional[Importance] = seen.get(Importance)
    gns = GNS in seen

    token_norms = engine_granularity == "token" or (
        clip is not None and clip.granularity == "token")
    token_weighted = clip is not None and clip.granularity == "token"
    if clip is not None and clip.granularity == "example" \
            and engine_granularity == "token":
        raise ValueError(
            "Clip(granularity='example') needs per-example norms, but the "
            "engine runs at token granularity; use Clip(C, "
            "granularity='token') or an example-granularity engine")
    if token_norms and gns:
        raise NotImplementedError(
            "GNS needs per-example ‖g_j‖²; the (B, S) token map does not "
            "sum to them (cross-token terms) — run GNS at example "
            "granularity")
    if token_norms and importance is not None:
        raise NotImplementedError(
            "Importance samples examples from per-example norms; it does "
            "not compose with token-granularity norms in one plan — run "
            "the token pass on the selected sub-batch instead")
    if noise is not None:
        check_noise_args(noise.noise_std, noise.rng)
        if noise.scale is None and clip is None:
            raise ValueError(
                "Noise without Clip needs an explicit sensitivity: pass "
                "Noise(σ, rng, scale=...) — σ·scale is the noise stddev")
        if noise.scale is None and token_weighted:
            raise ValueError(
                "Noise cannot default its sensitivity to a token-"
                "granularity Clip's C: per-token clipping bounds each of "
                "the S token terms by C, so an example's total "
                "contribution is bounded by S·C, not C — pass "
                "Noise(σ, rng, scale=...) with the sensitivity your "
                "accounting assumes")

    if importance is not None and importance.rng is None:
        raise ValueError(
            "Importance needs an rng key: pass Importance(k, rng=...) — "
            "or run through the Trainer, which injects step keys into "
            "rng=None consumers")

    needs_grads = (Grads in seen or clip is not None or noise is not None
                   or gns)
    needs_norms = (Norms in seen or clip is not None or gns
                   or importance is not None)
    return Plan(clip=clip, noise=noise, importance=importance, gns=gns,
                wants_norms=Norms in seen, wants_grads=Grads in seen,
                needs_norms=needs_norms, needs_grads=needs_grads,
                token_norms=token_norms, token_weighted=token_weighted)


class StepResult(NamedTuple):
    """Everything a fused plan produced. Fields a plan did not demand
    are None — ``grads`` when no consumer needed a gradient,
    ``sq_norms`` when none needed norms, etc."""
    loss: jax.Array                 # Σ_j loss_vec (the full input batch)
    loss_vec: jax.Array             # (B,) per-example losses
    aux: Any = None
    sq_norms: Optional[jax.Array] = None    # (B, G) or (B, S)
    grads: Any = None
    weights: Optional[jax.Array] = None     # per-example seed actually used
    token_weights: Optional[jax.Array] = None   # (B, S) token seed
    clip_coef: Optional[jax.Array] = None   # (B,) or (B, S)
    gns: Optional[jax.Array] = None
    sample: Optional[imp.ImportanceSample] = None
    sub_sq_norms: Optional[jax.Array] = None  # pool norms on the sub-batch
    #: backward applications the traced program runs, over all its
    #: regions: a Python int fixed at trace time (2 for a reweighted
    #: plan that kept both backwards, 1 where the book replaced the
    #: second)
    backwards: Optional[int] = None


# ---------------------------------------------------------------------------
# the fused single-region core
# ---------------------------------------------------------------------------

def _vjp(acc_loss: Callable, params, batch, acc0, token_weighted: bool,
         level=None):
    """One tapped forward via ``jax.vjp``; the returned vjp_fn is
    applied up to twice (norms seed / weight seed) over the shared
    residuals, each application DCE'd to its live outputs. With a book
    ``level`` the forward also takes the book's zero slots, whose
    cotangents are the book entries."""
    if level is not None:
        def f(p, acc, slots):
            lv, _tok, acc_out, aux = acc_loss(
                p, acc, batch, book_mod.Booking(book_mod.RECORD, level,
                                                slots))
            return lv, (acc_out, aux)

        lv, vjp_fn, (_, aux) = jax.vjp(f, params, acc0,
                                       book_mod.zeros(level), has_aux=True)
        return lv, None, aux, vjp_fn

    if token_weighted:
        def f(p, acc):
            lv, tok, acc_out, aux = acc_loss(p, acc, batch)
            if tok is None:
                raise ValueError(
                    "per-token reweighting needs the per-token loss map: "
                    "the loss function never called tap.token_loss(...) "
                    "on its (B, S) token losses")
            return (lv, tok), (acc_out, aux)

        (lv, tok), vjp_fn, (_, aux) = jax.vjp(f, params, acc0, has_aux=True)
        return lv, tok, aux, vjp_fn

    def f(p, acc):
        lv, _tok, acc_out, aux = acc_loss(p, acc, batch)
        return lv, (acc_out, aux)

    lv, vjp_fn, (_, aux) = jax.vjp(f, params, acc0, has_aux=True)
    return lv, None, aux, vjp_fn


def run_fused(plan: Plan, acc_loss: Callable, params, batch,
              batch_size: int, layout, *, loss_weights=None):
    """Execute the single-region part of a plan (no importance split,
    no noise, no GNS — drivers add those): one forward, ≤ 2 backward
    applications. Returns ``(loss_vec, aux, sq_norms, grads, weights,
    token_weights, clip_coef, backwards)`` with undemanded entries None;
    ``backwards`` is the number of backward applications traced (a
    Python int).

    Usable directly inside a ``shard_map`` body (``dist.pex``) — all
    per-example quantities are computed shard-locally.

    A reweighted example-granularity plan takes the book (``core.book``)
    where the loss allows it: the norms backward also returns every tap
    site's entry, and the gradient is their weighted contraction — one
    backward, not two. Otherwise it keeps the second backward.
    """
    if not plan.needs_norms and not plan.needs_grads:
        # plain forward: the tap is never live, the program is the model
        with jax.named_scope(FORWARD):
            lv, _, _, aux = acc_loss(params, None, batch)
        return lv, aux, None, None, None, None, None, 0

    if not plan.needs_norms:
        # gradient pass only (possibly user-weighted): no instrumentation
        def f(p):
            lv, _tok, _acc, aux = acc_loss(p, None, batch)
            return lv, aux

        with jax.named_scope(FORWARD):
            lv, vjp_fn, aux = jax.vjp(f, params, has_aux=True)
        seed = mark_seed(loss_weights.astype(lv.dtype), kind="weighted") \
            if loss_weights is not None \
            else mark_seed(jnp.ones_like(lv), kind="plain")
        with jax.named_scope(WEIGHT_BACKWARD):
            (grads,) = vjp_fn(seed)
        return lv, aux, None, grads, loss_weights, None, None, 1

    acc0 = layout.init(batch_size)
    folded = plan.needs_grads and not plan.weighted and loss_weights is None
    level = None
    if plan.needs_grads and not folded and not plan.token_weighted:
        level = book_mod.plan(acc_loss, params, batch, acc0,
                              plan.book_room)
    with jax.named_scope(FORWARD):
        lv, tok, aux, vjp_fn = _vjp(acc_loss, params, batch, acc0,
                                    plan.token_weighted, level)
    if plan.token_weighted and tok.shape[:2] != (lv.shape[0], layout.seq):
        raise ValueError(
            f"the registered per-token loss map has shape {tok.shape}, "
            f"which does not lead with (B, S)=({lv.shape[0]}, "
            f"{layout.seq}) of the TokenLayout accumulator")

    ones = jnp.ones_like(lv)

    def seeds(lv_seed, tok_seed=None):
        if not plan.token_weighted:
            return (lv_seed,)
        return ((lv_seed, tok_seed if tok_seed is not None
                 else jnp.zeros_like(tok)),)

    grads = None
    if folded:
        # norms and gradients fold into ONE backward (paper §4/§5)
        with jax.named_scope(WEIGHT_BACKWARD):
            grads, sq = vjp_fn(*seeds(mark_seed(ones, kind="plain")))
    elif level is not None:
        # dW chains dead → DCE; the book entries come back with the norms
        with jax.named_scope(NORMS_BACKWARD):
            _, sq, entries = vjp_fn(mark_seed(ones, kind="norms"))
    else:
        # dW chains dead → DCE
        with jax.named_scope(NORMS_BACKWARD):
            _, sq = vjp_fn(*seeds(mark_seed(ones, kind="norms")))

    with jax.named_scope(NORMS_BACKWARD):
        w, tw, cc = _compose_weights(plan, sq, loss_weights)
    if level is not None:
        with jax.named_scope(WEIGHT_BACKWARD):
            seed = mark_seed(ones, kind="plain") if w is None \
                else mark_seed(w.astype(lv.dtype), kind="weighted")
            grads = book_contraction(acc_loss, params, batch, level,
                                     entries, seed)
        return lv, aux, sq, grads, w, tw, cc, 1
    if plan.needs_grads and grads is None:
        with jax.named_scope(WEIGHT_BACKWARD):
            if tw is not None:
                tok_seed = tw if w is None else tw * w[:, None]
                grads, _ = vjp_fn((jnp.zeros_like(lv),
                                   mark_seed(tok_seed.astype(tok.dtype),
                                             kind="weighted")))
            else:
                seed = mark_seed(ones, kind="plain") if w is None \
                    else mark_seed(w.astype(lv.dtype), kind="weighted")
                grads, _ = vjp_fn(*seeds(seed))
        return lv, aux, sq, grads, w, tw, cc, 2
    return lv, aux, sq, grads, w, tw, cc, 1


def book_contraction(acc_loss: Callable, params, batch, level, entries,
                     weights):
    """The reweighted gradient ``Σ_j w_j ∂L_j/∂θ`` from the norms
    backward's book (``core.book.grads``)."""
    return book_mod.grads(acc_loss, params, batch, level, entries, weights)


def _compose_weights(plan: Plan, sq_norms, loss_weights,
                     extra_weights=None):
    """Product of clip coefficients × importance weights × user loss
    weights. Returns (per-example w | None, token w | None,
    clip_coef | None)."""
    w = None

    def mul(a, b):
        return b if a is None else a * b

    if loss_weights is not None:
        w = mul(w, loss_weights)
    if extra_weights is not None:
        w = mul(w, extra_weights)
    cc = tw = None
    if plan.clip is not None:
        if plan.clip.granularity == "token":
            cc = tw = token_clip_coefficients(sq_norms, plan.clip.clip_norm,
                                              plan.clip.eps)
        else:
            cc = clip_coefficients(sq_norms, plan.clip.clip_norm,
                                   plan.clip.eps)
            w = mul(w, cc)
    return w, tw, cc


# ---------------------------------------------------------------------------
# the driver: importance split, noise, GNS
# ---------------------------------------------------------------------------

#: a fused_fn: (sub-plan, batch, batch_size, loss_weights) -> the
#: run_fused tuple. ``execute`` defaults to the local one; dist.pex
#: passes a shard_map-wrapping one so the same driver serves the mesh.
FusedFn = Callable[[Plan, Any, int, Optional[jax.Array]], Tuple]

_NORMS_ONLY = Plan(needs_norms=True, wants_norms=True)
_GRADS_ONLY = Plan(needs_grads=True, wants_grads=True)


def execute(plan: Plan, acc_loss: Callable, params, batch,
            batch_size: int, layout, *, loss_weights=None,
            fused_fn: Optional[FusedFn] = None) -> StepResult:
    """Run a full plan: the fused region(s), then noise and GNS."""
    if fused_fn is None:
        def fused_fn(sub, b, bs, lw):
            return run_fused(sub, acc_loss, params, b, bs, layout,
                             loss_weights=lw)

    samp = sub_sq = None
    if plan.importance is None:
        lv, aux, sq, grads, w, tw, cc, backwards = fused_fn(
            plan, batch, batch_size, loss_weights)
    else:
        ip = plan.importance
        lv, aux, sq, _, _, _, _, backwards = fused_fn(
            _NORMS_ONLY, batch, batch_size, None)
        samp = imp.sample(ip.rng, sq, ip.k, smoothing=ip.smoothing,
                          replace=ip.replace)
        sub_batch = imp.gather_batch(batch, samp.indices,
                                     batch_size=batch_size)
        sub_sq = jnp.take(sq, samp.indices, axis=0)
        w, tw, cc = _compose_weights(
            plan, sub_sq,
            None if loss_weights is None
            else jnp.take(loss_weights, samp.indices, axis=0),
            extra_weights=samp.weights)
        grads = None
        if plan.needs_grads:
            _, _, _, grads, w, _, _, n = fused_fn(_GRADS_ONLY, sub_batch,
                                                  ip.k, w)
            backwards += n

    gns = None
    if plan.gns:
        gns = gradient_noise_scale(
            sq if sub_sq is None else sub_sq, grads,
            batch_size=batch_size if samp is None else plan.importance.k,
            weights=w)
    if grads is not None:
        # the plan/apply boundary: everything downstream (noise add,
        # optimizer moments) re-reads the summed gradient from HBM —
        # the traffic pass counts those passes per leaf off these
        # identity markers (they vanish in lowering)
        grads = mark_grad_tree(grads)
    if plan.noise is not None and grads is not None:
        scale = plan.noise.scale if plan.noise.scale is not None \
            else plan.clip.clip_norm
        with jax.named_scope(NOISE):
            if plan.noise.segments is not None:
                grads = add_grad_noise_segmented(
                    grads, plan.noise.noise_std, scale, plan.noise.rng,
                    plan.noise.segments)
            else:
                grads = add_grad_noise(grads, plan.noise.noise_std, scale,
                                       plan.noise.rng)
    return StepResult(jnp.sum(lv), lv, aux, sq, grads, w, tw, cc, gns,
                      samp, sub_sq, backwards)


# ---------------------------------------------------------------------------
# GNS — a thin consumer over quantities the plan already has
# ---------------------------------------------------------------------------

def gradient_noise_scale(sq_norms: jax.Array, grads,
                         batch_size: Optional[int] = None,
                         weights: Optional[jax.Array] = None) -> jax.Array:
    """Critical-batch diagnostic B_simple = tr(Σ) / ‖G‖² from the
    per-example squared norms the pipeline already computes.

    With s̄ = mean_j ‖g_j‖² and the batch gradient G_B (= mean of the
    per-example gradients): E[s̄] = tr(Σ) + ‖G‖² and
    E[‖G_B‖²] = ‖G‖² + tr(Σ)/B, so both moments are recovered
    unbiasedly from one step — the large-batch monitoring quantity of
    Gray et al. (2024) / McCandlish et al. (2018). ``grads`` is the
    *summed* gradient pytree; pass ``batch_size`` when it differs from
    ``len(sq_norms)``. ``weights`` (per-example reweighting active in
    the plan) scales the norms by w² so the estimate describes the
    reweighted estimator.
    """
    if sq_norms.ndim == 2:
        sq_norms = jnp.sum(sq_norms, axis=-1)
    if weights is not None:
        sq_norms = sq_norms * jnp.square(weights.astype(jnp.float32))
    b = batch_size if batch_size is not None else sq_norms.shape[0]
    if b < 2:
        raise ValueError(f"gradient_noise_scale needs batch >= 2 to "
                         f"separate the two moments (got {b})")
    s_bar = jnp.mean(sq_norms.astype(jnp.float32))
    g_mean_sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                    for g in jax.tree_util.tree_leaves(grads)) / (b * b)
    tr_sigma = (s_bar - g_mean_sq) * b / (b - 1)
    norm_g_sq = (b * g_mean_sq - s_bar) / (b - 1)
    return tr_sigma / jnp.maximum(norm_g_sq, 1e-20)
