"""pex v2 ``Engine`` — one entry point for local, sharded, and
token-level per-example-gradient runs (DESIGN.md §7, §9).

The Engine is constructed once with the instrumentation policy and the
execution context, and every pass takes a **tap-collector loss**

    loss_fn(params, batch, tap) -> (loss_vec, aux)

(the v2 canonical signature; ``registry.make_loss_fn_v2`` builds one
for any registered arch). The consumer surface is declarative
(``core.plan``): ``step`` compiles a list of consumers into one fused
execution plan — a single tapped forward, an activation backward for
the norms every consumer shares, and at most one reweighted backward
whose per-example (or per-token) weights are the product of clip
coefficients, importance weights, and user loss weights:

    eng = Engine(PexSpec(method="auto"), mesh=mesh)
    res = eng.step(loss_fn, params, batch,
                   consumers=[pex.Clip(1.0), pex.Noise(0.5, rng),
                              pex.GNS()])
    res.grads, res.sq_norms, res.gns   # one compiled program

The four fixed-function methods (``value_and_norms``,
``value_grads_and_norms``, ``clipped_step``,
``gradient_noise_scale``) remain as one-line sugar over ``step``.

``granularity="token"`` swaps the accumulator layout to the per-token
``(B, S)`` map (``TokenLayout``) — same taps, same passes, token-level
norms; with a loss that registers its token map (``tap.token_loss``),
``Clip(C, granularity="token")`` reweights every token's loss term by
its own contribution norm in the same fused pass.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Sequence

import jax

from repro.core import plan as plan_mod
from repro.core.passes import PexResult
from repro.core.plan import StepResult
from repro.core.taps import DISABLED, ExampleLayout, PexSpec, Tap, TokenLayout
from repro.dist import pex as _dpex


def infer_batch_size(batch) -> int:
    """Leading-axis extent shared by every batch leaf."""
    leaves = jax.tree_util.tree_leaves(batch)
    if not leaves:
        raise ValueError("cannot infer batch_size from an empty batch pytree")
    sizes = {leaf.shape[0] for leaf in leaves}
    if len(sizes) != 1:
        raise ValueError(f"batch leaves disagree on the leading (example) "
                         f"axis: {sorted(sizes)}; pass batch_size= explicitly")
    return sizes.pop()


def infer_seq_len(batch) -> int:
    """Second-axis extent of the sequence-shaped batch leaves; must be
    unambiguous (multi-sequence batches, e.g. encoder-decoder frames vs
    ids, need an explicit seq=)."""
    sizes = {leaf.shape[1] for leaf in jax.tree_util.tree_leaves(batch)
             if leaf.ndim >= 2}
    if len(sizes) == 1:
        return sizes.pop()
    if not sizes:
        raise ValueError("token granularity needs a (B, S, ...) batch leaf "
                         "to infer the sequence length; pass seq= explicitly")
    raise ValueError(f"batch leaves carry different sequence lengths "
                     f"{sorted(sizes)}; pass seq= explicitly to pick the "
                     f"tapped one")


class Engine:
    """Per-example-gradient engine bound to one (spec, mesh, policy).

    Parameters
    ----------
    spec:        ``PexSpec`` instrumentation policy (default: enabled,
                 method='auto'). ``taps.DISABLED`` gives a plain engine
                 (taps compile away; sq_norms are zeros).
    mesh:        ``jax.sharding.Mesh`` or None. A mesh routes every pass
                 through the ``dist.pex`` shard_map pipeline over
                 ``data_axes``; None runs single-device/GSPMD.
    clip_norm:   default clip threshold C for ``clipped_step``.
    noise_std:   default DP-SGD noise multiplier σ for ``clipped_step``
                 (noise σ·C is added once, after the gradient psum).
    granularity: 'example' → (B, G) accumulator (per-group columns from
                 ``spec.groups``); 'token' → (B, S) accumulator.
    """

    def __init__(self, spec: Optional[PexSpec] = None, *,
                 mesh=None, data_axes: Sequence[str] = ("data",),
                 clip_norm: Optional[float] = None, noise_std: float = 0.0,
                 granularity: str = "example"):
        if granularity not in ("example", "token"):
            raise ValueError(f"granularity must be 'example' or 'token', "
                             f"got {granularity!r}")
        self.spec = spec if spec is not None else PexSpec()
        self.mesh = mesh
        self.data_axes = (data_axes,) if isinstance(data_axes, str) \
            else tuple(data_axes)
        self.clip_norm = clip_norm
        self.noise_std = noise_std
        self.granularity = granularity
        #: bytes the clipped plan's book may take (``Plan.book_room``):
        #: None for the device rule; a caller that has measured its
        #: compiled step sets it (``Trainer``: 0 where the booked step
        #: would not fit the device)
        self._book_room: Optional[int] = None

    # ------------------------------------------------------------------
    def _adapt(self, loss_fn: Callable, layout,
               want_token_map: bool = False) -> Callable:
        """Tap-collector loss → the explicit-acc loss the plan layer
        consumes; the Tap is created inside the traced function, per
        trace. Signature of the result:
        ``acc_loss(params, acc, batch, book=None) -> (loss_vec,
        token_map|None, acc_out, aux)`` (acc=None ⇒ inert tap ⇒ the
        plain model; ``book``: the ``core.book.Booking`` the tap books
        its sites under)."""
        def acc_loss(params, acc, batch, book=None):
            tap = Tap(self.spec, acc=acc, layout=layout, book=book)
            loss_vec, aux = loss_fn(params, batch, tap)
            tok = tap.token_losses() if want_token_map else None
            return loss_vec, tok, tap.carry(), aux
        return acc_loss

    # ------------------------------------------------------------------
    def step(self, loss_fn: Callable, params, batch,
             consumers: Sequence = (), *,
             loss_weights: Optional[jax.Array] = None,
             batch_size: Optional[int] = None,
             seq: Optional[int] = None) -> StepResult:
        """Compile a consumer list into one fused pass and run it.

        ``consumers`` is any subset of ``{pex.Norms(), pex.Grads(),
        pex.Clip(C, granularity=...), pex.Noise(σ, rng),
        pex.Importance(k, ...), pex.GNS()}`` — each a ~30-line
        declarative object; composition and weight semantics are
        DESIGN.md §9. ``loss_weights`` is an optional (B,) user weight
        vector folded into the same reweighted backward. With
        ``consumers=()`` the program is the plain forward."""
        plan = plan_mod.analyze(consumers,
                                engine_granularity=self.granularity)
        plan = dataclasses.replace(plan, book_room=self._book_room)
        b = batch_size if batch_size is not None else infer_batch_size(batch)
        if plan.token_norms:
            layout = TokenLayout(seq if seq is not None
                                 else infer_seq_len(batch))
        else:
            layout = ExampleLayout(self.spec.n_groups)
        acc_loss = self._adapt(loss_fn, layout,
                               want_token_map=plan.token_weighted)
        if self.mesh is None:
            return plan_mod.execute(plan, acc_loss, params, batch, b,
                                    layout, loss_weights=loss_weights)
        return _dpex.plan_step(plan, acc_loss, params, batch, b,
                               mesh=self.mesh, data_axes=self.data_axes,
                               layout=layout, loss_weights=loss_weights)

    # -- fixed-function sugar (one line each over `step`) ---------------
    def value_and_norms(self, loss_fn: Callable, params, batch, *,
                        batch_size: Optional[int] = None,
                        seq: Optional[int] = None) -> PexResult:
        """Norms-only pass (paper §5 cheap pass): no ``dW`` chains."""
        r = self.step(loss_fn, params, batch, [plan_mod.Norms()],
                      batch_size=batch_size, seq=seq)
        return PexResult(r.loss, r.loss_vec, r.aux, r.sq_norms)

    def value_grads_and_norms(self, loss_fn: Callable, params, batch, *,
                              batch_size: Optional[int] = None,
                              seq: Optional[int] = None) -> PexResult:
        """Summed gradients AND all per-example norms in one backward."""
        r = self.step(loss_fn, params, batch,
                      [plan_mod.Norms(), plan_mod.Grads()],
                      batch_size=batch_size, seq=seq)
        return PexResult(r.loss, r.loss_vec, r.aux, r.sq_norms, r.grads)

    def clipped_step(self, loss_fn: Callable, params, batch, *,
                     rng: Optional[jax.Array] = None,
                     clip_norm: Optional[float] = None,
                     noise_std: Optional[float] = None,
                     batch_size: Optional[int] = None,
                     seq: Optional[int] = None) -> PexResult:
        """Per-example clipping (paper §6 two-pass ghost form), plus
        DP-SGD noise when ``noise_std > 0`` (needs ``rng``). On a
        token-granularity engine this is per-token clipping."""
        c = clip_norm if clip_norm is not None else self.clip_norm
        if c is None:
            raise ValueError("clipped_step needs clip_norm: set it on the "
                             "Engine or pass clip_norm= per call")
        sigma = noise_std if noise_std is not None else self.noise_std
        consumers = [plan_mod.Clip(c, granularity=self.granularity)]
        if sigma and sigma > 0.0:
            # on a token engine, analyze() rejects the defaulted scale
            # (per-token C is not a per-example sensitivity)
            consumers.append(plan_mod.Noise(sigma, rng))
        r = self.step(loss_fn, params, batch, consumers,
                      batch_size=batch_size, seq=seq)
        return PexResult(r.loss, r.loss_vec, r.aux, r.sq_norms, r.grads)

    def gradient_noise_scale(self, loss_fn: Callable, params, batch, *,
                             batch_size: Optional[int] = None) -> jax.Array:
        """Critical-batch diagnostic B_simple = tr(Σ)/||G||² from one
        grads+norms pass (Gray et al. 2024 / McCandlish et al. 2018)."""
        return self.step(loss_fn, params, batch, [plan_mod.GNS()],
                         batch_size=batch_size).gns

    # ------------------------------------------------------------------
    def verify(self, loss_fn: Callable, params, batch,
               consumers: Sequence = (), *, allow: Sequence[str] = (),
               batch_size: Optional[int] = None,
               seq: Optional[int] = None, cfg=None,
               backend: str = "tpu", deep: bool = True,
               cost: bool = False, optimizer: str = "adamw",
               profile: Optional[str] = None, chips: int = 1,
               model: Optional[str] = None):
        """Static verification of a (model, plan) pair — trace-only,
        no compilation, safe on abstract ``ShapeDtypeStruct`` params
        and batches (DESIGN.md §10, §12).

        Runs the pexlint passes against THIS engine's spec, mesh, and
        granularity: plan analysis of ``consumers`` (one list or a
        sequence of lists), tap-coverage verification of ``loss_fn``
        (``allow`` declares intentionally-untapped parameter path
        substrings; ``registry.untapped_allowlist`` has the registered
        archs' tables), launch validation of every Pallas schedule
        the trace's tap sites imply (``cfg`` additionally checks the
        config-derived production geometries), and — with ``deep`` —
        the privacy-flow, collective-layout (mesh engines), and
        determinism passes over full step traces. With ``cost``, the
        traffic/cost passes additionally trace the full training step
        — plan execution plus the ``optimizer`` apply — and attach
        ``TrafficReport``/``CostReport`` tuples (predicted on hardware
        ``profile`` × ``chips``). Returns a
        ``repro.analysis.VerifyReport``; ``.ok`` /
        ``.raise_if_errors()`` gate on it."""
        from repro.analysis.verify import verify as _verify
        return _verify(loss_fn, params, batch, consumers,
                              spec=self.spec,
                              granularity=self.granularity, allow=allow,
                              batch_size=batch_size, seq=seq, cfg=cfg,
                              backend=backend, mesh=self.mesh,
                              data_axes=self.data_axes, deep=deep,
                              cost=cost, optimizer=optimizer,
                              profile=profile, chips=chips, model=model)

    # ------------------------------------------------------------------
    def tap(self, batch_size: int, *, seq: Optional[int] = None) -> Tap:
        """Standalone live Tap for hand-rolled transforms (the Engine
        passes above create their own)."""
        layout = TokenLayout(seq) if self.granularity == "token" \
            else ExampleLayout(self.spec.n_groups)
        return Tap(self.spec, acc=layout.init(batch_size), layout=layout)


#: Engine with instrumentation off: every pass is the plain model
#: (sq_norms are zeros; taps compile away).
def plain_engine(**kw) -> Engine:
    return Engine(DISABLED, **kw)
