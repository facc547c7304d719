"""Plan-invariant analyzers: the zero-overhead and one-forward-budget
claims, pinned by HLO cost (pexlint pass 2, DESIGN.md §10).

These promote the flop/byte assertions that grew up inside
``tests/test_dce.py`` and ``benchmarks/bench_plan.py`` into reusable
checks, so the CLI lints them per model and the test/bench callers
share one definition of each invariant:

  * a DISABLED spec (or an enabled spec whose stats nobody reads)
    lowers to the plain model — the DCE property;
  * ``step([])`` compiles to exactly the plain forward;
  * ``step([Grads()])`` costs no more than plain ``value_and_grad``;
  * the Clip plan fits the one-forward budget
    ``cost(norms) + (cost(grad) − cost(forward))`` — one tapped
    forward, one activation backward, ONE reweighted backward — and,
    where it traced one backward (the book, ``core.book``), the
    tighter ``cost(norms) + BOOK_SHARE·(cost(grad) − cost(forward))``:
    the weight-gradient matmuls and no second activation backward.

Each invariant exists at two levels: a pure arithmetic ``check_*``
(takes costs, raises AssertionError — what the bench calls after
measuring once) and a compile-and-check ``assert_*`` (takes the model,
measures, delegates — what the tests and the CLI call). The ``assert_*``
level does compile HLO, so it is NOT trace-only; the CLI keeps it
behind an opt-in flag.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.engine import Engine
from repro.core.taps import DISABLED, ExampleLayout, NULL, PexSpec, Tap
from repro.roofline.hlo import compiled_cost

#: "the same program" modulo float accounting noise
EQ_TOL = 1e-6
#: Clip-plan headroom over the 1F + 1aB + 1wB budget
BUDGET_TOL = 0.02
#: Noise+GNS epsilon over the Clip plan alone (O(n_params) extras)
EPS_TOL = 0.25
#: a one-backward plan's share of a plain backward: the weight-gradient
#: half of its matmuls (a third where the backward recomputes a
#: rematerialized forward), with room for the per-example scaling
BOOK_SHARE = 0.6


def cost_of(fn, *args) -> Tuple[float, float]:
    """(flops, bytes) of ``jit(fn)(*args)`` from XLA's cost model."""
    return compiled_cost(jax.jit(fn).lower(*args).compile())


def grad_cost(loss_fn, params, batch,
              spec: Optional[PexSpec]) -> Tuple[float, float]:
    """Cost of grad-wrt-params of the total loss; ``spec=None`` runs
    the NULL tap (plain model), otherwise a live Tap whose accumulator
    gradient is never requested."""
    b = jax.tree_util.tree_leaves(batch)[0].shape[0]

    def total(p):
        if spec is None:
            lv, _ = loss_fn(p, batch, NULL)
        else:
            tap = Tap(spec, acc=ExampleLayout(spec.n_groups).init(b))
            lv, _ = loss_fn(p, batch, tap)
        return jnp.sum(lv)

    return cost_of(jax.grad(total), params)


# ---------------------------------------------------------------------------
# pure checks (cost arithmetic only — no compilation)
# ---------------------------------------------------------------------------

def check_empty_plan(f_empty: float, f_fwd: float, *,
                     tol: float = EQ_TOL) -> None:
    if f_fwd <= 0.0:
        return
    assert abs(f_empty - f_fwd) <= tol * f_fwd, (
        f"step([]) is not the plain forward: {f_empty} vs {f_fwd}")


def check_grads_plan(f_gonly: float, f_grad: float, *,
                     tol: float = EQ_TOL) -> None:
    assert f_gonly <= f_grad * (1 + tol), (
        f"step([Grads()]) exceeds plain value_and_grad: "
        f"{f_gonly} vs {f_grad}")


def backward_budget(f_norms: float, f_grad: float, f_fwd: float) -> float:
    """The one-forward flop budget for any norm-consuming plan: the
    norms pass already pays one tapped forward + one activation
    backward; a reweighted parameter backward may add at most
    ``cost(plain grad) − cost(plain forward)``."""
    return f_norms + (f_grad - f_fwd)


def check_backward_budget(f_plan: float, f_norms: float, f_grad: float,
                          f_fwd: float, *,
                          tol: float = BUDGET_TOL) -> None:
    budget = backward_budget(f_norms, f_grad, f_fwd)
    assert f_plan <= budget * (1 + tol), (
        f"plan exceeds the one-forward budget (a second forward crept "
        f"in?): {f_plan} vs budget {budget}")


def check_booked_budget(f_plan: float, f_norms: float, f_grad: float,
                        f_fwd: float, *, share: float = BOOK_SHARE) -> None:
    budget = f_norms + share * (f_grad - f_fwd)
    assert f_plan <= budget, (
        f"a one-backward plan costs more than the norms pass plus the "
        f"weight-gradient matmuls (a second backward crept in?): "
        f"{f_plan} vs budget {budget}")


def check_fused_epsilon(f_fused: float, f_base: float, *,
                        tol: float = EPS_TOL) -> None:
    assert f_fused <= f_base * (1 + tol), (
        f"extra consumers are not folding into the base plan: "
        f"{f_fused} vs {f_base}")


def check_dce(f_inst: float, b_inst: float, f_plain: float,
              b_plain: float, *, tol: float = EQ_TOL,
              exact: bool = False) -> None:
    """Instrumented-but-unread stat chains must be dead code. With
    ``exact`` the programs must match bidirectionally (DISABLED spec);
    otherwise the instrumented program may lower marginally cheaper
    (custom_vjp bwd rules emit slightly different HLO under remat) but
    never costlier."""
    if exact:
        assert abs(f_inst - f_plain) <= tol * max(f_plain, 1.0), (
            f"disabled taps changed the program: flops {f_inst} vs "
            f"{f_plain}")
        assert abs(b_inst - b_plain) <= tol * max(b_plain, 1.0), (
            f"disabled taps changed the program: bytes {b_inst} vs "
            f"{b_plain}")
    else:
        assert f_inst <= f_plain * (1 + tol), (
            f"unread stat work survived DCE: flops {f_inst} vs {f_plain}")
        assert b_inst <= b_plain * (1 + tol), (
            f"unread stat work survived DCE: bytes {b_inst} vs {b_plain}")


# ---------------------------------------------------------------------------
# compile-and-check analyzers (measure, then delegate)
# ---------------------------------------------------------------------------

def assert_disabled_spec_is_plain(loss_fn, params, batch, *,
                                  tol: float = EQ_TOL) -> None:
    """DISABLED taps compile to the plain model, flop- and byte-exact."""
    f_p, b_p = grad_cost(loss_fn, params, batch, None)
    f_o, b_o = grad_cost(loss_fn, params, batch, DISABLED)
    check_dce(f_o, b_o, f_p, b_p, tol=tol, exact=True)


def assert_unrequested_norms_dce(loss_fn, params, batch, *,
                                 spec: Optional[PexSpec] = None,
                                 tol: float = EQ_TOL) -> None:
    """Taps ENABLED, grad w.r.t. params only: every stat chain must be
    DCE-dead — no flop/byte cost over the plain model."""
    spec = spec if spec is not None else PexSpec(enabled=True,
                                                method="gram")
    f_p, b_p = grad_cost(loss_fn, params, batch, None)
    f_i, b_i = grad_cost(loss_fn, params, batch, spec)
    check_dce(f_i, b_i, f_p, b_p, tol=tol, exact=False)


def assert_empty_plan_is_plain(loss_fn, params, batch, *,
                               engine: Optional[Engine] = None,
                               tol: float = EQ_TOL) -> None:
    """``Engine.step(consumers=[])`` lowers to exactly the plain
    forward — plan analysis with nothing demanded never creates taps."""
    eng = engine if engine is not None else Engine(
        PexSpec(enabled=True, method="gram"))

    def plain_fwd(p):
        return jnp.sum(loss_fn(p, batch, NULL)[0])

    f_fwd, _ = cost_of(plain_fwd, params)
    f_empty, _ = cost_of(
        lambda p: eng.step(loss_fn, p, batch, []).loss, params)
    check_empty_plan(f_empty, f_fwd, tol=tol)


def assert_backward_budget(loss_fn, params, batch, consumers, *,
                           engine: Optional[Engine] = None,
                           tol: float = BUDGET_TOL) -> None:
    """A norm-consuming plan (Clip and friends) fits the one-forward
    budget: cost(norms pass) + (cost(plain grad) − cost(plain fwd));
    one that traced a single backward (``StepResult.backwards``) fits
    the booked budget too."""
    from repro import pex
    eng = engine if engine is not None else Engine(
        PexSpec(enabled=True, method="gram"), clip_norm=1.0)

    def plain_fwd(p):
        return jnp.sum(loss_fn(p, batch, NULL)[0])

    f_fwd, _ = cost_of(plain_fwd, params)
    f_grad, _ = cost_of(jax.value_and_grad(plain_fwd), params)
    f_norms, _ = cost_of(
        lambda p: eng.step(loss_fn, p, batch, [pex.Norms()]).sq_norms,
        params)
    traced = {}

    def plan_grads(p):
        res = eng.step(loss_fn, p, batch, list(consumers))
        traced["backwards"] = res.backwards
        return res.grads

    f_plan, _ = cost_of(plan_grads, params)
    check_backward_budget(f_plan, f_norms, f_grad, f_fwd, tol=tol)
    if traced["backwards"] == 1:
        check_booked_budget(f_plan, f_norms, f_grad, f_fwd)
