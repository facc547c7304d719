"""Mutation corpus for the pexcost traffic pass (DESIGN.md §13): plant
each of the four cost bugs the pass exists to catch — an extra
full-gradient HBM stream, a duplicated forward, a dropped-residual
second linearization, a silent f32 upcast of a bf16 gradient tree —
through the same seams the pipeline resolves at call time
(``plan.run_fused``, ``optim.adamw.update``), and prove each mutant is
detected through the real ``Engine.verify(cost=True)`` entry point by
its OWN finding code (clean separation), while the unmutated trace
stays green (test_pexcost.py's sweep)."""
import jax
import jax.numpy as jnp
import pytest

from repro import pex
from repro.core import plan as plan_mod
from repro.core.engine import Engine
from repro.core.taps import PexSpec
from repro.models import registry
from repro.optim import adamw

from tests.test_pexlint import abstract_setup

tree_map = jax.tree_util.tree_map


def _verify(loss_fn, params, batch, *, model="llama3.2-1b"):
    eng = Engine(PexSpec(enabled=True))
    return eng.verify(
        loss_fn, params, batch,
        [[pex.Clip(1.0), pex.Noise(0.1, jax.random.PRNGKey(0)),
          pex.GNS()]],
        allow=registry.untapped_allowlist(model), seq=8,
        deep=False, cost=True, model=model)


def _codes(rep):
    return {f.code for f in rep.findings}


def test_extra_gradient_stream_is_detected(monkeypatch):
    """A gradient-normalization pre-pass bolted onto the optimizer adds
    full-tree HBM streams beyond the structural expectation —
    redundant-hbm-stream must escalate to a hard ERROR (not the
    allowlisted 'expected today' report)."""
    _, loss_fn, params, batch = abstract_setup("llama3.2-1b")
    real_update = adamw.update

    def normalizing_update(cfg, state, p, grads):
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                          for g in jax.tree_util.tree_leaves(grads)))
        grads = tree_map(lambda g: g / (gn + 1e-6), grads)
        return real_update(cfg, state, p, grads)

    monkeypatch.setattr(adamw, "update", normalizing_update)
    rep = _verify(loss_fn, params, batch)
    assert not rep.ok
    assert "redundant-hbm-stream" in _codes(rep)
    (tr,) = rep.traffic
    assert tr.n_streams > tr.expected_streams


def test_duplicated_forward_is_detected(monkeypatch):
    """A plan layer that silently traces the forward twice (the classic
    pre-fusion clipped path) must trip duplicate-forward."""
    _, loss_fn, params, batch = abstract_setup("llama3.2-1b")
    real = plan_mod.run_fused

    def doubled(plan, acc_loss, params, batch, bs, layout, **kw):
        lv, aux, sq, grads, w, tw, cc, n = real(plan, acc_loss, params,
                                             batch, bs, layout, **kw)
        lv2, *_ = real(plan, acc_loss, params, batch, bs, layout, **kw)
        return lv + 0.0 * lv2, aux, sq, grads, w, tw, cc, n

    monkeypatch.setattr(plan_mod, "run_fused", doubled)
    rep = _verify(loss_fn, params, batch)
    assert not rep.ok
    assert "duplicate-forward" in _codes(rep)
    (tr,) = rep.traffic
    assert tr.forward_flops > 1.5 * tr.ref_forward_flops


def test_dropped_residual_sharing_is_detected(monkeypatch):
    """Taking the reweighted gradients from a SECOND independent
    linearization (instead of reusing the norms backward's residuals)
    must trip dead-residual."""
    _, loss_fn, params, batch = abstract_setup("llama3.2-1b")
    real = plan_mod.run_fused

    def relinearized(plan, acc_loss, params, batch, bs, layout, **kw):
        lv, aux, sq, _, w, tw, cc, n = real(plan, acc_loss, params,
                                         batch, bs, layout, **kw)
        _, _, _, grads, *_ = real(plan, acc_loss, params, batch, bs,
                                  layout, **kw)
        return lv, aux, sq, grads, w, tw, cc, n

    monkeypatch.setattr(plan_mod, "run_fused", relinearized)
    rep = _verify(loss_fn, params, batch)
    assert not rep.ok
    assert "dead-residual" in _codes(rep)
    (tr,) = rep.traffic
    assert tr.residual_sharing < 0.25


def test_silent_f32_upcast_is_detected(monkeypatch):
    """bf16 gradient trees silently materialized as f32 copies before
    the optimizer read must trip upcast-materialization — and ONLY it
    (the stream count stays at the structural expectation)."""
    _, loss_fn, params, batch = abstract_setup("llama3.2-1b")
    bf16_params = tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16)
        if s.dtype == jnp.float32 else s, params)
    real_update = adamw.update

    def upcasting_update(cfg, state, p, grads):
        g32 = tree_map(lambda g: g.astype(jnp.float32), grads)
        return real_update(cfg, state, p, g32)

    monkeypatch.setattr(adamw, "update", upcasting_update)
    rep = _verify(loss_fn, bf16_params, batch)
    assert not rep.ok
    assert "upcast-materialization" in _codes(rep)
    (tr,) = rep.traffic
    assert tr.n_streams == tr.expected_streams   # clean separation


def test_bf16_params_alone_stay_clean():
    """The upcast detector keys on a *materialized* copy, not on mixed
    precision itself: the unmutated bf16 step is finding-free."""
    _, loss_fn, params, batch = abstract_setup("llama3.2-1b")
    bf16_params = tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16)
        if s.dtype == jnp.float32 else s, params)
    rep = _verify(loss_fn, bf16_params, batch)
    assert rep.ok, rep.summary()
    assert not rep.findings


def test_mutants_fire_through_the_cli_gate(monkeypatch):
    """The same detection must reach the CLI exit code: a mutated
    pipeline under ``--cost --fail-on-error`` exits nonzero."""
    from repro.analysis.__main__ import main
    real = plan_mod.run_fused

    def doubled(plan, acc_loss, params, batch, bs, layout, **kw):
        lv, aux, sq, grads, w, tw, cc, n = real(plan, acc_loss, params,
                                             batch, bs, layout, **kw)
        lv2, *_ = real(plan, acc_loss, params, batch, bs, layout, **kw)
        return lv + 0.0 * lv2, aux, sq, grads, w, tw, cc, n

    monkeypatch.setattr(plan_mod, "run_fused", doubled)
    assert main(["--arch", "llama3.2-1b", "--fast", "--cost",
                 "--fail-on-error"]) == 1
