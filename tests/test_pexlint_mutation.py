"""Mutation corpus for the pexlint passes: break one privacy-critical
piece of the pipeline at a time and prove the static passes flag EVERY
mutant (100% detection) while the clean traces stay green (zero false
positives — test_pexlint.py and the cross-pass sweep below).

Coverage mutants programmatically delete one tap site at a time —
route the k-th instrumented op of the trace through its plain
counterpart — across all four model families. A deleted site sends the
weight's gradient down the ordinary autodiff path, so its taint
reaches the loss and the leaf classifies as untapped-but-trained; a
site inside a scan body covers all layers at once (the body traces
once), which only makes the mutant bigger, not harder to see.

Flow mutants (DESIGN.md §12) monkeypatch the plan layer's seams —
``run_fused`` / ``add_grad_noise`` / ``_compose_weights`` are
module-level names both ``plan.execute`` and ``dist.pex.plan_step``
resolve at call time — to build the classic DP-pipeline bugs:

  * noise added per shard inside the region (before the psum);
  * noise applied twice to the reduced gradient;
  * clip coefficients computed but never folded into the backward seed;
  * the booked gradient's contraction run without the clip coefficients;
  * the booked contraction scaling by the weights after the batch sum;
  * one PRNG key shared by every leaf's noise draw;
  * a per-example output psum'd over the data axis;
  * the data pipeline's seed ignoring the step cursor.
"""
import dataclasses
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro import pex
from repro.analysis import _jaxpr as _J
from repro.analysis import collectives as col
from repro.analysis import coverage as cov
from repro.analysis import determinism as det
from repro.analysis import privacy as priv
from repro.core import book
from repro.core import plan as plan_mod
from repro.core.provenance import mark_noise, mark_rng
from repro.core.taps import Tap
from repro.models import registry

from tests.test_pexlint import abstract_setup

# one representative per family: transformer (with MoE), rwkv6,
# zamba2, seamless (encoder-decoder)
CORPUS = ["phi3.5-moe", "rwkv6-3b", "zamba2-7b", "seamless-m4t-medium"]


class MutantTap(Tap):
    """Tap that drops its ``kill``-th op call (trace order) to the
    uninstrumented path; ``kill=-1`` counts sites without mutating."""
    __slots__ = ("kill", "count")

    def __init__(self, spec, acc=None, layout=None, kill=-1):
        super().__init__(spec, acc, layout)
        self.kill = kill
        self.count = 0

    def _dead(self) -> bool:
        k = self.count
        self.count += 1
        return k == self.kill

    def dense(self, h, w, **kw):
        if self._dead():
            return jnp.einsum("...i,io->...o", h, w)
        return super().dense(h, w, **kw)

    def bias_add(self, x, b, **kw):
        if self._dead():
            return x + b
        return super().bias_add(x, b, **kw)

    def scale(self, h, g, **kw):
        if self._dead():
            return h * g
        return super().scale(h, g, **kw)

    def embedding(self, table, ids, **kw):
        if self._dead():
            return jnp.take(table, ids, axis=0)
        return super().embedding(table, ids, **kw)

    def dense_expert(self, x, w, seg, tok=None, **kw):
        if self._dead():
            return jnp.einsum("ecd,edf->ecf", x, w)
        return super().dense_expert(x, w, seg, tok, **kw)

    def dense_expert_grouped(self, x, w, seg, bg, tok=None, **kw):
        if self._dead():
            return jnp.einsum("gecd,gedf->gecf", x, w) \
                if w.ndim == 4 else jnp.einsum("gecd,edf->gecf", x, w)
        return super().dense_expert_grouped(x, w, seg, bg, tok, **kw)


def _count_sites(loss_fn, params, batch) -> int:
    holder = {}

    def factory(spec, acc=None, layout=None):
        tap = MutantTap(spec, acc, layout, kill=-1)
        holder["tap"] = tap
        return tap

    cov.trace_coverage(loss_fn, params, batch, tap_factory=factory)
    return holder["tap"].count


@pytest.mark.parametrize("arch_id", CORPUS)
def test_every_deleted_tap_site_is_flagged(arch_id):
    _, loss_fn, params, batch = abstract_setup(arch_id)
    allow = registry.untapped_allowlist(arch_id)
    n = _count_sites(loss_fn, params, batch)
    assert n > 0
    missed = []
    for k in range(n):
        rep = cov.trace_coverage(
            loss_fn, params, batch, allow=allow,
            tap_factory=lambda spec, acc=None, layout=None:
                MutantTap(spec, acc, layout, kill=k))
        if rep.ok:
            missed.append(k)
    assert not missed, (
        f"{arch_id}: deleting tap call(s) {missed} of {n} went "
        f"undetected by the coverage pass")


def test_mutant_errors_name_the_right_leaves():
    """Killing the first dense site must flag exactly the weight(s)
    routed through it — not unrelated leaves (precision, not just
    recall)."""
    _, loss_fn, params, batch = abstract_setup("llama3.2-1b")
    clean = cov.trace_coverage(loss_fn, params, batch)
    assert clean.ok
    rep = cov.trace_coverage(
        loss_fn, params, batch,
        tap_factory=lambda spec, acc=None, layout=None:
            MutantTap(spec, acc, layout, kill=0))
    assert not rep.ok
    # every flagged leaf was TAPPED in the clean trace
    clean_by_path = {str(l.path): l.status for l in clean.leaves}
    for leaf in rep.errors:
        assert clean_by_path[str(leaf.path)] == cov.TAPPED


# ---------------------------------------------------------------------------
# flow mutants — privacy / collectives / determinism (DESIGN.md §12)
# ---------------------------------------------------------------------------

KEY = jax.random.PRNGKey(0)
DP_CONSUMERS = [pex.Clip(1.0), pex.Noise(0.1, KEY)]


def _one_device_mesh():
    return Mesh(np.array(jax.devices()[:1]), ("data",))


def _dp_trace(mesh=None):
    _, loss_fn, params, batch = abstract_setup("llama3.2-1b")
    return _J.trace_step(loss_fn, params, batch, DP_CONSUMERS,
                         mesh=mesh, batch_size=3)


def _codes(report):
    return {f.code for f in report.findings}


def test_mutant_noise_before_psum(monkeypatch):
    """Noise applied per shard inside the region — each shard's draw
    adds up, inflating the variance by the shard count."""
    real_fused = plan_mod.run_fused
    real_noise = plan_mod.add_grad_noise

    def in_region(sub, acc_loss, p, b, bs, layout, *, loss_weights=None):
        lv, aux, sq, grads, w, tw, cc, n = real_fused(
            sub, acc_loss, p, b, bs, layout, loss_weights=loss_weights)
        if sub.noise is not None and grads is not None:
            scale = sub.noise.scale if sub.noise.scale is not None \
                else sub.clip.clip_norm
            grads = real_noise(grads, sub.noise.noise_std, scale,
                               sub.noise.rng)
        return lv, aux, sq, grads, w, tw, cc, n

    monkeypatch.setattr(plan_mod, "run_fused", in_region)
    monkeypatch.setattr(plan_mod, "add_grad_noise",
                        lambda g, *a, **kw: g)
    rep = priv.analyze_trace(_dp_trace(mesh=_one_device_mesh()))
    assert not rep.ok
    assert "noise-before-psum" in _codes(rep)


def test_mutant_double_noise(monkeypatch):
    """The noise step applied twice to the reduced gradient — double
    the privacy budget's variance, silently."""
    real_noise = plan_mod.add_grad_noise

    def twice(grads, noise_std, clip_norm, rng):
        once = real_noise(grads, noise_std, clip_norm, rng)
        return real_noise(once, noise_std, clip_norm,
                          jax.random.fold_in(rng, 1))

    monkeypatch.setattr(plan_mod, "add_grad_noise", twice)
    rep = priv.analyze_trace(_dp_trace())
    assert not rep.ok
    assert "double-noise" in _codes(rep)


def test_mutant_unclipped_leaf(monkeypatch):
    """Clip coefficients computed (and returned!) but never folded
    into the backward seed — the result LOOKS clipped while every
    gradient is the raw sum."""
    real_compose = plan_mod._compose_weights

    def drop_fold(plan, sq_norms, loss_weights, extra_weights=None):
        _, tw, cc = real_compose(plan, sq_norms, loss_weights,
                                 extra_weights)
        unfolded = real_compose(
            dataclasses.replace(plan, clip=None), sq_norms,
            loss_weights, extra_weights)[0]
        return unfolded, tw, cc

    monkeypatch.setattr(plan_mod, "_compose_weights", drop_fold)
    rep = priv.analyze_trace(_dp_trace())
    assert not rep.ok
    assert "unclipped-leaf" in _codes(rep)


def test_mutant_book_contraction_drops_clip(monkeypatch):
    """The booked form (``core.book``) contracting the norms backward's
    per-example entries with unit weights — the clip coefficients
    dropped — sums every example unclipped."""
    real = plan_mod.book_contraction

    def unweighted(acc_loss, params, batch, level, entries, weights):
        return real(acc_loss, params, batch, level, entries,
                    jnp.ones(weights.shape, weights.dtype))

    monkeypatch.setattr(plan_mod, "book_contraction", unweighted)
    rep = priv.analyze_trace(_dp_trace())
    assert any(m.tag == "book" for m in rep.marks)
    assert not rep.ok
    assert "unclipped-leaf" in _codes(rep)


def test_mutant_book_weights_after_the_batch_sum(monkeypatch):
    """The booked contraction summing each site's entries over the
    batch first and scaling the sum by the mean weight — the clip
    coefficients applied after the batch sum, not per example."""
    real = book.contract

    def late(kind, entry, w, shape, dtype):
        g = real(kind, entry, jnp.ones_like(w), shape, dtype)
        return (g * jnp.mean(w)).astype(dtype)

    clean = priv.analyze_trace(_dp_trace())
    assert clean.ok and any(m.tag == "book_fold" for m in clean.marks)
    monkeypatch.setattr(book, "contract", late)
    rep = priv.analyze_trace(_dp_trace())
    assert not any(m.tag == "book_fold" and m.token for m in rep.marks)
    assert not rep.ok
    assert "unclipped-leaf" in _codes(rep)


def test_mutant_reused_key(monkeypatch):
    """Every leaf's noise drawn from the SAME key — perfectly
    correlated noise across leaves, not independent Gaussians."""
    def shared_key(grads, noise_std, clip_norm, rng):
        flat, tree = jax.tree_util.tree_flatten(grads)
        out = []
        for i, g in enumerate(flat):
            k = mark_rng(rng, purpose="noise", index=i)
            sample = noise_std * clip_norm * \
                jax.random.normal(k, g.shape, jnp.float32).astype(g.dtype)
            out.append(g + mark_noise(sample, noise_std=noise_std,
                                      scale=clip_norm, leaf=i))
        return jax.tree_util.tree_unflatten(tree, out)

    monkeypatch.setattr(plan_mod, "add_grad_noise", shared_key)
    rep = priv.analyze_trace(_dp_trace())
    assert not rep.ok
    assert "key-reuse" in _codes(rep)


def test_mutant_per_example_psum(monkeypatch):
    """A per-example output reduced over the data axis — every shard's
    loss vector silently becomes the cross-shard sum."""
    real_fused = plan_mod.run_fused

    def psum_lv(sub, acc_loss, p, b, bs, layout, *, loss_weights=None):
        lv, aux, sq, grads, w, tw, cc, n = real_fused(
            sub, acc_loss, p, b, bs, layout, loss_weights=loss_weights)
        return jax.lax.psum(lv, "data"), aux, sq, grads, w, tw, cc, n

    monkeypatch.setattr(plan_mod, "run_fused", psum_lv)
    rep = col.analyze_trace(_dp_trace(mesh=_one_device_mesh()))
    assert not rep.ok
    assert "per-example-psum" in _codes(rep)


def test_mutant_seed_drift():
    """The real pipeline source with ``step`` dropped from the seed
    tuple — every step replays step-0 data after a restore."""
    import repro.data.pipeline as pipeline
    src = inspect.getsource(pipeline)
    assert "(cfg.seed, step, self.host_id, 0xDA7A)" in src
    mutated = src.replace("(cfg.seed, step, self.host_id, 0xDA7A)",
                          "(cfg.seed, self.host_id, 0xDA7A)")
    findings = det.check_source(mutated, "data/pipeline.py")
    assert "seed-ignores-step" in {f.code for f in findings}
    # and the unmutated source is clean
    assert not det.check_source(src, "data/pipeline.py")


# ---------------------------------------------------------------------------
# cross-pass clean sweep — zero false positives on every registered
# arch × granularity × consumer set, through every pass at once
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch_id", sorted(registry.ARCHS))
def test_clean_sweep_all_passes(arch_id):
    from repro.analysis.__main__ import lint_arch
    findings, _costs = lint_arch(arch_id, backend="tpu", production=True,
                                 key=KEY, mesh=_one_device_mesh(), deep=True)
    assert not findings, "\n".join(f.render() for f in findings)
