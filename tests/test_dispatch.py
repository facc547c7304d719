"""The two-sided (backend-aware) dense-stat dispatch.

``stat_dense(method="auto")`` must (a) price gram vs direct with the
cost model of the backend that will actually run the stat, and (b)
with ``use_pallas=True`` land on a *real Pallas kernel* in both
regimes — the triangular gram kernel for short S, the blocked HᵀZ̄
direct kernel for long S — never the lax.scan fallback.
"""
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import norms as N
from repro.kernels import ops, ref

RNG = np.random.default_rng(11)

GRAM_REGIME = dict(s=64, pi=512, po=512)     # s ≪ pi·po/(pi+po) = 256
DIRECT_REGIME = dict(s=1024, pi=256, po=256)  # s ≫ crossover


def _hz(b, s, pi, po):
    h = jnp.asarray(RNG.normal(size=(b, s, pi)), jnp.float32)
    z = jnp.asarray(RNG.normal(size=(b, s, po)), jnp.float32)
    return h, z


@pytest.mark.parametrize("use_pallas", [False, True])
def test_pick_method_regimes(use_pallas):
    assert N.pick_method(GRAM_REGIME["s"], GRAM_REGIME["pi"],
                         GRAM_REGIME["po"], use_pallas) == "gram"
    assert N.pick_method(DIRECT_REGIME["s"], DIRECT_REGIME["pi"],
                         DIRECT_REGIME["po"], use_pallas) == "direct"


def test_pallas_crossover_later_than_xla():
    """The triangular kernel halves gram's cost, so on the Pallas
    backend gram stays competitive to ~2× longer sequences."""
    xla = N.crossover_s(512, 512)
    plls = N.crossover_s(512, 512, use_pallas=True)
    assert xla == pytest.approx(512 * 512 / (512 + 512), rel=0.05)
    assert 1.2 * xla < plls <= 2.2 * xla


def test_pallas_cost_charges_padding():
    """A 1-wide p_out pads to a 128-lane chunk; the Pallas direct cost
    must reflect the padded shape, the XLA cost the logical one."""
    xla = N.dense_cost("direct", 256, 256, 1)
    plls = N.dense_cost("direct", 256, 256, 1, use_pallas=True)
    assert plls >= 100 * xla  # 1 → 128 lanes


def test_auto_dispatches_to_pallas_kernel_in_both_regimes():
    """use_pallas + auto must invoke ops.gram_norm in the gram regime
    and ops.direct_norm in the direct regime (no scan fallback)."""
    for regime, expect_called, expect_not in [
            (GRAM_REGIME, "gram_norm", "direct_norm"),
            (DIRECT_REGIME, "direct_norm", "gram_norm")]:
        h, z = _hz(2, regime["s"], regime["pi"], regime["po"])
        with mock.patch.object(ops, expect_called,
                               wraps=getattr(ops, expect_called)) as hit, \
             mock.patch.object(ops, expect_not,
                               wraps=getattr(ops, expect_not)) as miss:
            got = N.stat_dense(h, z, method="auto", use_pallas=True)
            assert hit.call_count == 1, regime
            assert miss.call_count == 0, regime
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(ref.gram_norm_ref(h, z)),
                                   rtol=1e-4)


@pytest.mark.parametrize("method", ["gram", "direct"])
@pytest.mark.parametrize("use_pallas", [False, True])
def test_stat_dense_parity_across_backends(method, use_pallas):
    h, z = _hz(2, 96, 160, 224)
    got = N.stat_dense(h, z, method=method, use_pallas=use_pallas)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.gram_norm_ref(h, z)),
                               rtol=1e-4)


def test_taps_auto_pallas_hits_direct_kernel():
    """End-to-end: a long-S dense tap with PexSpec(use_pallas, auto)
    reaches the Pallas direct kernel inside the custom_vjp backward,
    and the recovered norms match the hand-computed oracle."""
    from repro.core.engine import Engine
    from repro.core.taps import PexSpec

    b, s, pi, po = 2, 512, 32, 48  # crossover ≈ 19 ⇒ direct regime
    spec = PexSpec(enabled=True, method="auto", use_pallas=True)
    h = jnp.asarray(RNG.normal(size=(b, s, pi)), jnp.float32)
    w = jnp.asarray(RNG.normal(size=(pi, po)) / np.sqrt(pi), jnp.float32)

    def loss_fn(p, batch, tap):
        z = tap.dense(batch["h"], p["w"])
        return jnp.sum(jnp.square(z), axis=(1, 2)), {}

    with mock.patch.object(ops, "direct_norm",
                           wraps=ops.direct_norm) as hit:
        res = Engine(spec).value_and_norms(loss_fn, {"w": w}, {"h": h})
        assert hit.call_count >= 1

    # oracle: z̄ = 2z per example, s_j = ||h_jᵀ z̄_j||²_F
    z = np.asarray(h) @ np.asarray(w)
    want = np.stack([((np.asarray(h)[j].T @ (2 * z[j])) ** 2).sum()
                     for j in range(b)])
    np.testing.assert_allclose(np.asarray(jnp.sum(res.sq_norms, -1)), want,
                               rtol=1e-4)


def test_cost_analysis_exposes_flops():
    """compiled.cost_analysis() stays consumable for both kernels (on
    TPU it reflects the attached CostEstimate — the halved gram work;
    XLA:CPU counts the interpreter's grid loop body once, so here we
    only pin the plumbing and the model ratio)."""
    from repro.kernels import direct_norm as dn
    from repro.kernels import gram_norm as gn

    h, z = _hz(1, 256, 256, 256)

    def run_tri(h, z):
        return gn.gram_norm(h, z, tile_s=64, chunk_in=256, chunk_out=256,
                            triangular=True, interpret=True)

    def run_dir(h, z):
        return dn.direct_norm(h, z, tile_s=64, chunk_in=256, chunk_out=256,
                              interpret=True)

    for fn in (run_tri, run_dir):
        ca = jax.jit(fn).lower(h, z).compile().cost_analysis()
        assert ca.get("flops", 0) > 0

    full = gn.flop_estimate(1, 1024, 512, 512, triangular=False)
    tri = gn.flop_estimate(1, 1024, 512, 512, triangular=True)
    assert full / tri >= 1.7
