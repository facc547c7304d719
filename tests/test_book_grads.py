"""The booked clipped gradient (``core/book.py``): the norms backward
keeps each tap site's ``(H, Z̄)`` and the reweighted gradient is their
weighted contraction, with no second backward.

Where the booked form applies it must equal the two-backward form it
replaces (``book.plan`` monkeypatched to refuse gives the latter), on
the local and the mesh path and through ``Trainer.run_step``, and the
paper's §6 one-pass oracle; ``StepResult.backwards`` and the trainer's
``pex_backwards`` say 1 there and 2 on every path that keeps the second
backward.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro import pex
from repro.core import book, clipping
from repro.core.engine import Engine
from repro.core.taps import PexSpec
from repro.data.pipeline import DataConfig
from repro.models import registry
from repro.optim import adamw
from repro.train.trainer import TrainConfig, Trainer

from tests.helpers import smoke_setup

B, S = 4, 8


def _qwen(**spec):
    aspec, cfg, _, params, batch = smoke_setup("qwen2-7b", B=B, S=S)
    return registry.make_loss_fn_v2(aspec, cfg), params, batch, \
        Engine(PexSpec(**spec))


def _two_backwards(monkeypatch):
    monkeypatch.setattr(book, "plan", lambda *a, **k: None)


def _assert_trees_close(got, want, rtol=2e-5):
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree_util.tree_leaves(want)):
        g, w = np.asarray(g), np.asarray(w)
        np.testing.assert_allclose(
            g, w, rtol=rtol, atol=rtol * float(np.max(np.abs(w))),
            err_msg=jax.tree_util.keystr(path))


PLANS = {
    "clip": ([pex.Norms(), pex.Clip(0.05)], None),
    "clip_loss_weights": ([pex.Clip(0.05)],
                          jnp.asarray([1.0, 0.0, 2.5, 0.5])),
    "norms_loss_weights": ([pex.Norms(), pex.Grads()],
                           jnp.asarray([0.5, 1.0, 0.0, 3.0])),
}


@pytest.mark.parametrize("case", sorted(PLANS))
@pytest.mark.parametrize("meshed", [False, True])
def test_booked_gradient_equals_two_backwards(case, meshed, monkeypatch):
    consumers, lw = PLANS[case]
    loss_fn, params, batch, _ = _qwen()
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",)) if meshed \
        else None
    eng = Engine(PexSpec(), mesh=mesh)
    booked = eng.step(loss_fn, params, batch, consumers, loss_weights=lw)
    _two_backwards(monkeypatch)
    ref = eng.step(loss_fn, params, batch, consumers, loss_weights=lw)
    assert (booked.backwards, ref.backwards) == (1, 2)
    np.testing.assert_allclose(booked.sq_norms, ref.sq_norms, rtol=1e-6)
    np.testing.assert_allclose(booked.weights, ref.weights, rtol=1e-6)
    _assert_trees_close(booked.grads, ref.grads)


def test_mesh_path_books_per_shard_like_the_local_path():
    loss_fn, params, batch, eng = _qwen()
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    cons = [pex.Norms(), pex.Clip(0.05)]
    local = eng.step(loss_fn, params, batch, cons)
    meshed = Engine(PexSpec(), mesh=mesh).step(loss_fn, params, batch, cons)
    assert local.backwards == meshed.backwards == 1
    _assert_trees_close(meshed.grads, local.grads, rtol=1e-6)


def _trainer(consumers, params):
    aspec = registry.get("qwen2-7b")
    cfg = aspec.smoke()
    return Trainer(registry.make_loss_fn_v2(aspec, cfg),
                   jax.tree_util.tree_map(jnp.copy, params), PexSpec(),
                   adamw.AdamWConfig(lr=1e-3),
                   TrainConfig(consumers=consumers, log_every=0, seed=3),
                   DataConfig(vocab=cfg.vocab, seq=S, global_batch=B))


def test_dpsgd_trainer_step_equals_two_backwards(monkeypatch):
    """Norms + Clip + Noise through ``Trainer.run_step``: the same noise
    draw on both sides, so AdamW's first moment (linear in the noised,
    globally clipped gradient) must agree."""
    _, params, batch, _ = _qwen()
    cons = (pex.Norms(), pex.Clip(0.05), pex.Noise(1.0))
    t = _trainer(cons, params)
    m = t.run_step(batch)
    _two_backwards(monkeypatch)
    r = _trainer(cons, params)
    m_ref = r.run_step(batch)
    assert (m["pex_backwards"], m_ref["pex_backwards"]) == (1, 2)
    assert m["loss"] == pytest.approx(m_ref["loss"], rel=1e-6)
    assert m["norm_max"] == pytest.approx(m_ref["norm_max"], rel=1e-6)
    _assert_trees_close(t.opt_state.mu, r.opt_state.mu)


def test_booked_gradient_matches_the_onepass_oracle():
    """Where both apply — dense layers over a sequence — the booked
    contraction equals ``clipping.onepass_clipped_weight_grads_seq``."""
    rng = np.random.default_rng(3)
    D, H = 8, 10
    params = {"w1": jnp.asarray(rng.normal(size=(D, H)), jnp.float32) * .4,
              "w2": jnp.asarray(rng.normal(size=(H, D)), jnp.float32) * .4}
    batch = {"x": jnp.asarray(rng.normal(size=(B, S, D)), jnp.float32),
             "y": jnp.asarray(rng.normal(size=(B, S, D)), jnp.float32)}

    def forward(p, tp, b):
        z1 = b["x"] @ p["w1"] + tp["w1"]
        h1 = jnp.tanh(z1)
        z2 = h1 @ p["w2"] + tp["w2"]
        return (jnp.sum(jnp.square(z2 - b["y"]), axis=(1, 2)),
                {"w1": b["x"], "w2": h1})

    def loss_fn(p, b, tap):
        h1 = jnp.tanh(tap.dense(b["x"], p["w1"]))
        z2 = tap.dense(h1, p["w2"])
        return jnp.sum(jnp.square(z2 - b["y"]), axis=(1, 2)), {}

    _, sq, wbar = clipping.onepass_clipped_weight_grads_seq(
        forward, params, batch, {"w1": (B, S, H), "w2": (B, S, D)},
        clip_norm=0.7)
    res = Engine(PexSpec()).step(loss_fn, params, batch, [pex.Clip(0.7)])
    assert res.backwards == 1
    np.testing.assert_allclose(res.sq_norms[:, 0], sq, rtol=1e-5)
    _assert_trees_close(res.grads, wbar, rtol=1e-5)


# ---------------------------------------------------------------------------
# where the second backward stays
# ---------------------------------------------------------------------------

def _batched_loss(p, b, tap):
    """Per-example weights: the multi-tenant ``dense_batched`` tap."""
    w = jnp.take(p["adapters"], b["tenant"], axis=0)
    z = tap.dense_batched(b["x"], w)
    return jnp.sum(jnp.square(z), axis=(1, 2)), {}


def _fallback_case(name):
    cons = [pex.Norms(), pex.Clip(0.05)]
    if name == "token_clip":
        loss_fn, params, batch, _ = _qwen()
        return (Engine(PexSpec(), granularity="token"), loss_fn, params,
                batch, [pex.Clip(0.05, granularity="token"), pex.Grads()])
    if name == "untapped_head":
        loss_fn, params, batch, eng = _qwen(tap_head=False)
        return eng, loss_fn, params, batch, cons
    if name == "dense_batched":
        rng = np.random.default_rng(0)
        params = {"adapters": jnp.asarray(rng.normal(size=(3, 6, 5)),
                                          jnp.float32)}
        batch = {"x": jnp.asarray(rng.normal(size=(B, S, 6)), jnp.float32),
                 "tenant": jnp.asarray([0, 2, 1, 2])}
        return Engine(PexSpec()), _batched_loss, params, batch, cons
    aspec, cfg, _, params, batch = smoke_setup(name, B=B, S=S)
    return (Engine(PexSpec()), registry.make_loss_fn_v2(aspec, cfg), params,
            batch, cons)


FALLBACKS = ["token_clip", "phi3.5-moe", "dense_batched", "rwkv6-3b",
             "zamba2-7b", "untapped_head"]


@pytest.mark.parametrize("name", FALLBACKS)
def test_paths_without_a_booked_form_keep_two_backwards(name):
    eng, loss_fn, params, batch, cons = _fallback_case(name)
    res = eng.step(loss_fn, params, batch, cons)
    assert res.backwards == 2


def test_a_book_over_the_memory_rule_keeps_two_backwards(monkeypatch):
    loss_fn, params, batch, eng = _qwen()
    cons = [pex.Norms(), pex.Clip(0.05)]
    assert eng.step(loss_fn, params, batch, cons).backwards == 1
    eng._book_room = 0              # a caller's measured room
    assert eng.step(loss_fn, params, batch, cons).backwards == 2
    monkeypatch.setattr(book, "bytes_limit", lambda: 1 << 10)
    eng._book_room = None           # the device rule: half of 1 KiB
    assert eng.step(loss_fn, params, batch, cons).backwards == 2
    t = _trainer(tuple(cons), params)
    assert t.run_step(batch)["pex_backwards"] == 2


def test_the_memory_rule(monkeypatch):
    assert book.bytes_limit() is None and book.default_room() is None
    monkeypatch.setattr(book, "bytes_limit", lambda: 1001)
    assert book.default_room() == 500
    assert [book.nbytes(lv) for lv in (
        (book.Site("bias_add", (((4, 3), jnp.float32),)),),
        (book.Scan(2, (book.Site("dense", (((4, 5), jnp.bfloat16),
                                           ((4, 7), jnp.bfloat16))),)),),
    )] == [48, 2 * (40 + 56)]


def test_a_trainer_step_that_would_not_fit_falls_back(monkeypatch):
    """The trainer checks its compiled step against the device: a
    booked step that needs more than ``bytes_limit`` (here one byte
    more than it compiles to) is built again with the second backward,
    whose step then runs; the book itself is within the device rule."""
    _, params, batch, _ = _qwen()
    cons = (pex.Norms(), pex.Clip(0.05), pex.Noise(1.0))
    t = _trainer(cons, params)
    args = (t.params, t.opt_state, t.err, batch, t.rng)
    need = book.compiled_bytes(t._step_fn.lower(*args).compile())
    assert t._backwards[False] == 1
    monkeypatch.setattr(book, "bytes_limit", lambda: need - 1)
    loss_fn, _, _, eng = _qwen()
    assert eng.step(loss_fn, params, batch,
                    [pex.Norms(), pex.Clip(0.05)]).backwards == 1
    t = _trainer(cons, params)
    m = t.run_step(batch)
    assert m["pex_backwards"] == 2 and t.engine._book_room == 0
    monkeypatch.setattr(book, "bytes_limit", lambda: None)
    r = _trainer(cons, params)
    assert r.run_step(batch)["pex_backwards"] == 1
    _assert_trees_close(t.opt_state.mu, r.opt_state.mu)


def test_shared_inputs_are_booked_once():
    """q/k/v and gate/up read one H each: their later sites book Z̄
    alone."""
    aspec, cfg, _, params, batch = smoke_setup("qwen2-7b", B=B, S=S)
    loss_fn = registry.make_loss_fn_v2(aspec, cfg)
    eng = Engine(PexSpec())
    acc_loss = eng._adapt(loss_fn, pex.ExampleLayout(1))
    level = book.plan(acc_loss, params, batch,
                      pex.ExampleLayout(1).init(B))
    (scan,) = [x for x in level if isinstance(x, book.Scan)]
    dense = [s for s in scan.sites if s.kind == "dense"]
    assert [s.h_from is not None for s in dense] == \
        [False, True, True, False, False, True, False]
    assert [len(s.avals) for s in dense] == [2, 1, 1, 2, 2, 1, 2]


def test_plain_and_folded_plans_run_one_backward():
    loss_fn, params, batch, eng = _qwen()
    assert eng.step(loss_fn, params, batch, []).backwards == 0
    assert eng.step(loss_fn, params, batch, [pex.Grads()]).backwards == 1
    assert eng.step(loss_fn, params, batch,
                    [pex.Norms(), pex.Grads()]).backwards == 1
    assert eng.step(loss_fn, params, batch, [pex.Norms()]).backwards == 1
