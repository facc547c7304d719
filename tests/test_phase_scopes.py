"""The step's phase scopes and ``Trainer.run_step``'s host spans.

The plan, the taps and AdamW mark their parts of the step with
``jax.named_scope`` (``pex.forward``, ``pex.norms_backward``,
``pex.stats``, ``pex.weight_backward``, ``pex.noise``,
``optim.adamw``); the scopes reach the compiled HLO's ``op_name``
metadata and change nothing else. ``run_step`` writes its host parts
as profiler spans (``trainer.*``), in the order it runs them.
"""
import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import plan as pex
from repro.core.taps import PexSpec, Tap
from repro.data.pipeline import DataConfig
from repro.optim import adamw
from repro.train.trainer import TrainConfig, Trainer

B, S, V, D, H = 4, 8, 16, 8, 12

PLANS = {
    "dpsgd": (pex.Norms(), pex.Clip(1.0), pex.Noise(1.0)),
    "plain": (pex.Grads(),),
}
ALL_SCOPES = {"pex.forward", "pex.norms_backward", "pex.stats",
              "pex.weight_backward", "pex.noise", "optim.adamw"}
PLAIN_SCOPES = {"pex.forward", "pex.weight_backward", "optim.adamw"}


def _params(seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32) * .3
    return {"emb": f(V, D), "w1": f(D, H), "b1": f(H), "g": f(H) + 1.0,
            "w2": f(H, V)}


def _batch(seed=1):
    rng = np.random.default_rng(seed)
    return {"ids": jnp.asarray(rng.integers(0, V, size=(B, S))),
            "labels": jnp.asarray(rng.integers(0, V, size=(B, S)))}


def _loss(p, b, tap):
    h = tap.embedding(p["emb"], b["ids"])
    z = tap.bias_add(tap.dense(h, p["w1"]), p["b1"])
    h = tap.scale(jax.nn.gelu(jnp.cumsum(z, axis=1)), p["g"])
    logp = jax.nn.log_softmax(tap.dense(h, p["w2"]))
    ll = jnp.take_along_axis(logp, b["labels"][..., None], -1)[..., 0]
    return -jnp.sum(ll, axis=-1), {}


def _trainer(plan):
    return Trainer(_loss, _params(), PexSpec(), adamw.AdamWConfig(lr=1e-3),
                   TrainConfig(consumers=PLANS[plan], log_every=0),
                   DataConfig(vocab=V, seq=S, global_batch=B))


def _op_names(hlo: str) -> set:
    return set(re.findall(r'op_name="([^"]*)"', hlo))


#: a scope as JAX writes it into ``op_name``: bare or wrapped in the
#: transformations it ran under (``jvp(pex.stats)``); a ``transpose``
#: wrapper marks the backward of the scope's operations
SCOPE = re.compile(r"(?:(?!transpose\()\w+\()*((?:pex|optim)\.\w+)\)*")


def _scopes(hlo: str) -> set:
    return {m.group(1) for name in _op_names(hlo) for c in name.split("/")
            for m in [SCOPE.fullmatch(c)] if m}


def _strip(hlo: str) -> str:
    """The HLO text without its debug metadata: each ``metadata={...}``
    and the stack-frame tables before the first computation."""
    out, skip = [], False
    for line in hlo.splitlines():
        if line in ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames"):
            skip = True
        elif skip and re.match(r"^(ENTRY )?%", line):
            skip = False
        if not skip:
            out.append(re.sub(r",? metadata=\{[^}]*\}", "", line))
    return "\n".join(out)


@pytest.mark.parametrize("plan", sorted(PLANS))
def test_the_compiled_step_carries_the_plans_scopes(plan):
    t = _trainer(plan)
    hlo = t.lower_step(_batch()).compile().as_text()
    assert hlo.startswith("HloModule jit_step_fn")
    assert _scopes(hlo) == (ALL_SCOPES if plan == "dpsgd"
                            else PLAIN_SCOPES)
    assert t.step == 0 and t.metrics == []   # lowering runs nothing


@pytest.mark.parametrize("plan", sorted(PLANS))
def test_scopes_change_nothing_but_metadata(plan, monkeypatch):
    batch = _batch()
    scoped = _trainer(plan).lower_step(batch).compile().as_text()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = _trainer(plan).lower_step(batch).compile().as_text()
    assert not _scopes(bare)
    assert _strip(scoped) == _strip(bare)


def test_the_sharded_clipped_step_scopes_its_own_noise():
    from jax.sharding import Mesh
    from repro.dist import pex as dpex
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))

    def acc_loss(p, acc, b):
        tap = Tap(PexSpec(), acc=acc)
        lv, aux = _loss(p, b, tap)
        return lv, tap.carry(), aux

    def step(params, batch):
        return dpex.clipped_value_and_grads(
            acc_loss, params, batch, PexSpec(), B, 1.0, noise_std=1.0,
            noise_rng=jax.random.PRNGKey(0), mesh=mesh).grads

    hlo = jax.jit(step).lower(_params(), _batch()).compile().as_text()
    assert "pex.noise" in _scopes(hlo)


def _host_spans(path) -> list:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    out = []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                           for e in line.events
                           if e.name == "step" or
                           e.name.startswith("trainer."))
    return sorted(out, key=lambda s: s[1])


def test_run_step_writes_its_host_spans_in_order(tmp_path):
    t = _trainer("dpsgd")
    batch = _batch()
    t.run_step(batch)                        # compiles
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(3):
            with jax.profiler.TraceAnnotation("step"):
                m = t.run_step(batch)
    finally:
        jax.profiler.stop_trace()
    assert m["time_s"] > 0 and "norm_mean" in m
    spans = _host_spans(next(tmp_path.rglob("*.xplane.pb")))
    steps = [s for s in spans if s[0] == "step"]
    assert len(steps) == 3
    for _, lo, hi in steps:
        inside = [n for n, s, e in spans
                  if n != "step" and s >= lo and e <= hi]
        assert inside == ["trainer.dispatch", "trainer.fetch_loss",
                          "trainer.finite_check", "trainer.norm_stats"]
