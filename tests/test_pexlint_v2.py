"""pexlint v2 unit tests: the flow passes' report shapes, the CLI's
exit-code and JSON contracts, and the allowlist staleness check.

The mutation corpus (test_pexlint_mutation.py) proves detection; this
file pins the *interfaces* — a warnings-only run must exit 0 under
--fail-on-error (warnings are advisory), --json must be parseable and
carry every finding, stale allowlist entries must warn without
failing, and the new passes must stay trace-only.
"""
import json
import types

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from repro import pex
from repro.analysis import _jaxpr as _J
from repro.analysis import collectives as col
from repro.analysis import coverage as cov
from repro.analysis import determinism as det
from repro.analysis import privacy as priv
from repro.analysis.__main__ import main, resolve_exit
from repro.core import plan as plan_mod
from repro.models import registry

from tests.test_pexlint import abstract_setup

KEY = jax.random.PRNGKey(0)


def _mesh():
    return Mesh(np.array(jax.devices()[:1]), ("data",))


def _dp_trace(mesh=None, consumers=None):
    _, loss_fn, params, batch = abstract_setup("llama3.2-1b")
    cs = consumers if consumers is not None \
        else [pex.Clip(1.0), pex.Noise(0.1, KEY), pex.GNS()]
    return _J.trace_step(loss_fn, params, batch, cs, mesh=mesh,
                         batch_size=3)


# ---------------------------------------------------------------------------
# exit codes (the satellite fix: warnings must not fail --fail-on-error)
# ---------------------------------------------------------------------------

def test_resolve_exit_matrix():
    # (n_err, n_warn, fail_on_error, fail_on_warn) -> exit
    assert resolve_exit(0, 0, True, False) == 0
    assert resolve_exit(0, 0, False, False) == 0
    assert resolve_exit(3, 0, True, False) == 1
    assert resolve_exit(3, 0, False, False) == 0
    # THE fix: warnings-only exits 0 under --fail-on-error ...
    assert resolve_exit(0, 5, True, False) == 0
    # ... and nonzero only under --fail-on-warn
    assert resolve_exit(0, 5, False, True) == 1
    assert resolve_exit(0, 5, True, True) == 1
    assert resolve_exit(3, 5, False, True) == 1


def test_warnings_only_run_exit_codes(monkeypatch):
    """An unregistered allowlist key is a WARNING: green under
    --fail-on-error, red under --fail-on-warn."""
    monkeypatch.setitem(registry.UNTAPPED_ALLOWLIST,
                        "no-such-arch", ("bogus",))
    args = ["--arch", "llama3.2-1b", "--fast"]
    assert main(args + ["--fail-on-error"]) == 0
    assert main(args + ["--fail-on-warn"]) == 1


def test_json_report(capsys):
    assert main(["--arch", "llama3.2-1b", "--json",
                 "--fail-on-error"]) == 0
    out = capsys.readouterr()
    doc = json.loads(out.out)
    assert doc["archs"] == ["llama3.2-1b"]
    assert doc["errors"] == 0
    assert isinstance(doc["findings"], list)
    for f in doc["findings"]:
        assert {"pass", "severity", "code", "message"} <= set(f)
    # human status lines moved off stdout
    assert "pexlint:" in out.err


# ---------------------------------------------------------------------------
# allowlist staleness (satellite: stale entries warn, never fail)
# ---------------------------------------------------------------------------

def test_stale_allow_entry_warns_but_passes():
    _, loss_fn, params, batch = abstract_setup("llama3.2-1b")
    rep = cov.trace_coverage(loss_fn, params, batch,
                             allow=("no/such/param",))
    assert rep.ok
    assert rep.stale_allow == ("no/such/param",)
    assert "WARNING" in rep.summary()


def test_live_allow_entries_are_not_stale():
    arch = sorted(registry.UNTAPPED_ALLOWLIST)[0]
    _, loss_fn, params, batch = abstract_setup(arch)
    rep = cov.trace_coverage(loss_fn, params, batch,
                             allow=registry.untapped_allowlist(arch))
    assert rep.ok
    assert rep.stale_allow == ()


# ---------------------------------------------------------------------------
# privacy report internals
# ---------------------------------------------------------------------------

def test_privacy_report_shape():
    rep = priv.analyze_trace(_dp_trace())
    assert rep.ok, rep.summary()
    n_leaves = len(rep.leaves)
    assert n_leaves > 0
    by_tag = {}
    for m in rep.marks:
        by_tag.setdefault(m.tag, []).append(m)
    # one clip marker; one noise + one rng_use marker per leaf
    assert len(by_tag["clip_coef"]) == 1
    assert len(by_tag["noise"]) == n_leaves
    assert len(by_tag["rng_use"]) == n_leaves
    # exactly-once: each leaf carries exactly one noise token
    for leaf in rep.leaves:
        assert len(leaf.noise_tokens) == 1
    assert "privacy:" in rep.summary()


def test_privacy_clean_without_noise():
    rep = priv.analyze_trace(_dp_trace(consumers=[pex.Clip(1.0)]))
    assert rep.ok, rep.summary()
    assert all(m.tag != "noise" for m in rep.marks)


# ---------------------------------------------------------------------------
# collectives: region classification and the DP×TP schedule
# ---------------------------------------------------------------------------

def test_collectives_region_shape():
    rep = col.analyze_trace(_dp_trace(mesh=_mesh()))
    assert rep.ok, rep.summary()
    assert len(rep.regions) == 1
    r = rep.regions[0]
    assert r.mesh_axes == ("data",)
    per_ex = [o for o in r.outputs if o.sharded_over_data]
    repl = [o for o in r.outputs if not o.sharded_over_data]
    assert per_ex and repl
    # jax binds one psum per gradient leaf: exactly one per replicated
    # output, each over the data axis
    assert len(r.psums) == len(repl)
    assert all(ps.axes == ("data",) for ps in r.psums)
    assert all(o.data_psums == 0 for o in per_ex)
    assert all(o.data_psums == 1 for o in repl)


def test_collectives_local_trace_is_trivially_clean():
    rep = col.analyze_trace(_dp_trace())
    assert rep.ok
    assert rep.regions == ()


def test_expected_schedule_degenerate_and_dptp():
    plan = plan_mod.analyze([pex.Clip(1.0), pex.Noise(0.1, KEY)])
    flat = types.SimpleNamespace(axis_names=("data",),
                                 shape={"data": 8})
    sched = {e.output: e for e in
             col.expected_schedule(plan, flat, ("data",))}
    assert sched["loss_vec"].psum_axes == ()
    assert sched["grads"].psum_axes == ("data",)
    # 2-D DP×TP: per-example norms/losses gain the model-axis psum,
    # gradients reduce over both (the static half of the contract)
    dptp = types.SimpleNamespace(axis_names=("data", "model"),
                                 shape={"data": 4, "model": 2})
    sched2 = {e.output: e for e in
              col.expected_schedule(plan, dptp, ("data",))}
    assert sched2["loss_vec"].psum_axes == ("model",)
    assert sched2["sq_norms"].psum_axes == ("model",)
    assert sched2["sq_norms"].per_example
    assert sched2["grads"].psum_axes == ("data", "model")
    assert not sched2["grads"].per_example
    # weights derive from complete norms: never reduced
    assert sched2["weights"].psum_axes == ()


# ---------------------------------------------------------------------------
# determinism: clean targets + the non-seed-drift purity rules
# ---------------------------------------------------------------------------

def test_determinism_shipping_targets_are_clean():
    rep = det.analyze()
    assert rep.ok, rep.summary()
    names = [t.name for t in rep.targets]
    assert any("pipeline" in n for n in names)
    assert any("_probe_batch" in n for n in names)


@pytest.mark.parametrize("src,code", [
    ("def f(step):\n    return np.random.default_rng((time.time(), step))",
     "forbidden-call"),
    ("def f(step):\n    return np.random.randint(0, 9)",
     "forbidden-call"),
    ("def f(step):\n    return random.random()", "forbidden-call"),
    ("def f(step):\n    rng = np.random.default_rng()\n"
     "    return rng.integers(step)", "unseeded-rng"),
    ("def f(step):\n    return np.random.default_rng((hash('s'), step))",
     "unstable-hash"),
    ("class S:\n    def batch_at(self, step):\n"
     "        self.cursor = step\n"
     "        return np.random.default_rng((self.cursor, step))",
     "iterator-state"),
    ("def f(step):\n    global cur\n    cur += 1\n    return cur",
     "global-state"),
], ids=["wall-clock", "legacy-np", "stdlib-random", "unseeded",
        "hash-seed", "iter-state", "global"])
def test_determinism_rule(src, code):
    findings = det.check_source(src, "snippet")
    assert code in {f.code for f in findings}


def test_determinism_allows_seeded_streams():
    src = ("class S:\n"
           "    def __init__(self, seed):\n"
           "        self.seed = seed\n"
           "    def batch_at(self, step):\n"
           "        rng = np.random.default_rng((self.seed, step))\n"
           "        return rng.integers(0, 9, size=(4,))\n")
    assert not det.check_source(src, "snippet")


# ---------------------------------------------------------------------------
# composition: Engine.verify carries the new passes, trace-only holds
# ---------------------------------------------------------------------------

def test_engine_verify_deep_fields():
    from repro.core.engine import Engine
    from repro.core.taps import PexSpec
    aspec, loss_fn, params, batch = abstract_setup("llama3.2-1b")
    eng = Engine(PexSpec(enabled=True), mesh=_mesh())
    rep = eng.verify(loss_fn, params, batch,
                     [[pex.Clip(1.0), pex.Noise(0.1, KEY)]])
    assert rep.ok, rep.summary()
    assert len(rep.privacy) == 1
    assert len(rep.collectives) == 1
    assert rep.determinism is not None and rep.determinism.ok
    assert rep.findings == ()
    assert "collectives:" in rep.summary()
    assert "determinism:" in rep.summary()


def test_bench_drift_gate_clean_on_committed_baseline():
    from benchmarks import check_drift
    path = check_drift.newest_bench(
        check_drift.os.path.dirname(check_drift._HERE))
    with open(path) as f:
        bench = json.load(f)
    assert check_drift.check(bench) == []


def test_bench_drift_gate_catches_drift():
    from benchmarks import check_drift
    bench = {
        # crossover numbers far from what the current model computes
        "methods.crossover[p=512x512]": 0.0,
        "methods.crossover[p=512x512]#derived": "xla_s=9999;pallas_s=9999",
        # a pick the model disagrees with, and a measured pair where
        # the "picked" method is 10x the best
        "methods.gram[b=2,s=256,p=64x64]": 548.2,
        "methods.gram[b=2,s=256,p=64x64]#derived": "cost_model_pick=gram",
        "methods.direct[b=2,s=256,p=64x64]": 54.8,
        # interpret-mode rows must be skipped, however wrong
        "seg.crossover[p=32x32,n=4]": 0.0,
        "seg.crossover[p=32x32,n=4]#derived":
            "model_t=1;measured_t=1;interpret_mode",
    }
    problems = check_drift.check(bench)
    assert any("xla_s drifted" in p for p in problems)
    assert any("pick flipped" in p for p in problems)
    assert any("measured best" in p for p in problems)
    assert not any("seg.crossover[" in p for p in problems)


def test_flow_passes_are_trace_only():
    """Privacy + collectives over a mesh trace must never reach XLA
    compilation (key created before the block goes up)."""
    from jax._src import compiler
    mesh = _mesh()
    orig = compiler.backend_compile

    def blocked(*a, **kw):
        raise AssertionError("flow pass triggered an XLA compile")

    compiler.backend_compile = blocked
    try:
        tr = _dp_trace(mesh=mesh)
        assert priv.analyze_trace(tr).ok
        assert col.analyze_trace(tr).ok
        assert det.analyze().ok
    finally:
        compiler.backend_compile = orig
