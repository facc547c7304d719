"""The plain float32 reference against the program at test sizes, and
the control: the reference computed in float8 in the program's place
has to fail one of each cell's numbers."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import program
from chipbench.harness import Context
from chipbench.reference import common as C
from chipbench.reference import steps as R
from chipbench.tests import tiny


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return tiny.make(tmp_path_factory.mktemp("tiny") / "bench")


def _program_norms(cfg: dict):
    """``Engine(PexSpec()).step(loss_fn, params, batch, [Norms()])``:
    per-example losses and squared gradient norms."""
    from repro.core.engine import Engine
    from repro.core.taps import PexSpec
    fn, eng = program.loss_fn(cfg), Engine(PexSpec())

    @jax.jit
    def step(params, batch):
        res = eng.step(fn, params, batch, program.consumers({"norms": True}))
        return res.loss_vec, jnp.sum(res.sq_norms, axis=-1)
    return step


def test_reference_matches_the_program(bench):
    """Per-example losses and squared gradient norms of the program and
    of the reference, on one batch."""
    ctx = Context(bench, bench.workload("qwen2-7b-dpsgd-s1k"), 7000000003,
                  jax.devices()[:1])
    cfg, fam = ctx.config, ctx.family
    p = jax.jit(functools.partial(fam.init_params, cfg))(ctx.key(1))
    batch = ctx.stream.batch_at(0)
    losses, sq = _program_norms(cfg)(C.nest(p), batch)
    ref_losses, ref_sq = R.score(
        functools.partial(fam.example_loss, cfg=cfg), p,
        jnp.asarray(batch["ids"]), jnp.asarray(batch["labels"]), mode="f32")
    # both in float32, summed in different orders
    np.testing.assert_allclose(losses, ref_losses, rtol=1e-4)
    np.testing.assert_allclose(sq, ref_sq, rtol=1e-3)


@pytest.mark.parametrize("cell", ["qwen2-7b-dpsgd-s1k", "qwen2-7b-plain-s1k"])
def test_the_control_fails(bench, cell):
    ctx = Context(bench, bench.workload(cell), 7000000019,
                  jax.devices()[:1])
    job = bench.job(ctx.job).Job(ctx)
    ref = job.reference("f32")
    numbers = job.numbers(job.reference("fp8"), ref)
    limits = ctx.cell["limits"]
    assert any(numbers[k] > limits[k] for k in limits), numbers
