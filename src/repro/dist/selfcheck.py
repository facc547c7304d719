import os
os.environ.setdefault(
    "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
# ^ MUST precede any jax-touching import (device count locks at first init).

_DOC = """Mesh-sharded per-example pipeline self-check.

Runs the tap-instrumented smoke model through a local ``Engine`` and
again through a mesh-bound ``Engine`` (the dist.pex shard_map pipeline)
on a ≥2-way data-parallel host mesh, and asserts the two agree: scalar
loss, (B,) per-example losses, (B, G) squared norms, summed gradients,
clipped gradients, and a fused consumer plan (Clip + GNS — DESIGN.md
§9) (f32 allclose). This is the
repo's executable proof that the per-example-norm math composes with
batch sharding — run it on any box:

    PYTHONPATH=src python -m repro.dist.selfcheck
    PYTHONPATH=src python -m repro.dist.selfcheck --arch llama3.2-1b --batch 16

``--model-parallel N`` shows the pipeline's data-parallel-only limit:
any N > 1 exits with the dist.pex NotImplementedError (parameters are
replicated into every shard; ROADMAP R1).
"""

import argparse
import sys


def run(arch: str = "llama3.2-1b", batch: int = 8, seq: int = 8,
        model_parallel: int = 1, method: str = "gram",
        verbose: bool = True) -> int:
    import jax
    import numpy as np

    from repro.configs.common import ShapeSpec
    from repro.core.engine import Engine
    from repro.core.taps import PexSpec
    from repro.dist import pex
    from repro.launch.mesh import make_host_mesh
    from repro.models import registry
    from repro.nn.param import unbox

    n_dev = len(jax.devices())
    if n_dev < 2:
        print("selfcheck needs >=2 devices (set "
              "XLA_FLAGS=--xla_force_host_platform_device_count=N)")
        return 2

    aspec = registry.get(arch)
    cfg = aspec.smoke()
    mod = registry.family_module(aspec)
    params = unbox(mod.init(jax.random.PRNGKey(0), cfg))
    spec = PexSpec(enabled=True, method=method)
    loss_fn = registry.make_loss_fn_v2(aspec, cfg)
    batch_data = registry.make_train_batch(
        aspec, cfg, ShapeSpec("selfcheck", "train", seq, batch))

    mesh = make_host_mesh(model_parallel=model_parallel)
    n_shards = mesh.shape["data"]
    assert n_shards >= 2, f"only {n_shards} data shards; need >= 2"

    eng_local = Engine(spec)
    eng_mesh = Engine(spec, mesh=mesh)

    ref = jax.jit(lambda p, b: eng_local.value_grads_and_norms(
        loss_fn, p, b))(params, batch_data)
    got = jax.jit(lambda p, b: eng_mesh.value_grads_and_norms(
        loss_fn, p, b))(params, batch_data)

    ok = True

    def check(name, a, b, rtol=1e-5, atol=1e-6):
        nonlocal ok
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        good = np.allclose(a, b, rtol=rtol, atol=atol)
        ok &= good
        if verbose or not good:
            err = np.max(np.abs(a - b)) if a.size else 0.0
            print(f"[{'ok' if good else 'FAIL'}] {name:24s} "
                  f"max|Δ|={err:.3g}")

    check("loss", ref.loss, got.loss)
    check("loss_vec", ref.loss_vec, got.loss_vec)
    check("sq_norms", ref.sq_norms, got.sq_norms, rtol=1e-4)
    for (pa, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(ref.grads),
            jax.tree_util.tree_leaves_with_path(got.grads)):
        check("grads" + jax.tree_util.keystr(pa), a, b, rtol=1e-4,
              atol=1e-5)

    ref_n = jax.jit(lambda p, b: eng_local.value_and_norms(
        loss_fn, p, b))(params, batch_data)
    got_n = jax.jit(lambda p, b: eng_mesh.value_and_norms(
        loss_fn, p, b))(params, batch_data)
    check("norms-only sq_norms", ref_n.sq_norms, got_n.sq_norms, rtol=1e-4)

    clip = 0.5 * float(np.sqrt(np.median(
        np.sum(np.asarray(ref.sq_norms), -1))))
    ref_c = jax.jit(lambda p, b: eng_local.clipped_step(
        loss_fn, p, b, clip_norm=clip))(params, batch_data)
    got_c = jax.jit(lambda p, b: eng_mesh.clipped_step(
        loss_fn, p, b, clip_norm=clip))(params, batch_data)
    for (pa, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(ref_c.grads),
            jax.tree_util.tree_leaves_with_path(got_c.grads)):
        check("clipped" + jax.tree_util.keystr(pa), a, b, rtol=1e-4,
              atol=1e-5)

    # fused consumer plan (DESIGN.md §9): clip + GNS in one pass must
    # also agree across the shard boundary
    from repro.core import plan as plan_mod
    cons = [plan_mod.Clip(clip), plan_mod.GNS()]
    ref_p = jax.jit(lambda p, b: eng_local.step(
        loss_fn, p, b, consumers=cons))(params, batch_data)
    got_p = jax.jit(lambda p, b: eng_mesh.step(
        loss_fn, p, b, consumers=cons))(params, batch_data)
    check("plan gns", ref_p.gns, got_p.gns, rtol=1e-4)
    check("plan weights", ref_p.weights, got_p.weights, rtol=1e-4)
    for (pa, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(ref_p.grads),
            jax.tree_util.tree_leaves_with_path(got_p.grads)):
        check("plan" + jax.tree_util.keystr(pa), a, b, rtol=1e-4,
              atol=1e-5)

    gns = pex.gradient_noise_scale(got.sq_norms, got.grads)
    print(f"{'PASS' if ok else 'FAIL'}: {n_shards}-way data-parallel "
          f"(model_parallel={model_parallel}) matches single-device; "
          f"B_simple={float(gns):.3g}")
    return 0 if ok else 1


def main():
    ap = argparse.ArgumentParser(description=_DOC)
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=8)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--method", default="gram")
    sys.exit(run(**{k.replace("-", "_"): v
                    for k, v in vars(ap.parse_args()).items()}))


if __name__ == "__main__":
    main()
