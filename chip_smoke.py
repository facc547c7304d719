#!/usr/bin/env python3
"""Smoke run of the per-example DP-SGD training step on a TPU.

    python3 chip_smoke.py                # one chip
    python3 chip_smoke.py --four-chips   # data-parallel path, four chips

With no option it runs, in one process, on the first TPU device:

  1. kernels    — every Pallas kernel through its ``kernels/ops.py``
                  wrapper at qwen2-7b widths, against ``kernels/ref.py``;
  2. trainer    — ``Trainer`` → ``Engine.step`` → taps → Pallas kernels
                  for qwen2-7b at its published widths in bf16, cut to 2
                  layers and a 1/8 vocabulary share (19,008 of 152,064,
                  as one shard of an 8-way vocab-parallel deployment),
                  DP-SGD consumers (Norms, Clip, Noise), batch 8 × seq
                  1024, 3 steps;
  3. exactness  — per-example squared norms on the trained parameters:
                  Pallas against the XLA stats, and against the naive
                  per-example gradients of ``core/naive.py``.

``--four-chips`` runs only the data-parallel path over a 4-way data
mesh (``dist.pex``) against the single-device ``Engine.step``: the
data-parallel engine step's gradients, leaf by leaf, and the trainer
step's losses, norms, clip coefficients and placement. Timings printed on the way are smoke numbers, not benchmarks.

It stops with a non-zero exit when JAX finds no TPU, and on the first
phase that fails. The last line of standard output is, on success,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
The persistent compile cache lives where ``JAX_COMPILATION_CACHE_DIR``
says, or else in ``.jax_cache`` next to this file.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

B, SEQ, STEPS = 8, 1024, 3
#: qwen2-7b widths (configs/qwen2_7b.py)
D_MODEL, D_FF, N_HEADS, N_KV, HEAD_DIM = 3584, 18944, 28, 4, 128
VOCAB_SHARE = 152064 // 8


def log(msg: str) -> None:
    print(msg, flush=True)


def _report(name: str, got, want, rtol: float, why: str, measure: str,
            err_fn) -> None:
    """Fail the phase unless ``got`` is finite, shaped like ``want``, and
    within ``rtol`` of it by ``err_fn``."""
    import numpy as np
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        raise AssertionError(f"{name}: shape {got.shape} vs {want.shape}, "
                             f"finite={bool(np.all(np.isfinite(got)))}")
    err = float(err_fn(np, got, want))
    log(f"  {name}: {measure} {err:.3e} (rtol {rtol:g}: {why})")
    if err > rtol:
        raise AssertionError(f"{name}: {measure} {err:.3e} > {rtol:g}")


def check_close(name: str, got, want, rtol: float, why: str) -> None:
    """Largest elementwise relative error."""
    _report(name, got, want, rtol, why, "max rel err",
            lambda np, g, w: np.max(np.abs(g - w)
                                    / np.maximum(np.abs(w), 1e-30)))


def check_close_fro(name: str, got, want, rtol: float, why: str) -> None:
    """Relative Frobenius error ‖got − want‖ / ‖want‖."""
    _report(name, got, want, rtol, why, "rel Frobenius err",
            lambda np, g, w: np.linalg.norm(g - w) / np.linalg.norm(w))


# ---------------------------------------------------------------------------
# phase 1: kernels
# ---------------------------------------------------------------------------

def phase_kernels(jax, jnp) -> None:
    from repro.kernels import ops, ref
    if ops._interpret():
        raise AssertionError("kernels/ops would run Pallas in interpret "
                             "mode on this backend")
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 16))

    def normal(shape, dtype=jnp.bfloat16):
        return jax.random.normal(next(keys), shape, jnp.float32).astype(dtype)

    s = 512
    h = normal((B, s, D_MODEL))
    z_mlp = normal((B, s, D_FF))
    z_kv = normal((B, s, N_KV * HEAD_DIM))
    same = "the same bf16 operands accumulated in f32 in another order"
    log(f"[kernels] gram_norm {h.shape} x {z_mlp.shape}")
    check_close("gram_norm", jax.jit(ops.gram_norm)(h, z_mlp),
                ref.gram_norm_ref(h, z_mlp), 1e-3, same)
    log(f"[kernels] direct_norm {h.shape} x {z_mlp.shape}")
    check_close("direct_norm", jax.jit(ops.direct_norm)(h, z_mlp),
                ref.gram_norm_ref(h, z_mlp), 1e-3, same)
    log(f"[kernels] direct_norm {h.shape} x {z_kv.shape}")
    check_close("direct_norm kv", jax.jit(ops.direct_norm)(h, z_kv),
                ref.gram_norm_ref(h, z_kv), 1e-3, same)

    t, n_seg = B * s, B
    hs, zs = h.reshape(t, D_MODEL), z_mlp.reshape(t, D_FF)
    # one id in 9 is n_seg: a capacity-dropped row that must not count
    seg = jax.random.randint(next(keys), (t,), 0, n_seg + 1)
    log(f"[kernels] segmented_norm {hs.shape} x {zs.shape}, {n_seg} segments")
    check_close("segmented_norm",
                jax.jit(ops.segmented_norm, static_argnums=3)(hs, zs, seg,
                                                              n_seg),
                ref.segmented_norm_ref(hs, zs, seg, n_seg), 1e-3, same)

    c = jax.random.uniform(next(keys), (B,), jnp.float32)
    log(f"[kernels] clip_scale {z_mlp.shape}")
    check_close_fro("clip_scale", jax.jit(ops.clip_scale)(z_mlp, c),
                    ref.clip_scale_ref(z_mlp, c), 2.0 ** -7,
                    "the reference rounds c to bf16 before the product, "
                    "the kernel rounds once after it: two bf16 ulps")

    x = h.reshape(B, -1)
    log(f"[kernels] rowsumsq {x.shape}")
    check_close("rowsumsq", jax.jit(ops.rowsumsq)(x), ref.rowsumsq_ref(x),
                1e-4, "f32 sums of the same squares in another order")

    q = normal((B, N_HEADS, s, HEAD_DIM))
    k = normal((B, N_KV, s, HEAD_DIM))
    v = normal((B, N_KV, s, HEAD_DIM))
    scale = HEAD_DIM ** -0.5
    log(f"[kernels] flash_attention fwd + bwd q {q.shape}, k/v {k.shape}")
    flash = lambda q, k, v: ops.flash_attention_vjp(q, k, v, scale)
    attn_ref = lambda q, k, v: ref.flash_attention_ref(q, k, v, scale=scale)
    o, vjp = jax.vjp(jax.jit(flash), q, k, v)
    o_ref, vjp_ref = jax.vjp(attn_ref, q, k, v)
    bf16_out = ("bf16 outputs (2^-8 relative rounding) from an online "
                "softmax that rescales in another order")
    check_close_fro("flash fwd", o, o_ref, 2e-2, bf16_out)
    do = normal(o.shape)
    for name, g, g_ref in zip(("dq", "dk", "dv"), vjp(do), vjp_ref(do)):
        check_close_fro(f"flash bwd {name}", g, g_ref, 2e-2, bf16_out)


# ---------------------------------------------------------------------------
# phase 2: trainer; phase 3: exactness
# ---------------------------------------------------------------------------

def qwen2_cut(n_layers: int):
    from repro.configs import qwen2_7b
    return dataclasses.replace(qwen2_7b.full(), n_layers=n_layers,
                               vocab=VOCAB_SHARE)


def build_trainer(jax, cfg, consumers, *, mesh=None):
    from repro.core.taps import PexSpec
    from repro.data.pipeline import DataConfig
    from repro.models import registry
    from repro.nn.param import unbox
    from repro.optim import adamw
    from repro.train.trainer import TrainConfig, Trainer
    aspec = registry.get("qwen2-7b")
    mod = registry.family_module(aspec)
    params = unbox(jax.jit(lambda k: mod.init(k, cfg))(jax.random.PRNGKey(0)))
    loss_fn = registry.make_loss_fn_v2(aspec, cfg)
    trainer = Trainer(loss_fn, params, PexSpec(use_pallas=True),
                      adamw.AdamWConfig(lr=1e-5),
                      TrainConfig(consumers=consumers, steps=STEPS,
                                  log_every=1, seed=0),
                      DataConfig(vocab=cfg.vocab, seq=SEQ, global_batch=B,
                                 seed=0),
                      mesh=mesh)
    return trainer, loss_fn


def kernels_in_step(trainer, batch, key) -> None:
    """The compiled step must launch the gram and direct kernels as TPU
    custom calls (interpret mode lowers them to plain XLA loops)."""
    import re
    text = trainer._step_fn.lower(trainer.params, trainer.opt_state,
                                  trainer.err, batch, key).as_text()
    funcs = set(re.findall(r"func\.func private @(\w+)", text))
    n_calls = text.count("tpu_custom_call")
    log(f"  step program: {n_calls} tpu_custom_call site(s); kernel "
        f"functions {sorted(f for f in funcs if f.endswith('_norm'))}")
    missing = {"gram_norm", "direct_norm"} - funcs
    if missing or n_calls < 2:
        raise AssertionError(f"step does not launch {sorted(missing)} as "
                             f"compiled Pallas kernels")


def phase_trainer(jax, jnp, dev):
    from repro.core import plan as P
    cfg = qwen2_cut(2)
    trainer, loss_fn = build_trainer(
        jax, cfg, (P.Norms(), P.Clip(1.0), P.Noise(1.0)))
    n = sum(x.size for x in jax.tree_util.tree_leaves(trainer.params))
    log(f"[trainer] qwen2-7b widths, 2 layers, vocab {cfg.vocab}: "
        f"{n / 1e6:.1f}M params; batch {B} x seq {SEQ}; "
        f"consumers Norms, Clip(1.0), Noise(1.0)")
    kernels_in_step(trainer, trainer.data.batch_at(0),
                    jax.random.PRNGKey(0))
    metrics = trainer.train()
    for m in metrics:
        if not all(math.isfinite(m[k]) for k in ("loss", "norm_mean")):
            raise AssertionError(f"non-finite step metrics {m}")
        if m.get("skipped") or m.get("quarantined"):
            raise AssertionError(f"step degraded: {m}")
    times = [m["time_s"] for m in metrics]
    steady = sorted(times[1:])[len(times[1:]) // 2]
    peak = dev.memory_stats().get("peak_bytes_in_use", 0)
    log(f"  smoke timing: first step {times[0]:.2f} s (compile included), "
        f"steady step {steady:.3f} s (median of {len(times) - 1})")
    log(f"  peak_bytes_in_use {peak} ({peak / 2**30:.2f} GiB)")
    return trainer.params, loss_fn, trainer.data.batch_at(STEPS)


def phase_exactness(jax, jnp, params, loss_fn, batch) -> None:
    from repro.core import naive
    from repro.core import plan as P
    from repro.core.engine import Engine
    from repro.core.taps import NULL, PexSpec
    from repro.models import registry

    def norms(use_pallas):
        eng = Engine(PexSpec(use_pallas=use_pallas))
        fn = jax.jit(lambda p, b: jnp.sum(
            eng.step(loss_fn, p, b, [P.Norms()]).sq_norms, -1))
        return fn(params, batch)

    log("[exactness] per-example squared norms on the trained parameters")
    sq_pallas = norms(True)
    sq_xla = norms(False)
    check_close("pallas vs xla", sq_pallas, sq_xla, 5e-3,
                "one bf16 forward and backward; only the stat's f32 "
                "accumulation order differs, and gram cross terms cancel")

    excl = registry.untapped_allowlist("qwen2-7b")

    def single(p, ex):
        b1 = jax.tree_util.tree_map(lambda x: x[None], ex)
        return loss_fn(p, b1, NULL)[0][0]

    two = jax.tree_util.tree_map(lambda x: x[:2], batch)
    with jax.default_matmul_precision("highest"):
        sq_naive = jax.jit(lambda p, b: naive.per_example_sq_norms(
            single, p, b,
            lambda path: not any(e in str(path) for e in excl)))(params, two)
    check_close("pallas vs naive (2 examples)", sq_pallas[:2], sq_naive,
                2e-2, "the naive oracle reruns the bf16 forward one "
                "example at a time and rounds each gradient to bf16 "
                "before squaring")


# ---------------------------------------------------------------------------
# --four-chips: data-parallel step against the single-device step
# ---------------------------------------------------------------------------

def phase_four_chips(jax, jnp, devices) -> None:
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.core import plan as P
    from repro.core.engine import Engine
    from repro.launch.mesh import make_host_mesh
    from repro.train.trainer import _inject_rngs
    if len(devices) != 4:
        raise AssertionError(f"--four-chips needs 4 devices, JAX found "
                             f"{len(devices)}")
    # 1 layer: the data-parallel trainer replicates parameters and AdamW
    # moments on every chip, and device 0 also holds the single-device
    # reference step; at 2 layers that is ~15 GiB of a 16 GiB chip
    cfg = qwen2_cut(1)
    # σ small enough that the clipped gradient sum (per-element RMS
    # ~1e-4 at 369M parameters) is not lost in the bf16 rounding of the
    # noise, so a wrong psum or clip shows in the gradients
    consumers = (P.Norms(), P.Clip(1.0), P.Noise(1e-4))
    mesh = make_host_mesh()
    trainer, loss_fn = build_trainer(jax, cfg, consumers, mesh=mesh)
    log(f"[four-chips] mesh {dict(mesh.shape)}; qwen2-7b widths, 1 layer, "
        f"vocab {cfg.vocab}; global batch {B} x seq {SEQ}")
    batch = trainer.data.batch_at(0)
    key = jax.random.PRNGKey(11)
    eng = Engine(trainer.pex_spec)
    ref = jax.jit(lambda p, b: eng.step(
        loss_fn, p, b, consumers=_inject_rngs(consumers, key)))(
            trainer.params, batch)
    ref = jax.tree_util.tree_map(np.asarray, ref)

    # parameters and AdamW moments replicated on every chip, the batch
    # split over them: the layout the data-parallel step runs in
    replicated = NamedSharding(mesh, PartitionSpec())
    trainer.params = jax.device_put(trainer.params, replicated)
    trainer.opt_state = jax.device_put(trainer.opt_state, replicated)
    batch_dp = jax.device_put(batch, NamedSharding(mesh, PartitionSpec("data")))
    # the trainer's step returns no gradient tree (it donates the moments
    # the update overwrites): the data-parallel gradients come from the
    # same engine step it runs, jitted on its own
    grads_dp = jax.jit(lambda p, b: trainer.engine.step(
        loss_fn, p, b, consumers=_inject_rngs(consumers, key)).grads)(
            trainer.params, batch_dp)
    leaves = jax.tree_util.tree_leaves_with_path(grads_dp)
    leaves_ref = jax.tree_util.tree_leaves(ref.grads)
    if not leaves or len(leaves) != len(leaves_ref):
        raise AssertionError(f"{len(leaves)} data-parallel gradient leaves "
                             f"against {len(leaves_ref)} single-device ones")
    for (path, g), g_ref in zip(leaves, leaves_ref):
        check_close_fro(f"grad{jax.tree_util.keystr(path)}", g, g_ref, 2e-2,
                        "four bf16 partial sums and a psum against one sum "
                        "of 8; a lost or doubled psum is off by >= 0.5")
    log(f"  {len(leaves)} gradient leaves compared")
    del grads_dp, leaves
    gc.collect()
    t0 = time.perf_counter()
    params, opt_state, _, res = trainer._step_fn(
        trainer.params, trainer.opt_state, trainer.err, batch_dp, key)
    jax.block_until_ready(res.loss)
    t_first = time.perf_counter() - t0
    for name, arr in (("batch ids", batch_dp["ids"]),
                      ("per-example norms", res.sq_norms)):
        rows = {s.device.id: s.data.shape[0] for s in arr.addressable_shards}
        log(f"  {name}: rows per device {rows}")
        if len(rows) != 4 or set(rows.values()) != {B // 4}:
            raise AssertionError(f"{name} not split over 4 devices: {rows}")
    spread = {len(x.sharding.device_set)
              for x in jax.tree_util.tree_leaves((params, opt_state))}
    log(f"  updated parameters and moments live on {spread} device(s)")
    if spread != {4}:
        raise AssertionError(f"parameters not on all 4 devices: {spread}")

    rows_why = ("each chip runs the bf16 forward on 2 examples instead of "
                "8; a misplaced or reduced row is off by O(1)")
    check_close("per-example loss", res.loss_vec, ref.loss_vec, 1e-2,
                rows_why)
    check_close("per-example sq norms", jnp.sum(res.sq_norms, -1),
                ref.sq_norms.sum(-1), 1e-2, rows_why)
    check_close("clip coefficients", res.clip_coef, ref.clip_coef, 1e-2,
                rows_why)
    del res, ref
    trainer.params, trainer.opt_state = params, opt_state
    del params, opt_state
    gc.collect()
    m = trainer.run_step(batch_dp)
    log(f"  smoke timing: first data-parallel step {t_first:.2f} s (compile "
        f"included), next step {m['time_s']:.3f} s")
    if not np.isfinite(m["loss"]):
        raise AssertionError(f"non-finite data-parallel metrics {m}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip data-parallel step and the "
                         "single-device step it must equal")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (first device is "
              f"{dev.platform}); nothing was run", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    import repro
    if ROOT / "src" not in Path(repro.__file__).resolve().parents:
        print(f"chip_smoke: repro imported from {repro.__file__}, not from "
              f"this checkout", file=sys.stderr)
        return 2
    from repro.launch.cache import use_compile_cache
    log(f"device {dev.device_kind} x {len(devices)}; compile cache "
        f"{use_compile_cache(ROOT)}")

    t0 = time.perf_counter()
    if args.four_chips:
        phase_four_chips(jax, jnp, devices)
    else:
        phase_kernels(jax, jnp)
        params, loss_fn, batch = phase_trainer(jax, jnp, dev)
        gc.collect()
        phase_exactness(jax, jnp, params, loss_fn, batch)
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
