"""Training launcher: --arch <id> [--smoke] with pex step modes.

    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b \
        --smoke --mode clip --steps 50 --ckpt-dir /tmp/ck

Full-size configs target the production mesh (run under a real TPU
runtime with the same flags); --smoke runs the reduced config on
whatever devices exist.
"""
from __future__ import annotations

import argparse

import jax

from repro.core.taps import PexSpec
from repro.data.pipeline import DataConfig
from repro.launch.cache import use_compile_cache
from repro.models import registry
from repro.nn.param import count_params, unbox
from repro.optim import adamw
from repro.optim.schedule import linear_warmup_cosine
from repro.train.trainer import TrainConfig, Trainer, consumers_for_mode


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--mode", default="norms",
                    choices=["plain", "norms", "clip", "importance"])
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--clip-norm", type=float, default=1.0)
    ap.add_argument("--noise-std", type=float, default=0.0,
                    help="DP-SGD noise multiplier for --mode clip")
    ap.add_argument("--pex-method", default="auto")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--data-parallel", action="store_true",
                    help="shard the batch over all local devices via the "
                         "dist.pex shard_map pipeline")
    args = ap.parse_args()
    use_compile_cache()

    aspec = registry.get(args.arch)
    cfg = aspec.smoke() if args.smoke else aspec.full()
    mod = registry.family_module(aspec)
    params = unbox(mod.init(jax.random.PRNGKey(args.seed), cfg))
    print(f"{args.arch}: {count_params(params) / 1e6:.1f}M params, "
          f"mode={args.mode}")

    pex = PexSpec(enabled=args.mode != "plain", method=args.pex_method)
    loss_fn = registry.make_loss_fn_v2(aspec, cfg)
    mesh = None
    if args.data_parallel:
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(model_parallel=1)
        print(f"data-parallel over {mesh.shape['data']} devices")
    # the CLI still speaks the legacy mode names; the trainer itself
    # runs declarative consumer plans (DESIGN.md §9)
    consumers = consumers_for_mode(args.mode, args.batch,
                                   clip_norm=args.clip_norm,
                                   noise_std=args.noise_std)
    trainer = Trainer(
        loss_fn, params, pex,
        adamw.AdamWConfig(lr=args.lr,
                          schedule=linear_warmup_cosine(10, args.steps)),
        TrainConfig(consumers=consumers,
                    steps=args.steps, ckpt_dir=args.ckpt_dir, seed=args.seed),
        DataConfig(vocab=cfg.vocab, seq=args.seq, global_batch=args.batch,
                   seed=args.seed),
        mesh=mesh)
    trainer.train(resume=args.resume)


if __name__ == "__main__":
    main()
