"""A copy of the benchmark at test sizes, for CPU tests of the harness.

``make(dest)`` copies the benchmark's files into ``dest`` and shrinks
each configuration's widths and each traffic mix, so that a whole run
(set-up, window, reference, comparison) takes seconds on the CPU. The
cells keep their names, jobs and consumers; their limits are replaced
by ``LIMITS``, set for these sizes in float32.
"""
from __future__ import annotations

import json
import shutil
from pathlib import Path

from chipbench.harness import BENCH_DIR, Bench

_ATTN = {"attn.d_model": 64, "attn.n_heads": 4, "attn.n_kv": 2,
         "attn.head_dim": 16, "attn.head_multiple": 1}

#: configuration → (file keys, the program's fields) at test sizes
SHRINK = {
    "qwen2-7b-2l": (
        {"hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 256},
        {"n_layers": 2, "d_model": 64, "vocab": 256, **_ATTN,
         "mlp.d_model": 64, "mlp.d_ff": 128}),
}

#: per job, the limits of a float32 run at these sizes
LIMITS = {
    "dpsgd": {"loss": 1e-4, "norms": 1e-3, "sum": 1e-2, "quiet_sum": 1e-2,
              "grad": 1e-2, "update": 0.3, "later": 1e-3},
    "plain": {"loss": 1e-4, "sum": 1e-2, "quiet_sum": 1e-2, "grad": 1e-2,
              "update": 0.3, "later": 1e-3},
}


def make(dest: Path, dtype: str = "float32") -> Bench:
    dest = Path(dest)
    shutil.copytree(BENCH_DIR, dest, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    shutil.copy(BENCH_DIR.parent / "BENCHMARK.json", dest)
    for name, (keys, fields) in SHRINK.items():
        path = dest / "configs" / f"{name}.json"
        cfg = json.loads(path.read_text())
        cfg.update(keys, dtype=dtype)
        cfg["program"] = fields
        path.write_text(json.dumps(cfg))
    for path in (dest / "mixes").glob("*.json"):
        mix = json.loads(path.read_text())
        mix.update(batch=mix["batch"] // 4, seq=32, n_motifs=8, motif_len=4)
        path.write_text(json.dumps(mix))
    for path in (dest / "workloads").glob("*.json"):
        cell = json.loads(path.read_text())
        job = json.loads((dest / "mixes" / f"{cell['traffic']}.json")
                         .read_text())["job"]
        cell["limits"] = LIMITS[job]
        path.write_text(json.dumps(cell))
    return Bench(dest, dest / "BENCHMARK.json")


# ---------------------------------------------------------------------------
# faults planted in the program under the timed path
# ---------------------------------------------------------------------------

def _unchanged():
    """Every optimizer step returns the parameters and moments it got."""
    from repro.optim import adamw

    def update(cfg, state, params, grads):
        return params, adamw.AdamWState(state.step + 1, state.mu, state.nu)
    adamw.update = update


def _half_batch():
    """Every step sees the first half of its batch; the losses and
    norms of the rest are those of the first half again, and the
    gradient is twice the half's (the mean over the rest, scaled)."""
    import jax
    import jax.numpy as jnp
    from repro.core import engine

    step = engine.Engine.step

    def half(self, loss_fn, params, batch, consumers=(), **kw):
        b = jax.tree_util.tree_leaves(batch)[0].shape[0]
        sub = jax.tree_util.tree_map(lambda x: x[: b // 2], batch)
        res = step(self, loss_fn, params, sub, consumers, **kw)
        twice = lambda x: None if x is None else jnp.concatenate([x, x])
        grads = None if res.grads is None else \
            jax.tree_util.tree_map(lambda g: 2 * g, res.grads)
        return res._replace(loss=2 * res.loss, loss_vec=twice(res.loss_vec),
                            sq_norms=twice(res.sq_norms), grads=grads)
    engine.Engine.step = half


def _clip_weights(fault):
    """The clip-weighted backward alone goes wrong: the losses and the
    per-example norms stay those of the whole batch."""
    import jax.numpy as jnp
    from repro.core import plan

    coefficients = plan.clip_coefficients

    def wrong(sq_norms, clip_norm, eps=1e-6):
        c = coefficients(sq_norms, clip_norm, eps)
        if fault == "unclipped":
            return jnp.ones_like(c)
        half = jnp.arange(c.shape[0]) < c.shape[0] // 2
        return jnp.where(half, 2 * c, 0.0)
    plan.clip_coefficients = wrong


class _Over:
    """``base`` with some attributes replaced."""

    def __init__(self, base, **over):
        self._base, self._over = base, over

    def __getattr__(self, name):
        return self._over.get(name, getattr(self._base, name))


def _one_shard():
    """The data-parallel step's gradient psum left out: each shard
    keeps the clipped sum of its own rows, times the shard count."""
    import jax
    from repro.dist import pex

    def own(x, axes):
        n = jax.lax.psum(1, axes)
        return jax.tree_util.tree_map(lambda v: v * n, x)
    pex.jax = _Over(jax, lax=_Over(jax.lax, psum=own))


FAULTS = {"unchanged": _unchanged, "half_batch": _half_batch,
          "half_grad": lambda: _clip_weights("half_grad"),
          "unclipped": lambda: _clip_weights("unclipped"),
          "one_shard": _one_shard}


def main(argv) -> int:
    """``python -m chipbench.tests.tiny <dest> <fault|none> <run args>``:
    one run of the benchmark at test sizes on this machine's devices,
    with ``fault`` planted in the program first."""
    from chipbench import program
    from chipbench.harness import run
    dest, fault, rest = argv[0], argv[1], argv[2:]
    bench = make(dest)
    program.import_program()
    if fault != "none":
        FAULTS[fault]()
    return run(rest, bench=bench, require_tpu=False)


if __name__ == "__main__":
    import sys
    sys.exit(main(sys.argv[1:]))
