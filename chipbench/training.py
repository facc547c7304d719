"""Training jobs: the program's ``Trainer.run_step``, one batch a step,
on the cell's chips: one, or a data-parallel mesh over all of them (axis
``"data"``), the global batch split over it.

Set-up builds one trainer from weights drawn on the device from the
seed, and drives it through its first ``CHECKED_STEPS`` steps with the
window's own call and feed; those steps compile every program the
window runs. From them it keeps what the reference is compared with:
each step's loss (and, where the plan has norms, the mean and largest
per-example gradient norm), AdamW's first moment after the first step
(a host copy: the first gradient as AdamW received it, times 1 − β₁),
and the per-leaf norms of the parameters' change over the checked
steps. The same trainer then runs the window.

The reference runs the same steps in float32 (``reference/steps.py``),
with the DP-SGD noise that the program draws: the same ``jax.random``
calls on the trainer's key schedule (``noise_key``), once after the sum
on a mesh as on one chip. On a mesh each chip takes its own rows of the
global batch one at a time, and the sums are added. Numbers, each a
relative gap (0 is exact):

  loss    first step: |loss − ref| / |ref|
  norms   first step, mean and largest per-example norm:
          |norm − ref| / ref
  sum     the first gradient, element by element: ‖g − g_ref‖ over
          ‖g_ref − noise‖, the clipped sum that the gradient carries
          beside its noise. The part of the gap along the noise, a
          scale that the global-norm clip sets, is left out of both.
  quiet_sum  ``sum`` over the elements whose noise is at most
          ``QUIET`` σC. Elsewhere a bfloat16 gradient rounds the
          clipped sum, some 1e-4 of the noise an element, away: the
          noise's own rounding step is larger than it.
  grad    worst leaf of the first gradient's norm:
          |‖g‖ − ‖g_ref‖| / max(‖g_ref‖, median leaf's)
  update  the same for the parameters' change over the checked steps
  later   the second and later steps' loss and norms, as ``loss`` and
          ``norms``

A cell compares the numbers its file gives limits for. Leaves whose
reference gradient is under a thousandth of the median leaf's move
under AdamW by round-off alone; they are left out of ``update``.
"""
from __future__ import annotations

import functools
import gc

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from chipbench import program
from chipbench.reference import common as C
from chipbench.reference import steps as R

CHECKED_STEPS = 3
#: ``quiet_sum`` takes the elements whose noise is at most this share of
#: σC: there a bfloat16 rounding step is under a tenth of the clipped
#: sum that qwen2-7b-2l's rows give an element
QUIET = 2.0 ** -8


def noise_key(seed: int, t: int):
    """The key the program draws step ``t``'s DP-SGD noise from (``t``
    from 1), on the trainer's documented schedule: ``PRNGKey`` of
    ``TrainConfig.seed``, one split a step, and from that step's key
    one more split for the plan's one ``Noise`` consumer."""
    rng = jax.random.PRNGKey(seed)
    for _ in range(t):
        rng, sub = jax.random.split(rng)
    return jax.random.split(sub, 1)[0]


def _leaf_norms(tree) -> dict:
    return dict(zip(C.leaf_paths(tree), (
        jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
        for x in jax.tree_util.tree_leaves(tree))))


def _flat(tree) -> dict:
    return dict(zip(C.leaf_paths(tree), jax.tree_util.tree_leaves(tree)))


def _host(d: dict) -> dict:
    return {k: float(v) for k, v in jax.device_get(d).items()}


@functools.partial(jax.jit, static_argnames=("dtype", "std"))
def _leaf_sums(mu, mu_ref, key, coef, *, dtype, std):
    """⟨d, d⟩, ⟨d, n⟩, ⟨n, n⟩, ⟨t, t⟩, ⟨t, n⟩ of one leaf, with d the
    gap of the two first moments, n the reference's noise as it enters
    its first moment, and t the rest of the reference's moment; then
    the same five over the elements whose noise is quiet (``QUIET``)."""
    f32 = jnp.float32
    raw = R.noise(key, mu_ref.shape, dtype, std).astype(f32) if std \
        else jnp.zeros_like(mu_ref)
    n = coef * raw
    d, t = mu.astype(f32) - mu_ref, mu_ref - n
    quiet = (jnp.abs(raw) <= QUIET * std).astype(f32)
    return jnp.stack([jnp.sum(d * d), jnp.sum(d * n), jnp.sum(n * n),
                      jnp.sum(t * t), jnp.sum(t * n),
                      jnp.sum(quiet * d * d), jnp.sum(quiet * d * n),
                      jnp.sum(quiet * n * n), jnp.sum(quiet * t * t),
                      jnp.sum(quiet * t * n)])


def rel(a, b) -> float:
    return abs(a - b) / abs(b)


def worst_leaf(a: dict, b: dict, keep=None) -> float:
    keys = [k for k in b if keep is None or k in keep]
    med = float(np.median([b[k] for k in b]))
    return max(abs(a[k] - b[k]) / max(b[k], med) for k in keys)


class TrainJob:
    weight_grads = True
    first_window_step = CHECKED_STEPS
    #: faults the calibration plants in the reference put in the
    #: program's place: half the batch dropped (the sum over the rest
    #: doubled); the clipped sum taken over half the rows, doubled,
    #: with the whole batch's losses and norms; the clip left out; on a
    #: mesh, the clipped sum of one shard's rows times the shard count,
    #: what a missing psum gives (``one_shard``)
    FAULTS = ("half_batch", "half_grad", "unclipped")
    #: the numeric counters of ``run_step``'s metrics that a step keeps
    COUNTERS = ("pex_backwards",)

    def __init__(self, ctx):
        self.ctx = ctx
        self.args = ctx.args
        self.cfg = ctx.config
        init = functools.partial(ctx.family.init_params, self.cfg)
        if ctx.chips == 1:
            self.mesh, self.rows = None, ctx.devices[0]
            self.init = jax.jit(init)
        else:
            if ctx.mix["batch"] % ctx.chips:
                raise ValueError(f"batch {ctx.mix['batch']} does not split "
                                 f"over {ctx.chips} chips")
            self.mesh = Mesh(np.array(ctx.devices), ("data",))
            self.rows = NamedSharding(self.mesh, P("data"))
            self.init = jax.jit(init, out_shardings=NamedSharding(
                self.mesh, P()))
        self.trainer = None
        self.checked = {"loss": [], "norm_mean": [], "norm_max": []}

    def faults(self) -> tuple:
        """The faults this cell's job can have."""
        own = self.FAULTS if self.args.get("clip_norm") is not None \
            else ("half_batch",)
        return own + (("one_shard",) if self.mesh is not None else ())

    # -- the program ---------------------------------------------------
    def setup(self):
        params = C.nest(self.init(self.ctx.key(1)))
        mix = self.ctx.mix
        self.trainer = program.trainer(self.cfg, params, self.args,
                                       seed=self.ctx.seed31,
                                       batch=mix["batch"], seq=mix["seq"],
                                       mesh=self.mesh)
        del params
        for i in range(CHECKED_STEPS):
            rec = self.step(i)
            for k in self.checked:
                if k in rec:
                    self.checked[k].append(rec[k])
            if i == 0:
                mu = self.trainer.opt_state.mu
                self.checked["mu1"] = jax.device_get(_flat(mu))
                self.checked["grad"] = {
                    k: v / (1 - self.args["b1"])
                    for k, v in _host(jax.jit(_leaf_norms)(mu)).items()}
        change = jax.jit(lambda p, key: R.leaf_change_norms(
            _flat(p), self.ctx.family.init_params(self.cfg, key)))
        self.checked["update"] = _host(change(self.trainer.params,
                                              self.ctx.key(1)))

    def step(self, i: int) -> dict:
        with jax.profiler.TraceAnnotation("loader"):
            batch = jax.device_put(self.ctx.stream.batch_at(i), self.rows)
        with jax.profiler.TraceAnnotation("run_step"):
            m = self.trainer.run_step(batch)
            self.trainer.step += 1
        rec = {"tokens": self.ctx.stream.tokens_per_step,
               "failed": bool(m.get("skipped") or m.get("quarantined")),
               "loss": m["loss"],
               "counters": {k: m[k] for k in self.COUNTERS if k in m}}
        if "norm_mean" in m:
            rec["norm_mean"], rec["norm_max"] = m["norm_mean"], m["norm_max"]
        return rec

    def step_hlo(self) -> str:
        """The HLO text of the step program the window ran, compiled
        again (the process's own cache holds it)."""
        batch = jax.device_put(self.ctx.stream.batch_at(0), self.rows)
        return self.trainer.lower_step(batch).compile().as_text()

    def answers(self) -> dict:
        return {k: v for k, v in self.checked.items() if v}

    def close(self):
        self.trainer = None
        gc.collect()

    # -- the reference -------------------------------------------------
    def reference(self, mode: str = "f32", fault: str = None) -> dict:
        """The checked steps, computed by the reference in ``mode``,
        with ``fault`` planted where asked."""
        args, fam, cfg = self.args, self.ctx.family, self.cfg
        loss = functools.partial(fam.example_loss, cfg=cfg)
        b = self.ctx.mix["batch"]
        rows, weights, loss_scale, clip = b, np.ones(b, np.float32), 1.0, True
        if fault == "half_batch":
            rows, loss_scale = b // 2, 2.0
            weights = np.full(rows, 2.0, np.float32)
        elif fault == "half_grad":
            weights = np.where(np.arange(b) < b // 2, 2.0, 0.0
                               ).astype(np.float32)
        elif fault == "unclipped":
            clip = False
        elif fault == "one_shard" and self.mesh is not None:
            n = self.ctx.chips
            weights = np.where(np.arange(b) < b // n, float(n), 0.0
                               ).astype(np.float32)
        elif fault is not None:
            raise ValueError(f"unknown fault {fault!r}")
        opt = R.AdamW(**{k: args[k] for k in program.ADAMW_KEYS})
        p = self.init(self.ctx.key(1))
        order = C.leaf_paths(C.nest(p))
        step = jax.jit(functools.partial(
            R.train_step, loss, mode=mode, opt=opt,
            clip_norm=args.get("clip_norm"),
            noise_std=args.get("noise_std", 0.0), leaf_order=order,
            clip=clip, summed=R.clipped_sum if self.mesh is None
            else R.over_devices(self.mesh)), donate_argnums=(0, 1, 2))
        out = {"loss": [], "norm_mean": [], "norm_max": [], "order": order,
               "dtypes": {k: v.dtype for k, v in p.items()}}
        mu = {k: jnp.zeros(v.shape, jnp.float32) for k, v in p.items()}
        nu = {k: jnp.zeros(v.shape, jnp.float32) for k, v in p.items()}
        for t in range(1, CHECKED_STEPS + 1):
            batch = self.ctx.stream.batch_at(t - 1)
            ids, labels = (jnp.asarray(batch[k][:rows])
                           for k in ("ids", "labels"))
            p, mu, nu, losses, sq, leaf, s = step(
                p, mu, nu, jnp.float32(t), ids, labels,
                noise_key(self.ctx.seed31, t), jnp.asarray(weights))
            norms = np.sqrt(np.asarray(sq, np.float64))
            out["loss"].append(loss_scale * float(
                np.sum(np.asarray(losses, np.float64))))
            out["norm_mean"].append(float(norms.mean()))
            out["norm_max"].append(float(norms.max()))
            if t == 1:
                out["grad"] = _host(leaf)
                out["mu1"] = jax.device_get(mu)
                out["gscale"] = float(s)
        del mu, nu
        out["update"] = _host(jax.jit(R.leaf_change_norms)(
            p, self.init(self.ctx.key(1))))
        return out

    def _sum_gaps(self, mu: dict, ref: dict) -> tuple:
        """``sum`` over the whole gradient, its worst leaf among those
        that carry a thousandth of the median leaf's sum or more, and
        ``quiet_sum``."""
        args = self.args
        std = args.get("noise_std", 0.0) * (args.get("clip_norm") or 0.0)
        coef = (1 - args["b1"]) * ref["gscale"]
        keys = R.noise_keys(noise_key(self.ctx.seed31, 1), ref["order"])
        per = {k: np.asarray(_leaf_sums(
            jnp.asarray(mu[k]), jnp.asarray(r), keys[k], coef,
            dtype=ref["dtypes"][k], std=std), np.float64)
            for k, r in ref["mu1"].items()}
        total = sum(per.values())

        def gap(s, whole):
            dn, nn, tn = whole[1], whole[2], whole[4]
            a, c = (dn / nn, tn / nn) if nn > 0 else (0.0, 0.0)
            d = s[0] - 2 * a * s[1] + a * a * s[2]
            t = s[3] - 2 * c * s[4] + c * c * s[2]
            return max(d, 0.0), max(t, 0.0)

        d, t = gap(total[:5], total[:5])
        leaves = {k: gap(s[:5], total[:5]) for k, s in per.items()}
        med = float(np.median([t for _, t in leaves.values()]))
        worst = max(np.sqrt(d / t) for d, t in leaves.values()
                    if t >= 1e-6 * med and t > 0)
        qd, qt = gap(total[5:], total[5:])
        return float(np.sqrt(d / t)), float(worst), float(np.sqrt(qd / qt))

    def numbers(self, answers: dict, ref: dict) -> dict:
        med = float(np.median(list(ref["grad"].values())))
        moved = {k for k, v in ref["grad"].items() if v >= 1e-3 * med}
        keys = [k for k in ("loss", "norm_mean", "norm_max") if k in answers]
        gaps = [[rel(answers[k][t], ref[k][t]) for k in keys]
                for t in range(CHECKED_STEPS)]
        out = {"loss": gaps[0][0]}
        if len(keys) > 1:
            out["norms"] = max(gaps[0][1:])
        out["sum"], out["sum_leaf"], out["quiet_sum"] = self._sum_gaps(
            answers["mu1"], ref)
        out["grad"] = worst_leaf(answers["grad"], ref["grad"])
        out["update"] = worst_leaf(answers["update"], ref["update"], moved)
        out["later"] = max(max(g) for g in gaps[1:])
        return out
