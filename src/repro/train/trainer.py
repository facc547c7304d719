"""Training loop over declarative consumer plans (DESIGN.md §9).

The old fixed step modes (plain / norms / clip / importance) are gone
as trainer concepts: a step is described by a **consumer list** and the
pex v2 ``Engine.step`` compiles it into one fused pass —

    TrainConfig(consumers=(pex.Clip(1.0), pex.Noise(0.1), pex.GNS()))

runs DP-SGD clipping, noise, and gradient-noise-scale telemetry off a
single tapped forward + activation backward + one reweighted backward.
``Noise``/``Importance`` entries may leave ``rng=None``; the trainer
splits its step key into them. ``consumers_for_mode`` maps the legacy
mode names (the launcher CLI still speaks them) onto consumer lists.

Every per-example pass routes through one ``Engine`` (``core.engine``):
the Trainer takes the v2 canonical loss
``loss_fn(params, batch, tap) -> (loss_vec, aux)`` and the Engine
dispatches single-device vs. the data-parallel shard_map pipeline from
its mesh.

Integrates: microbatch gradient accumulation, optional int8
error-feedback compression, async checkpointing, heartbeats, straggler
stats, deterministic resume.
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.ckpt.checkpoint import CheckpointManager
from repro.core import book
from repro.core import plan as plan_mod
from repro.core.engine import Engine
from repro.core.taps import PexSpec
from repro.core.engine import infer_batch_size
from repro.data.pipeline import DataConfig, PipelineState, SyntheticLM
from repro.ft.heartbeat import HeartbeatConfig, HeartbeatMonitor
from repro.optim import adamw, grad_compress


@dataclasses.dataclass
class TrainConfig:
    #: the consumer plan for every step; None ⇒ (Norms(), Grads()) —
    #: the classic grads+norms-in-one-backward step
    consumers: Optional[Sequence] = None
    microbatches: int = 1
    compress_grads: bool = False
    steps: int = 100
    log_every: int = 10
    ckpt_every: int = 50
    ckpt_dir: Optional[str] = None
    seed: int = 0


def consumers_for_mode(mode: str, batch_size: int, *,
                       clip_norm: float = 1.0, noise_std: float = 0.0,
                       candidate_factor: int = 4,
                       importance_smoothing: float = 0.2) -> Tuple:
    """Legacy mode names → consumer lists (the launcher CLI contract).

    plain       — gradient only (no instrumentation traced at all)
    norms       — grads + per-example norms in one backward (§4/§5)
    clip        — per-example clipping (§6); + Noise when noise_std>0
    importance  — norms on the pool → sample batch/candidate_factor
                  examples ∝ norm → weighted step on the sub-batch
    """
    if mode == "plain":
        return (plan_mod.Grads(),)
    if mode == "norms":
        return (plan_mod.Norms(), plan_mod.Grads())
    if mode == "clip":
        cons = [plan_mod.Norms(), plan_mod.Clip(clip_norm)]
        if noise_std > 0.0:
            cons.append(plan_mod.Noise(noise_std))
        return tuple(cons)
    if mode == "importance":
        return (plan_mod.Importance(batch_size // candidate_factor,
                                    smoothing=importance_smoothing),
                plan_mod.Grads())
    raise ValueError(f"unknown mode {mode!r}; have plain/norms/clip/"
                     f"importance (or pass TrainConfig(consumers=...))")


def _inject_rngs(consumers: Sequence, rng: jax.Array):
    """Fill rng=None slots of Noise/Importance consumers from the step
    key (one split per slot, order-stable)."""
    need = [c for c in consumers
            if isinstance(c, (plan_mod.Noise, plan_mod.Importance))
            and c.rng is None]
    if not need:
        return tuple(consumers)
    keys = iter(jax.random.split(rng, len(need)))
    return tuple(dataclasses.replace(c, rng=next(keys)) if c in need else c
                 for c in consumers)


#: checkpoint ``extra`` keys a resume refuses to run without: the step
#: cursor, the optimizer step, the trainer seed, and the data-pipeline
#: state (which carries its own step/seed — PipelineState.from_dict
#: validates those).
RESUME_EXTRA_KEYS = ("step", "opt_step", "seed", "data")


class Trainer:
    def __init__(self, loss_fn: Callable, params, pex_spec: PexSpec,
                 opt_cfg: adamw.AdamWConfig, train_cfg: TrainConfig,
                 data_cfg: DataConfig, *, mesh=None, data_axes=("data",),
                 data=None):
        """``loss_fn`` is the v2 canonical tap-collector loss
        (``registry.make_loss_fn_v2``). ``mesh=None`` runs
        single-device; a mesh routes every per-example transform
        through the data-parallel shard_map pipeline (dist.pex) with
        gradients psum'd across ``data_axes``. ``data`` overrides the
        default ``SyntheticLM(data_cfg)`` with any source exposing
        ``batch_at(step)`` (e.g. the soak harness's
        ``LogicalShardedLM``)."""
        self.loss_fn = loss_fn
        self.cfg = train_cfg
        self.opt_cfg = opt_cfg
        self.data_cfg = data_cfg
        self.consumers = tuple(train_cfg.consumers) \
            if train_cfg.consumers is not None \
            else (plan_mod.Norms(), plan_mod.Grads())
        if not any(isinstance(c, (plan_mod.Grads, plan_mod.Clip,
                                  plan_mod.Noise, plan_mod.GNS))
                   for c in self.consumers):
            raise ValueError(
                f"training needs a gradient-producing consumer "
                f"(Grads/Clip/Noise/GNS); got {self.consumers}")
        self.pex_spec = pex_spec
        self.data_axes = data_axes
        self.engine = Engine(pex_spec, mesh=mesh, data_axes=data_axes)
        self.data = data if data is not None else SyntheticLM(data_cfg)
        self.params = params
        self.opt_state = adamw.init(params)
        self.err = grad_compress.init_error(params) \
            if train_cfg.compress_grads else None
        self.step = 0
        self.rng = jax.random.PRNGKey(train_cfg.seed)
        self.ckpt = CheckpointManager(train_cfg.ckpt_dir) \
            if train_cfg.ckpt_dir else None
        self.metrics: list = []
        #: graceful-degradation log: quarantine / skip events
        self.events: list = []
        #: backward applications of the step program as last traced
        #: (``StepResult.backwards``), by whether it is the weighted
        #: quarantine retry; reported as ``pex_backwards``
        self._backwards: Dict[bool, int] = {}
        self._step_fn = self._build_step()
        self._step_fn_weighted = None       # built on first quarantine
        self._fitted: set = set()           # steps whose memory was checked

    # ------------------------------------------------------------------
    def _build_step(self, weighted: bool = False):
        """The jitted step. It donates the state the trainer owns (the
        optimizer moments and the compression error carry) and returns
        no gradient tree, so the update is written in place of the
        moments it reads. A step whose loss or per-example norms are
        not finite returns the moments and carry it was given, so that
        ``run_step``'s quarantine retry and its skip start from the
        state before the step (the parameters are not donated; there
        ``run_step`` keeps its own)."""
        loss_fn, opt_cfg = self.loss_fn, self.opt_cfg
        consumers, eng = self.consumers, self.engine
        traced = self._backwards

        @partial(jax.jit, donate_argnums=(1, 2))
        def step_fn(params, opt_state, err, batch, rng, *loss_weights):
            res = eng.step(loss_fn, params, batch,
                           consumers=_inject_rngs(consumers, rng),
                           loss_weights=loss_weights[0] if weighted
                           else None)
            traced[weighted] = res.backwards
            grads, new_err = res.grads, err
            if err is not None:
                grads, new_err = grad_compress.compress_decompress(grads,
                                                                   err)
            params, new_opt = adamw.update(opt_cfg, opt_state, params,
                                           grads)
            ok = jnp.isfinite(res.loss)
            if res.sq_norms is not None:
                ok = ok & jnp.all(jnp.isfinite(res.sq_norms))
            # fuses into the update's own pass, whose scope it takes
            with jax.named_scope(adamw.SCOPE):
                keep = partial(jax.tree_util.tree_map,
                               lambda new, old: jnp.where(ok, new, old))
                new_opt, new_err = keep(new_opt, opt_state), \
                    keep(new_err, err)
            return (params, new_opt, new_err,
                    res._replace(grads=None, backwards=None))

        return step_fn

    # -- graceful degradation -------------------------------------------
    def _quarantine_mask(self, res) -> Optional[np.ndarray]:
        """True for examples whose loss and per-example norms are
        finite. None when every example is bad (nothing to salvage)."""
        mask = np.isfinite(np.asarray(res.loss_vec, np.float32))
        if res.sq_norms is not None:
            per_ex = np.asarray(res.sq_norms, np.float32)
            mask &= np.isfinite(per_ex).all(axis=tuple(range(1, per_ex.ndim)))
        return mask if mask.any() else None

    @staticmethod
    def _substitute_rows(batch, mask: np.ndarray):
        """Replace quarantined rows with the first healthy row. The
        zero loss-weight removes the substitute's (finite) gradient
        contribution *exactly*; substitution only keeps NaNs produced
        in the forward out of the program — a zero cotangent seed does
        not (0·NaN = NaN), so masking by weight alone cannot quarantine
        an example whose activations are already poisoned."""
        b = infer_batch_size(batch)
        donor = int(np.argmax(mask))
        keep = jnp.asarray(mask)

        def sub(x):
            if x.ndim == 0 or x.shape[0] != b:
                return x
            m = keep.reshape((b,) + (1,) * (x.ndim - 1))
            return jnp.where(m, x, x[donor][None])

        return jax.tree_util.tree_map(sub, batch)

    # ------------------------------------------------------------------
    def lower_step(self, batch) -> jax.stages.Lowered:
        """The step program that ``run_step`` runs on ``batch``, lowered
        and not run (``.compile().as_text()`` is the HLO the device
        runs). The trainer's state is left as it is."""
        args = (self.params, self.opt_state, self.err, batch, self.rng)
        return self._step(False, *args).lower(*args)

    def _step(self, weighted: bool, *args):
        """The jitted step ``run_step`` calls on ``args`` (the weighted
        quarantine retry's built on first use). Its first use checks the
        compiled program's memory against the device
        (``book.step_overflows``): where the step took the book
        (``core.book``) and would not fit, the engine's book room is
        set to 0 and the step is built again with the second backward.
        The check's compile is the one the call would make."""
        if weighted and self._step_fn_weighted is None:
            self._step_fn_weighted = self._build_step(weighted=True)
        fn = self._step_fn_weighted if weighted else self._step_fn
        if weighted not in self._fitted:
            self._fitted.add(weighted)
            if book.step_overflows(fn, *args) and \
                    self._backwards.get(weighted) == 1:
                self.engine._book_room = 0
                fn = self._build_step(weighted)
                if weighted:
                    self._step_fn_weighted = fn
                else:
                    self._step_fn = fn
        return fn

    def run_step(self, batch) -> Dict:
        """One step. The host's parts are ``jax.profiler``
        annotations (``trainer.*``), so that a profiler trace can put
        the device's idle time down to them; ``time_s`` is the whole
        step on the host clock."""
        t0 = time.perf_counter()
        with TraceAnnotation("trainer.dispatch"):
            self.rng, sub = jax.random.split(self.rng)
            args = (self.params, self.opt_state, self.err, batch, sub)
            params, self.opt_state, self.err, res = \
                self._step(False, *args)(*args)
        with TraceAnnotation("trainer.fetch_loss"):
            loss = float(res.loss)
        bad = not np.isfinite(loss)
        if not bad and res.sq_norms is not None:
            with TraceAnnotation("trainer.finite_check"):
                bad = not bool(jnp.all(jnp.isfinite(res.sq_norms)))
        quarantined = 0
        if bad:
            with TraceAnnotation("trainer.quarantine"):
                # non-finite loss/grad: the per-example norms (and losses)
                # the pass already computed identify the poisoned examples;
                # reweight them out through the plan's loss_weights= path
                # and retry — skip examples, not steps (DESIGN.md §11)
                mask = self._quarantine_mask(res)
                if mask is None:
                    self.events.append({"step": self.step, "kind": "skip_step",
                                        "reason": "every example non-finite"})
                    dt = time.perf_counter() - t0
                    m = {"step": self.step, "loss": loss, "time_s": dt,
                         "skipped": 1,
                         "pex_backwards": self._backwards[False]}
                    self.metrics.append(m)
                    return m          # previous params/opt_state kept
                quarantined = int((~mask).sum())
                self.events.append({
                    "step": self.step, "kind": "quarantine",
                    "examples": [int(i) for i in np.flatnonzero(~mask)]})
                args = (self.params, self.opt_state, self.err,
                        self._substitute_rows(batch, mask), sub,
                        jnp.asarray(mask, jnp.float32))
                params, self.opt_state, self.err, res = \
                    self._step(True, *args)(*args)
                loss = float(res.loss)
        self.params = params
        jax.block_until_ready(res.loss)
        dt = time.perf_counter() - t0
        m = {"step": self.step, "loss": loss, "time_s": dt,
             "pex_backwards": self._backwards[bool(quarantined)]}
        if quarantined:
            m["quarantined"] = quarantined
        if res.sq_norms is not None:
            with TraceAnnotation("trainer.norm_stats"):
                sqs = jnp.sum(res.sq_norms, -1)
                m["norm_mean"] = float(jnp.mean(jnp.sqrt(sqs)))
                m["norm_max"] = float(jnp.max(jnp.sqrt(sqs)))
        if res.gns is not None:
            m["gns"] = float(res.gns)
        self.metrics.append(m)
        return m

    # -- checkpoint plumbing --------------------------------------------
    def _state_tree(self):
        return {"params": self.params, "mu": self.opt_state.mu,
                "nu": self.opt_state.nu}

    def _ckpt_extra(self) -> Dict:
        return {"step": self.step, "opt_step": int(self.opt_state.step),
                "seed": self.cfg.seed,
                "data": PipelineState(step=self.step,
                                      seed=self.data_cfg.seed).to_dict()}

    def save_checkpoint(self, block: bool = False) -> None:
        assert self.ckpt is not None, "no ckpt_dir configured"
        self.ckpt.save(self.step, self._state_tree(),
                       extra=self._ckpt_extra(), block=block)

    def _validate_extra(self, extra: Dict) -> PipelineState:
        missing = [k for k in RESUME_EXTRA_KEYS if k not in extra]
        if missing:
            raise ValueError(
                f"checkpoint extra is missing key(s) {missing} (have "
                f"{sorted(extra)}); refusing to resume — a checkpoint "
                f"without step/seed/pipeline state cannot be replayed "
                f"deterministically")
        if int(extra["seed"]) != self.cfg.seed:
            raise ValueError(
                f"checkpoint was written by a run with seed="
                f"{extra['seed']}, this trainer has seed={self.cfg.seed}; "
                f"resuming would fork the rng/noise stream")
        ps = PipelineState.from_dict(extra["data"])
        if ps.seed != self.data_cfg.seed:
            raise ValueError(
                f"checkpoint data stream has seed={ps.seed}, this "
                f"trainer's pipeline has seed={self.data_cfg.seed}; "
                f"resuming would replay different batches")
        return ps

    def restore_from(self, step: Optional[int] = None,
                     shardings=None) -> int:
        """Restore params/opt-state/step from the newest restorable
        checkpoint (≤ ``step`` if given; CheckpointManager falls back
        past corrupt ones), after validating the ``extra`` payload.
        Returns the step actually restored."""
        assert self.ckpt is not None, "no ckpt_dir configured"
        self.ckpt.wait()        # surface writer-thread failures first
        restored, extra = self.ckpt.restore(step, self._state_tree(),
                                            shardings=shardings)
        ps = self._validate_extra(extra)
        self.params = restored["params"]
        self.opt_state = adamw.AdamWState(
            jnp.asarray(extra["opt_step"], jnp.int32),
            restored["mu"], restored["nu"])
        self.step = int(extra["step"])
        assert ps.step == self.step, \
            f"pipeline cursor {ps.step} != trainer step {self.step}"
        return self.step

    # -- elastic rebinding ----------------------------------------------
    def rebind_mesh(self, mesh, data_axes=None) -> None:
        """Rebuild the engine and compiled step(s) on a new topology —
        the supervisor calls this after contraction/expansion. Params
        and optimizer state are re-placed by the next jitted step."""
        if data_axes is not None:
            self.data_axes = data_axes
        self.engine = Engine(self.pex_spec, mesh=mesh,
                             data_axes=self.data_axes)
        self._step_fn = self._build_step()
        self._step_fn_weighted = None
        self._fitted = set()

    # ------------------------------------------------------------------
    def train(self, resume: bool = False) -> list:
        if resume and self.ckpt and self.ckpt.latest_step() is not None:
            self.restore_from(None)
        while self.step < self.cfg.steps:
            batch = self.data.batch_at(self.step)
            m = self.run_step(batch)
            self.step += 1
            if self.cfg.log_every and self.step % self.cfg.log_every == 0:
                print(f"[{self.step}] " + " ".join(
                    f"{k}={v:.4g}" for k, v in m.items() if k != "step"))
            if self.ckpt and self.step % self.cfg.ckpt_every == 0:
                self.save_checkpoint()
        if self.ckpt:
            self.ckpt.wait()
        return self.metrics
