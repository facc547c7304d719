"""Shared abstract-trace front end for the pexlint passes
(DESIGN.md §10, §12).

Two pieces every trace-only analyzer needs:

  * ``Walker`` — forward dataflow over a (closed) jaxpr on the union
    semilattice of frozensets. The base class owns the structural
    recursion — ``jit``, ``scan`` (carry fixpoint), ``while``
    (fixpoint), ``cond`` (branch join), ``shard_map`` (with region-
    depth tracking), single-sub-jaxpr generic calls, and a
    conservative everything-flows-everywhere fallback — so a pass only
    overrides ``hook`` for the equations it gives meaning to
    (coverage: the pex ``custom_vjp`` taps; privacy: ``pex_mark`` and
    the random primitives; collectives: ``psum``).

  * ``trace_step`` — trace one full ``Engine.step`` program (local or
    mesh path) on abstract inputs and return the closed jaxpr together
    with the maps the passes need: which invars are consumer PRNG
    keys, and which flat outvars are which result field (gradient
    leaves keep their parameter paths). Consumer rng keys are rebound
    as explicit arguments of the traced function so key lineage starts
    at an invar instead of vanishing into a constant.

Everything here is ``jax.make_jaxpr`` — no compilation, no execution;
``ShapeDtypeStruct`` params, batches, and keys all work.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax

from repro.core import plan as plan_mod

EMPTY = frozenset()


class AnalysisError(RuntimeError):
    """The jaxpr walker met a structure it cannot soundly propagate
    through (a sub-jaxpr whose arity disagrees with its equation)."""


def as_open(j):
    """Jaxpr of a possibly-Closed jaxpr."""
    return j.jaxpr if hasattr(j, "jaxpr") else j


def is_jaxpr(v) -> bool:
    return hasattr(v, "eqns") or (hasattr(v, "jaxpr")
                                  and hasattr(as_open(v), "eqns"))


def sub_jaxprs(params: dict):
    """Every (Closed)Jaxpr value in an equation's params."""
    found = []
    for v in params.values():
        if is_jaxpr(v):
            found.append(v)
        elif isinstance(v, (tuple, list)):
            found.extend(w for w in v if is_jaxpr(w))
    return found


def read(env, atom):
    if hasattr(atom, "val"):            # Literal
        return EMPTY
    return env.get(atom, EMPTY)


def write(env, var, taint):
    # DropVars are placeholders for unused outputs
    if type(var).__name__ == "DropVar":
        return
    env[var] = env.get(var, EMPTY) | taint


def iter_eqns(jaxpr, depth: int = 0):
    """Yield ``(eqn, depth)`` for every equation, recursing into every
    sub-jaxpr; ``depth`` counts enclosing ``shard_map`` regions."""
    for eqn in as_open(jaxpr).eqns:
        yield eqn, depth
        d = depth + (1 if eqn.primitive.name == "shard_map" else 0)
        for sub in sub_jaxprs(eqn.params):
            yield from iter_eqns(sub, d)


class Walker:
    """Forward taint propagation with pluggable equation semantics.

    Override ``hook(eqn, in_taints)``: return a list of output taints
    to take over the equation, or None to fall through to the
    structural default. ``self.recording`` is False during fixpoint
    warm-up runs — hooks that record sites must check it so scan/while
    bodies are not double-counted. ``self.region_depth`` counts the
    ``shard_map`` regions enclosing the current equation.
    """

    def __init__(self):
        self.recording = True
        self.region_depth = 0

    # -- override points --------------------------------------------------
    def hook(self, eqn, in_taints) -> Optional[List[frozenset]]:
        return None

    def const_taint(self, var) -> frozenset:
        return EMPTY

    # -- the walk ---------------------------------------------------------
    def run(self, jaxpr, in_taints: Sequence[frozenset]) -> List[frozenset]:
        jaxpr = as_open(jaxpr)
        if len(jaxpr.invars) != len(in_taints):
            raise AnalysisError(
                f"sub-jaxpr arity mismatch: {len(jaxpr.invars)} invars vs "
                f"{len(in_taints)} operand taints")
        env = {}
        for v in jaxpr.constvars:
            write(env, v, self.const_taint(v))
        for v, t in zip(jaxpr.invars, in_taints):
            write(env, v, t)
        for eqn in jaxpr.eqns:
            self._eqn(eqn, env)
        return [read(env, v) for v in jaxpr.outvars]

    def _quiet_run(self, jaxpr, in_taints):
        rec, self.recording = self.recording, False
        try:
            return self.run(jaxpr, in_taints)
        finally:
            self.recording = rec

    def _eqn(self, eqn, env) -> None:
        in_t = [read(env, v) for v in eqn.invars]
        outs = self.hook(eqn, in_t)
        if outs is not None:
            for ov, t in zip(eqn.outvars, outs):
                write(env, ov, t)
            return

        name = eqn.primitive.name
        if name == "jit":
            outs = self.run(eqn.params["jaxpr"], in_t)
            for ov, t in zip(eqn.outvars, outs):
                write(env, ov, t)
            return

        if name == "shard_map":
            self.region_depth += 1
            try:
                outs = self.run(eqn.params["jaxpr"], in_t)
            finally:
                self.region_depth -= 1
            for ov, t in zip(eqn.outvars, outs):
                write(env, ov, t)
            return

        if name == "scan":
            nc = eqn.params["num_consts"]
            ncar = eqn.params["num_carry"]
            body = eqn.params["jaxpr"]
            consts_t, carry_t = in_t[:nc], list(in_t[nc:nc + ncar])
            xs_t = in_t[nc + ncar:]
            while True:                  # carry-taint fixpoint
                outs = self._quiet_run(body, consts_t + carry_t + xs_t)
                new_carry = [c | o for c, o in zip(carry_t, outs[:ncar])]
                if new_carry == carry_t:
                    break
                carry_t = new_carry
            outs = self.run(body, consts_t + carry_t + xs_t)
            final = [c | o for c, o in zip(carry_t, outs[:ncar])] \
                + outs[ncar:]
            for ov, t in zip(eqn.outvars, final):
                write(env, ov, t)
            return

        if name == "while":
            cn = eqn.params["cond_nconsts"]
            bn = eqn.params["body_nconsts"]
            body = eqn.params["body_jaxpr"]
            cond_t = in_t[:cn]
            body_c = in_t[cn:cn + bn]
            carry_t = list(in_t[cn + bn:])
            while True:
                outs = self._quiet_run(body, body_c + carry_t)
                new_carry = [c | o for c, o in zip(carry_t, outs)]
                if new_carry == carry_t:
                    break
                carry_t = new_carry
            self.run(body, body_c + carry_t)
            pred = frozenset().union(*cond_t) if cond_t else EMPTY
            for ov, t in zip(eqn.outvars, carry_t):
                write(env, ov, t | pred)
            return

        if name == "cond":
            pred_t = in_t[0]
            for branch in eqn.params["branches"]:
                outs = self.run(branch, in_t[1:])
                for ov, t in zip(eqn.outvars, outs):
                    write(env, ov, t | pred_t)
            return

        subs = sub_jaxprs(eqn.params)
        if len(subs) == 1 and len(as_open(subs[0]).invars) == len(in_t):
            outs = self.run(subs[0], in_t)
            for ov, t in zip(eqn.outvars, outs):
                write(env, ov, t)
            return

        # conservative fallback: everything flows everywhere
        union = frozenset().union(*in_t) if in_t else EMPTY
        for ov in eqn.outvars:
            write(env, ov, union)


# ---------------------------------------------------------------------------
# full-step tracing
# ---------------------------------------------------------------------------

_KEYED = (plan_mod.Noise, plan_mod.Importance)


@dataclasses.dataclass(frozen=True)
class StepTrace:
    """One traced ``Engine.step`` program plus the index maps the
    privacy and collective passes anchor on."""
    closed: Any                         # ClosedJaxpr of the step
    plan: plan_mod.Plan
    granularity: str
    batch_size: int
    data_axes: Tuple[str, ...]
    meshed: bool                        # traced through dist.pex.plan_step
    out_labels: Tuple[Tuple[str, str], ...]   # (field, leaf path) per outvar
    rng_positions: Tuple[int, ...]      # invar indices holding consumer keys
    rng_purposes: Tuple[str, ...]       # parallel to rng_positions

    @property
    def jaxpr(self):
        return self.closed.jaxpr

    def grad_outvars(self):
        """(outvar, leaf path) for every gradient leaf output."""
        jx = self.closed.jaxpr
        return [(jx.outvars[i], rest)
                for i, (head, rest) in enumerate(self.out_labels)
                if head == "grads"]


def _label(path) -> Tuple[str, str]:
    head = getattr(path[0], "key", str(path[0]))
    return str(head), jax.tree_util.keystr(path[1:])


@dataclasses.dataclass(frozen=True)
class TrainTrace:
    """One traced *training* step — ``Engine.step`` plus the optimizer
    apply — with the index maps the traffic/cost passes anchor on
    (DESIGN.md §13). ``ref_closed`` is the plain forward
    (``consumers=[]``) of the same model/batch, the duplicate-forward
    baseline."""
    closed: Any                         # ClosedJaxpr of step + apply
    plan: plan_mod.Plan
    granularity: str
    batch_size: int
    seq: Optional[int]
    optimizer: str                      # 'adamw' | 'adafactor' | 'none'
    global_clip: Optional[float]        # optimizer global-norm clip
    meshed: bool
    out_labels: Tuple[Tuple[str, str], ...]   # (field, leaf path) per outvar
    param_labels: Tuple[str, ...]       # leaf path per parameter position
    param_positions: Tuple[int, ...]    # invar indices of the param leaves
    opt_positions: Tuple[int, ...]      # invar indices of optimizer state
    batch_positions: Tuple[int, ...]
    rng_positions: Tuple[int, ...]
    ref_closed: Optional[Any] = None    # plain-forward ClosedJaxpr

    @property
    def jaxpr(self):
        return self.closed.jaxpr


def trace_train_step(loss_fn: Callable, params, batch,
                     consumers: Sequence, *, optimizer: str = "adamw",
                     opt_cfg=None, spec=None, granularity: str = "example",
                     mesh=None, data_axes: Sequence[str] = ("data",),
                     batch_size: Optional[int] = None,
                     seq: Optional[int] = None, loss_weights=None,
                     with_reference: bool = True) -> TrainTrace:
    """Trace one whole training step on abstract inputs: the plan
    execution (``Engine.step``, local or mesh path) *and* the optimizer
    apply — clip-scale, moment updates, parameter write — which no
    other pass covers. Gradient leaves cross the plan/apply boundary
    through the ``grad_leaf`` markers ``plan.execute`` plants, so the
    traffic pass can attribute every downstream HBM pass to a named
    parameter leaf. Optimizer state enters as explicit invars
    (abstract, from ``eval_shape`` over ``init``)."""
    from repro.core.engine import Engine, infer_batch_size

    eng = Engine(spec, mesh=mesh, data_axes=data_axes,
                 granularity=granularity)
    plan = plan_mod.analyze(consumers, engine_granularity=granularity)
    bs = batch_size if batch_size is not None else infer_batch_size(batch)

    keys = [c.rng for c in consumers
            if isinstance(c, _KEYED) and c.rng is not None]

    if optimizer == "adamw":
        from repro.optim import adamw as opt_mod
        cfg = opt_cfg if opt_cfg is not None else opt_mod.AdamWConfig()
        global_clip = cfg.global_clip
    elif optimizer == "adafactor":
        from repro.optim import adafactor as opt_mod
        cfg = opt_cfg if opt_cfg is not None else opt_mod.AdafactorConfig()
        global_clip = getattr(cfg, "global_clip", None)
    elif optimizer == "none":
        opt_mod = cfg = global_clip = None
    else:
        raise ValueError(f"unknown optimizer {optimizer!r}; expected "
                         f"'adamw', 'adafactor', or 'none'")

    apply = opt_mod is not None and plan.needs_grads
    opt_state = jax.eval_shape(opt_mod.init, params) if apply else ()

    def run(p, opt, b, *ks):
        it = iter(ks)
        cs = [dataclasses.replace(c, rng=next(it))
              if isinstance(c, _KEYED) and c.rng is not None else c
              for c in consumers]
        r = eng.step(loss_fn, p, b, cs, batch_size=bs, seq=seq,
                     loss_weights=loss_weights)
        out = {"loss_vec": r.loss_vec}
        if r.sq_norms is not None:
            out["sq_norms"] = r.sq_norms
        if r.gns is not None:
            out["gns"] = r.gns
        if r.grads is not None and apply:
            new_p, new_opt = opt_mod.update(cfg, opt, p, r.grads)
            out["new_params"] = new_p
            out["opt_state"] = new_opt
        elif r.grads is not None:
            out["grads"] = r.grads
        return out

    closed, out_shape = jax.make_jaxpr(run, return_shape=True)(
        params, opt_state, batch, *keys)
    flat_out, _ = jax.tree_util.tree_flatten_with_path(out_shape)
    labels = tuple(_label(p) for p, _ in flat_out)

    flat_p, _ = jax.tree_util.tree_flatten_with_path(params)
    param_labels = tuple(jax.tree_util.keystr(p) for p, _ in flat_p)
    n_p = len(flat_p)
    n_o = len(jax.tree_util.tree_leaves(opt_state))
    n_b = len(jax.tree_util.tree_leaves(batch))

    ref = None
    if with_reference:
        # the duplicate-forward baseline must NOT share the plan path —
        # a mutant that doubles the plan's forward would double the
        # reference too and cancel out. Trace the raw loss instead.
        from repro.core.taps import NULL
        ref = jax.make_jaxpr(
            lambda p, b: loss_fn(p, b, NULL)[0])(params, batch)

    return TrainTrace(
        closed=closed, plan=plan, granularity=granularity, batch_size=bs,
        seq=seq, optimizer=optimizer if apply else "none",
        global_clip=global_clip if apply else None,
        meshed=mesh is not None, out_labels=labels,
        param_labels=param_labels,
        param_positions=tuple(range(n_p)),
        opt_positions=tuple(range(n_p, n_p + n_o)),
        batch_positions=tuple(range(n_p + n_o, n_p + n_o + n_b)),
        rng_positions=tuple(range(n_p + n_o + n_b,
                                  n_p + n_o + n_b + len(keys))),
        ref_closed=ref)


def trace_step(loss_fn: Callable, params, batch, consumers: Sequence, *,
               spec=None, granularity: str = "example", mesh=None,
               data_axes: Sequence[str] = ("data",),
               batch_size: Optional[int] = None, seq: Optional[int] = None,
               loss_weights=None) -> StepTrace:
    """Trace ``Engine.step`` for one consumer list on abstract inputs.

    Consumer PRNG keys (``Noise.rng`` / ``Importance.rng``) are lifted
    to explicit invars of the traced function — ``dataclasses.replace``
    rebinds each consumer to its argument inside the trace — so the
    privacy pass sees key lineage start at a named input. Keys may be
    ``ShapeDtypeStruct``s of a real key's aval.
    """
    from repro.core.engine import Engine, infer_batch_size

    eng = Engine(spec, mesh=mesh, data_axes=data_axes,
                 granularity=granularity)
    plan = plan_mod.analyze(consumers, engine_granularity=granularity)
    bs = batch_size if batch_size is not None else infer_batch_size(batch)

    keys, purposes = [], []
    for c in consumers:
        if isinstance(c, _KEYED) and c.rng is not None:
            keys.append(c.rng)
            purposes.append("noise" if isinstance(c, plan_mod.Noise)
                            else "importance")

    def run(p, b, *ks):
        it = iter(ks)
        cs = [dataclasses.replace(c, rng=next(it))
              if isinstance(c, _KEYED) and c.rng is not None else c
              for c in consumers]
        r = eng.step(loss_fn, p, b, cs, batch_size=bs, seq=seq,
                     loss_weights=loss_weights)
        out = {"loss_vec": r.loss_vec}
        if r.sq_norms is not None:
            out["sq_norms"] = r.sq_norms
        if r.grads is not None:
            out["grads"] = r.grads
        if r.gns is not None:
            out["gns"] = r.gns
        return out

    closed, out_shape = jax.make_jaxpr(run, return_shape=True)(
        params, batch, *keys)
    flat_out, _ = jax.tree_util.tree_flatten_with_path(out_shape)
    labels = tuple(_label(p) for p, _ in flat_out)

    n_p = len(jax.tree_util.tree_leaves(params))
    n_b = len(jax.tree_util.tree_leaves(batch))
    positions = tuple(range(n_p + n_b, n_p + n_b + len(keys)))
    return StepTrace(closed=closed, plan=plan, granularity=granularity,
                     batch_size=bs,
                     data_axes=(data_axes,) if isinstance(data_axes, str)
                     else tuple(data_axes),
                     meshed=mesh is not None, out_labels=labels,
                     rng_positions=positions, rng_purposes=tuple(purposes))
