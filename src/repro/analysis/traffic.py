"""HBM-traffic attribution over a full *training* step — the static
half of the paper's overhead claim ("pexcost", DESIGN.md §13).

The paper's value proposition is a cost statement: per-example norms
ride along with one backward at near-zero extra traffic. The repo
checks that empirically (BENCH_PR*.json) and piecemeal (the HLO
one-forward budget); this pass certifies it *statically*, over the
whole step — ``plan.execute``/``dist.pex.plan_step`` **plus** the
optimizer apply (clip-scale, noise add, AdamW/Adafactor moments),
which no other pass covers.

The walk (over ``_jaxpr.Walker``, on the DCE'd jaxpr of
``_jaxpr.trace_train_step``) labels every equation with

  * a **phase** — forward / activation-bwd / weight-bwd / stats /
    apply — from taint lineage: gradient-leaf markers
    (``core.provenance.mark_grad_tree``, planted by ``plan.execute``
    at the plan/apply boundary), backward-seed markers, optimizer-
    state invars, and the noise key;
  * a **fusion component** — elementwise producer→consumer chains of
    equal reduction rank merge (reductions absorb their input chains;
    single-consumer dtype converts ride along; markers are barriers)
    — the static stand-in for XLA fusion, so "materialized bytes"
    means bytes that cross HBM, not every intermediate.

From those it derives the per-leaf **gradient stream count**: the
number of apply-phase fusion components that re-read a full
leaf-sized, gradient-tainted array. Today's DP-SGD path streams every
gradient three times (noise add; the optimizer's global-norm
reduction; the fused scale/moments/update) — the known 3× waste the
ROADMAP's fused-apply item documents, reported here as an allowlisted
finding so the future fused kernel lands against a ready oracle.

Findings:

  * ``redundant-hbm-stream`` — more full-gradient HBM passes than the
    plan + optimizer structurally require (the allowlisted "expected
    today" count is reported separately);
  * ``duplicate-forward``   — forward-phase flops exceed the plain
    forward of the same model (× the importance-region factor);
  * ``dead-residual``       — a two-backward plan whose reweighted
    backward does not reuse the norms backward's residuals (a second
    linearization doubles residual traffic); where the plan took the
    book (``core.book``), whose contraction reads no residuals, one
    whose book was not kept by the norms backward that produced the
    step's norms;
  * ``upcast-materialization`` — a widening dtype copy of a gradient
    leaf materialized as its own pass (f32 copies of bf16 trees).

Trace-only: ``jax.make_jaxpr`` + pure-python graph analysis.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

from repro.analysis import _jaxpr as _J
from repro.analysis.findings import ERROR, Finding
from repro.core.provenance import (MARK_PRIMITIVE, TAG_BOOK, TAG_GLEAF,
                                   TAG_RNG, TAG_SEED, meta_dict)

PASS = "traffic"
EMPTY = _J.EMPTY

#: taint tokens
T_PARAM = "p"
T_OPT = "opt"
T_BATCH = "b"
T_KEY = "key"
T_NOISEKEY = "nz"       # the DP noise draw's key lineage (rng_use marker)

#: phases, in attribution priority order
PH_APPLY = "apply"
PH_STATS = "stats"
PH_WEIGHT = "weight-bwd"
PH_ACT = "activation-bwd"
PH_FWD = "forward"
PHASES = (PH_FWD, PH_ACT, PH_WEIGHT, PH_STATS, PH_APPLY)

# ---------------------------------------------------------------------------
# primitive classification
# ---------------------------------------------------------------------------

#: elementwise compute that XLA fuses freely (1 flop / output element
#: when the output is floating)
_ELEM = frozenset({
    "add", "sub", "mul", "div", "rem", "max", "min", "pow", "atan2",
    "neg", "sign", "floor", "ceil", "round", "abs", "exp", "exp2", "log",
    "log1p", "expm1", "tanh", "sqrt", "rsqrt", "cbrt", "logistic", "erf",
    "erfc", "erf_inv", "sin", "cos", "tan", "asin", "acos", "atan",
    "sinh", "cosh", "asinh", "acosh", "atanh", "integer_pow", "square",
    "clamp", "select_n", "is_finite", "eq", "ne", "lt", "le", "gt", "ge",
    "and", "or", "xor", "not", "shift_left", "shift_right_logical",
    "shift_right_arithmetic", "population_count", "clz", "nextafter",
    "stop_gradient", "copy", "convert_element_type", "reduce_precision",
})

#: pure data-movement/layout ops — fusible, zero flops
_SHAPE = frozenset({
    "broadcast_in_dim", "reshape", "squeeze", "expand_dims", "transpose",
    "slice", "rev", "pad", "concatenate", "iota", "split",
})

#: reductions — one flop per *input* element; absorb their elementwise
#: input chains (XLA input fusion)
_REDUCE = frozenset({
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod", "reduce_and",
    "reduce_or", "reduce_xor", "argmax", "argmin",
})

_CUMULATIVE = frozenset({"cumsum", "cumprod", "cummax", "cummin",
                         "cumlogsumexp"})

#: free ops — layout changes and dtype converts. They belong to the
#: kernel that *consumes* them (an operand cast/reshape), never to
#: their producer's; one left behind as its own component is a
#: materialized copy.
_FREE = _SHAPE | frozenset({"convert_element_type"})


def _aval_bytes(aval) -> float:
    shape = getattr(aval, "shape", None)
    if shape is None:
        return 0.0
    n = 1
    for d in shape:
        n *= int(d)
    dt = getattr(aval, "dtype", None)
    return float(n) * (dt.itemsize if dt is not None else 4)


def _aval_size(aval) -> int:
    shape = getattr(aval, "shape", None)
    if shape is None:
        return 0
    n = 1
    for d in shape:
        n *= int(d)
    return n


_FLOAT_CACHE: Dict[Any, bool] = {}


def _is_float(aval) -> bool:
    # numpy's dtype.kind is 'V' for ml_dtypes extension floats
    # (bfloat16, fp8) — go through issubdtype, cached per dtype
    dt = getattr(aval, "dtype", None)
    if dt is None:
        return False
    r = _FLOAT_CACHE.get(dt)
    if r is None:
        import jax.numpy as jnp
        r = bool(jnp.issubdtype(dt, jnp.floating))
        _FLOAT_CACHE[dt] = r
    return r


def eqn_flops(eqn) -> float:
    """Static flop estimate of one equation, XLA ``cost_analysis``
    convention: 2·M·N·K for contractions, one per output element for
    floating elementwise, one per input element for reductions, zero
    for data movement."""
    name = eqn.primitive.name
    if name == "dot_general":
        (lc, _rc), _batch = eqn.params["dimension_numbers"]
        lhs = eqn.invars[0].aval
        k = 1
        for i in lc:
            k *= int(lhs.shape[i])
        return 2.0 * _aval_size(eqn.outvars[0].aval) * k
    if name in _REDUCE or name in _CUMULATIVE:
        return float(_aval_size(eqn.invars[0].aval))
    if name in _ELEM:
        out = eqn.outvars[0].aval
        return float(_aval_size(out)) if _is_float(out) else 0.0
    return 0.0


def eqn_bytes(eqn) -> float:
    """Operand + result bytes of one equation (pre-fusion touch
    estimate; the component model turns these into materialized
    traffic)."""
    if eqn.primitive.name == MARK_PRIMITIVE:
        return 0.0
    total = 0.0
    for v in eqn.invars:
        if not hasattr(v, "val"):
            total += _aval_bytes(v.aval)
    for v in eqn.outvars:
        total += _aval_bytes(v.aval)
    return total


# ---------------------------------------------------------------------------
# DCE — match XLA's post-optimization counts (the norms backward's dead
# dW chains must not be charged; the compiler deletes them)
# ---------------------------------------------------------------------------

def dce(closed):
    """Dead-code-eliminate a ClosedJaxpr, keeping every output and
    every invar position (the traffic pass's index maps depend on
    invar order). Falls back to the raw jaxpr if the internal API
    moved."""
    try:
        from jax._src.interpreters import partial_eval as pe
        jaxpr = closed.jaxpr
        used = [True] * len(jaxpr.outvars)
        new_jaxpr, _ = pe.dce_jaxpr(jaxpr, used, instantiate=True)
        return new_jaxpr
    except Exception:
        return closed.jaxpr


# ---------------------------------------------------------------------------
# the walker
# ---------------------------------------------------------------------------

_STRUCTURAL = frozenset({"jit", "shard_map", "scan", "while", "cond"})


@dataclasses.dataclass
class Rec:
    """One recorded equation (or structural container)."""
    idx: int
    eqn: Any
    name: str
    kind: str                   # elem | reduce | barrier | mark | container
    trips: float
    in_taints: Tuple[frozenset, ...]
    container: int              # enclosing container Rec idx, -1 at top
    flops: float = 0.0
    bytes: float = 0.0
    rank: int = 0
    phase: str = PH_FWD
    comp: int = -1              # fusion-component root (union-find)


def _kind(eqn) -> str:
    name = eqn.primitive.name
    if name == MARK_PRIMITIVE:
        return "mark"
    if name in _REDUCE:
        return "reduce"
    if name in _ELEM or name in _SHAPE:
        return "elem"
    return "barrier"


class _TrafficWalker(_J.Walker):
    """Taint + equation recorder for the traffic pass.

    Taint tokens: ``p`` (parameters), ``opt`` (optimizer state), ``b``
    (batch), ``key``/``nz`` (PRNG lineage), ``seed:<kind>`` (backward
    seeds), ``nb:<i>`` (which norms-seeded backward, one token per
    seed marker), ``g:<i>`` (gradient leaf i, from the plan/apply
    boundary markers). ``g:*`` is stripped at scalar reduction outputs
    so a global-norm scalar does not smear every leaf's token over the
    whole apply phase. ``book`` collects (bytes, taint) of each book
    marker.
    """

    def __init__(self):
        super().__init__()
        self.records: List[Rec] = []
        self.trips = 1.0
        self._stack: List[int] = []       # enclosing container Rec idxs
        self.gleaf_sizes: Dict[int, int] = {}
        self.book: List[Tuple[float, frozenset]] = []
        self._nb: Dict[int, str] = {}

    # -- recording ---------------------------------------------------------
    def _record(self, eqn, kind, in_t) -> Rec:
        rec = Rec(idx=len(self.records), eqn=eqn, name=eqn.primitive.name,
                  kind=kind, trips=self.trips, in_taints=tuple(in_t),
                  container=self._stack[-1] if self._stack else -1)
        if kind != "container":
            rec.flops = eqn_flops(eqn)
            rec.bytes = eqn_bytes(eqn)
        self.records.append(rec)
        return rec

    # -- the hook ----------------------------------------------------------
    def hook(self, eqn, in_t):
        name = eqn.primitive.name
        if name == MARK_PRIMITIVE:
            return self._mark(eqn, in_t)

        subs = _J.sub_jaxprs(eqn.params)
        structural = name in _STRUCTURAL or (
            len(subs) == 1
            and len(_J.as_open(subs[0]).invars) == len(in_t))
        if subs and structural:
            if self.recording:
                rec = self._record(eqn, "container", in_t)
                self._stack.append(rec.idx)
                # popped by _eqn's wrapper below
            return None

        # leaf or opaque equation: take it over
        if self.recording:
            self._record(eqn, _kind(eqn), in_t)
        out = frozenset().union(*in_t) if in_t else EMPTY
        # scalar outputs drop gradient-leaf taint: a global-norm scalar
        # is not "the gradient", and smearing every leaf's token over
        # the whole apply chain would make stream counting meaningless
        stripped = frozenset(t for t in out if not t.startswith("g:"))
        return [stripped if _aval_size(ov.aval) <= 1 else out
                for ov in eqn.outvars]

    def _mark(self, eqn, in_t):
        tag = eqn.params["tag"]
        meta = meta_dict(eqn.params["meta"])
        t = in_t[0]
        if tag == TAG_GLEAF:
            leaf = int(meta.get("leaf", -1))
            t = t | {f"g:{leaf}"}
            if self.recording:
                self.gleaf_sizes[leaf] = _aval_size(eqn.outvars[0].aval)
        elif tag == TAG_SEED:
            t = t | {f"seed:{meta.get('kind', '?')}"}
            if meta.get("kind") == "norms":
                t = t | {self._nb.setdefault(id(eqn),
                                             f"nb:{len(self._nb)}")}
        elif tag == TAG_RNG and meta.get("purpose") == "noise":
            t = t | {T_NOISEKEY}
        elif tag == TAG_BOOK and self.recording:
            self.book.append((_aval_bytes(eqn.outvars[0].aval), t))
        if self.recording:
            self._record(eqn, "mark", in_t)
        return [t]

    # -- structural dispatch with trip tracking ----------------------------
    def _eqn(self, eqn, env):
        name = eqn.primitive.name
        depth = len(self._stack)
        old_trips = self.trips
        if name == "scan":
            self.trips = old_trips * float(eqn.params.get("length", 1))
        try:
            super()._eqn(eqn, env)
        finally:
            self.trips = old_trips
            # pop the container frame hook() pushed (if any)
            if self.recording and len(self._stack) > depth:
                self._stack.pop()


# ---------------------------------------------------------------------------
# fusion components
# ---------------------------------------------------------------------------

class _UnionFind:
    def __init__(self, n):
        self.parent = list(range(n))

    def find(self, i):
        while self.parent[i] != i:
            self.parent[i] = self.parent[self.parent[i]]
            i = self.parent[i]
        return i

    def union(self, a, b):
        ra, rb = self.find(a), self.find(b)
        if ra != rb:
            self.parent[rb] = ra


def _build_graph(records: Sequence[Rec]):
    """Producer map (var id → Rec), consumer counts, ranks, and the
    fusion components. Var objects are unique per jaxpr scope, so
    producer/consumer edges exist exactly within one scope — which is
    also where fusion happens."""
    producer: Dict[int, Rec] = {}
    for rec in records:
        if rec.kind == "container":
            continue
        for ov in rec.eqn.outvars:
            if type(ov).__name__ != "DropVar":
                producer[id(ov)] = rec

    consumers: Dict[int, int] = {}
    consumer_recs: Dict[int, List[Rec]] = {}
    for rec in records:
        for v in rec.eqn.invars:
            if hasattr(v, "val"):
                continue
            consumers[id(v)] = consumers.get(id(v), 0) + 1
            consumer_recs.setdefault(id(v), []).append(rec)

    # ranks: how many reduction/barrier boundaries feed an equation
    for rec in records:
        if rec.kind == "container":
            continue
        in_rank = 0
        for v in rec.eqn.invars:
            p = producer.get(id(v))
            if p is not None:
                in_rank = max(in_rank, p.rank)
        rec.rank = in_rank if rec.kind == "elem" else in_rank + 1

    # single-output fusion, XLA-shaped: free ops (casts, reshapes) ride
    # into the kernel that consumes them; elementwise chains of equal
    # rank form one loop fusion; a reduction absorbs its elementwise
    # input chain. No multi-output fusion — a value needed both by a
    # reduction and past it (the unfused apply's signature shape) is
    # materialized, which is exactly the traffic this pass certifies.
    # A producer fuses forward only if EVERY consumer of the value
    # would land in the same kernel — otherwise the value crosses HBM.
    def _all_consumers_fuse(v, p):
        for c in consumer_recs.get(id(v), ()):
            if c.kind in ("container", "mark") or c.name in _FREE:
                return False
            if c.kind == "elem" and c.rank == p.rank:
                continue
            if c.kind == "reduce" and c.rank == p.rank + 1:
                continue
            return False
        return True

    uf = _UnionFind(len(records))
    for rec in records:
        if rec.kind in ("container", "mark"):
            continue
        for v in rec.eqn.invars:
            p = producer.get(id(v))
            if p is None or p.kind in ("container", "mark"):
                continue
            if p.name in _FREE:
                if consumers.get(id(v), 0) == 1:
                    uf.union(p.idx, rec.idx)   # operand cast/layout
            elif rec.name in _FREE:
                pass                           # free ops never pull
            elif p.kind == "elem" and rec.kind in ("elem", "reduce") \
                    and _all_consumers_fuse(v, p):
                uf.union(p.idx, rec.idx)
    for rec in records:
        rec.comp = uf.find(rec.idx)
    return producer, consumers


# ---------------------------------------------------------------------------
# phase attribution
# ---------------------------------------------------------------------------

def _phase(rec: Rec, batch_size: int, stat_elems: int,
           group_of: Dict[int, frozenset]) -> str:
    union = frozenset().union(*rec.in_taints) if rec.in_taints else EMPTY
    if any(t.startswith("g:") for t in union) or T_OPT in union \
            or T_NOISEKEY in union:
        return PH_APPLY
    seeds = {t for t in union if t.startswith("seed:")}
    groups = group_of.get(rec.idx, frozenset())
    if not seeds:
        # parameter/batch-only work that does not feed the loss is
        # either norms-only statistics or a backward region's remat
        # recompute — charged to the phase that demanded it, the way
        # rematerialization is normally accounted
        if groups and "loss_vec" not in groups:
            if groups <= frozenset({"sq_norms", "gns"}):
                return PH_STATS
            return PH_ACT
        return PH_FWD
    if groups and groups <= frozenset({"sq_norms", "gns", "loss_vec"}):
        return PH_STATS
    if "seed:norms" in seeds and rec.kind != "container":
        out = rec.eqn.outvars[0].aval
        if _aval_size(out) <= stat_elems:
            return PH_STATS
    if rec.kind != "container":
        shape = getattr(rec.eqn.outvars[0].aval, "shape", ())
        if batch_size not in tuple(int(d) for d in shape):
            return PH_WEIGHT
    return PH_ACT


def _needed_by(records: Sequence[Rec], top_outvars, out_labels):
    """Which output field(s) each record feeds: reverse reachability
    over the record list, crossing container boundaries by seeding each
    body's outvars from the call site's outvars (1:1 for jit /
    shard_map / scan / while / cond). Iterated to fixpoint — one
    reverse sweep resolves one nesting level."""
    var_groups: Dict[int, frozenset] = {}
    for v, (field, _rest) in zip(top_outvars, out_labels):
        var_groups[id(v)] = var_groups.get(id(v), frozenset()) \
            | frozenset({field})
    group_of: Dict[int, frozenset] = {}
    for _ in range(8):
        changed = False
        for rec in reversed(records):
            g = frozenset()
            for ov in rec.eqn.outvars:
                g |= var_groups.get(id(ov), frozenset())
            if g != group_of.get(rec.idx, EMPTY):
                group_of[rec.idx] = g
                changed = True
            if rec.kind == "container":
                for sub in _J.sub_jaxprs(rec.eqn.params):
                    body = _J.as_open(sub)
                    if len(body.outvars) != len(rec.eqn.outvars):
                        continue
                    for bv, ov in zip(body.outvars, rec.eqn.outvars):
                        if hasattr(bv, "val"):
                            continue
                        og = var_groups.get(id(ov), frozenset())
                        nv = var_groups.get(id(bv), frozenset()) | og
                        if nv != var_groups.get(id(bv), frozenset()):
                            var_groups[id(bv)] = nv
                            changed = True
            for v in rec.eqn.invars:
                if hasattr(v, "val"):
                    continue
                nv = var_groups.get(id(v), frozenset()) | g
                if nv != var_groups.get(id(v), frozenset()):
                    var_groups[id(v)] = nv
                    changed = True
        if not changed:
            break
    return group_of


# ---------------------------------------------------------------------------
# the report
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TrafficReport:
    """Traffic attribution of one traced training step."""
    granularity: str
    optimizer: str
    plan_desc: str
    n_leaves: int
    flops: float                # trips-weighted static flop count
    flops_hlo: float            # loop bodies once — cost_analysis scale
    hbm_bytes: float            # fusion-aware materialized traffic
    coll_bytes: float           # psum operand bytes
    phase_flops: Tuple[Tuple[str, float], ...]
    phase_bytes: Tuple[Tuple[str, float], ...]
    n_streams: int              # full-gradient HBM passes after the plan
    expected_streams: int       # what plan + optimizer structurally need
    forward_flops: float
    ref_forward_flops: float
    residual_sharing: float     # [0, 1]; -1 when not applicable
    findings: Tuple[Finding, ...]
    allowlisted: Tuple[Finding, ...]    # known waste, tracked not failed

    @property
    def errors(self) -> Tuple[Finding, ...]:
        return tuple(f for f in self.findings if f.severity == ERROR)

    @property
    def ok(self) -> bool:
        return not self.errors

    def summary(self) -> str:
        ph = ", ".join(f"{k}={v / 1e6:.1f}MB"
                       for k, v in self.phase_bytes if v)
        head = (f"traffic[{self.granularity}/{self.optimizer}]: "
                f"{self.flops_hlo:.3g} flops, "
                f"{self.hbm_bytes / 1e6:.1f} MB materialized ({ph}); "
                f"gradient streams {self.n_streams} "
                f"(expected {self.expected_streams}, "
                f"{len(self.allowlisted)} allowlisted)")
        return "\n".join([head] + [f"  {f.render()}" for f in
                                   self.findings + self.allowlisted])

    def to_json(self) -> dict:
        return {
            "granularity": self.granularity, "optimizer": self.optimizer,
            "plan": self.plan_desc, "n_leaves": self.n_leaves,
            "flops": self.flops, "flops_hlo": self.flops_hlo,
            "hbm_bytes": self.hbm_bytes, "coll_bytes": self.coll_bytes,
            "phase_flops": dict(self.phase_flops),
            "phase_bytes": dict(self.phase_bytes),
            "n_streams": self.n_streams,
            "expected_streams": self.expected_streams,
            "forward_flops": self.forward_flops,
            "ref_forward_flops": self.ref_forward_flops,
            "residual_sharing": self.residual_sharing,
            "findings": [f.to_json() for f in self.findings],
            "allowlisted": [f.to_json() for f in self.allowlisted],
        }


#: duplicate-forward fires above this multiple of the expected forward
FWD_TOL = 1.5
#: dead-residual fires below this shared fraction of residual bytes
RESIDUAL_TOL = 0.25


#: full-gradient passes each optimizer's own update structurally makes
#: today: AdamW reads the gradient once into its fused moment/update
#: loop; Adafactor makes four — the fused row+column mean reduction
#: (one g² chain feeds both means), the update build (g · rsqrt(v̂)),
#: and the RMS clip's reduce + rescale over the leaf-sized update.
_OPT_STREAMS = {"adamw": 1, "adafactor": 4}


def expected_streams(plan, optimizer: str,
                     global_clip: Optional[float]) -> int:
    """Full-gradient HBM passes the current (unfused) apply path
    structurally requires: the optimizer's update read(s), plus one
    for its global-norm clip reduction, plus one for the DP noise add.
    The fused-apply ROADMAP item collapses these to 1."""
    if optimizer == "none" or not plan.needs_grads:
        return 0
    n = _OPT_STREAMS.get(optimizer, 1)
    if global_clip is not None:
        n += 1
    if plan.noise is not None:
        n += 1
    return n


def _walk(closed, in_taints):
    walker = _TrafficWalker()
    walker.out_taints = walker.run(closed, in_taints)
    return walker


def _nb_tokens(taint) -> frozenset:
    return frozenset(t for t in taint if t.startswith("nb:"))


def program_flops(closed) -> Tuple[float, float]:
    """(flops_hlo, flops_total) of a ClosedJaxpr after DCE —
    ``flops_hlo`` counts loop bodies once (XLA ``cost_analysis``
    convention), ``flops_total`` weights them by trip count."""
    jaxpr = dce(closed)
    walker = _walk(jaxpr, [EMPTY] * len(jaxpr.invars))
    once = sum(r.flops for r in walker.records)
    total = sum(r.flops * r.trips for r in walker.records)
    return once, total


def analyze_trace(trace: _J.TrainTrace, *,
                  allow_known_streams: bool = True) -> TrafficReport:
    """Run the traffic pass on one ``TrainTrace``."""
    plan = trace.plan
    jaxpr = dce(trace.closed)
    in_t = [EMPTY] * len(jaxpr.invars)
    for i in trace.param_positions:
        in_t[i] = frozenset({T_PARAM})
    for i in trace.opt_positions:
        in_t[i] = frozenset({T_OPT})
    for i in trace.batch_positions:
        in_t[i] = frozenset({T_BATCH})
    for i in trace.rng_positions:
        in_t[i] = frozenset({T_KEY})
    walker = _walk(jaxpr, in_t)
    records = walker.records
    producer, consumers = _build_graph(records)

    # -- phases ------------------------------------------------------------
    stat_elems = trace.batch_size * max(trace.seq or 1, 64)
    group_of = _needed_by(records, _J.as_open(jaxpr).outvars,
                          trace.out_labels)
    for rec in records:
        rec.phase = _phase(rec, trace.batch_size, stat_elems, group_of)

    real = [r for r in records if r.kind not in ("container", "mark")]
    phase_flops = {ph: 0.0 for ph in PHASES}
    phase_bytes = {ph: 0.0 for ph in PHASES}
    for r in real:
        phase_flops[r.phase] += r.flops * r.trips

    # -- fusion-aware materialized bytes -----------------------------------
    comp_members: Dict[int, List[Rec]] = {}
    for r in real:
        comp_members.setdefault(r.comp, []).append(r)
    hbm_bytes = 0.0
    for comp, members in comp_members.items():
        member_ids = {m.idx for m in members}
        seen_in: set = set()
        ext = 0.0
        for m in members:
            for v in m.eqn.invars:
                if hasattr(v, "val") or id(v) in seen_in:
                    continue
                p = producer.get(id(v))
                if p is not None and p.idx in member_ids:
                    continue
                seen_in.add(id(v))
                ext += _aval_bytes(v.aval)
            for ov in m.eqn.outvars:
                if type(ov).__name__ == "DropVar":
                    continue
                p_used = consumers.get(id(ov), 0)
                internal = sum(
                    1 for m2 in members for v2 in m2.eqn.invars
                    if id(v2) == id(ov))
                if p_used > internal or p_used == 0:
                    ext += _aval_bytes(ov.aval)
        trips = members[0].trips
        hbm_bytes += ext * trips
        for m in members:
            phase_bytes[m.phase] += (ext * trips) / len(members)

    coll_bytes = sum(
        sum(_aval_bytes(v.aval) for v in r.eqn.invars
            if not hasattr(v, "val")) * r.trips
        for r in real if r.name in ("psum", "ppermute", "all_gather",
                                    "psum_scatter", "all_to_all"))

    # -- gradient streams --------------------------------------------------
    leaf_sizes = walker.gleaf_sizes
    streams: Dict[int, set] = {i: set() for i in leaf_sizes}
    convert_only: Dict[int, bool] = {
        comp: all(m.name in ("convert_element_type", MARK_PRIMITIVE)
                  for m in members)
        for comp, members in comp_members.items()}
    for r in real:
        if r.phase != PH_APPLY or convert_only.get(r.comp, False):
            continue
        member_ids = {m.idx for m in comp_members[r.comp]}
        for v, t in zip(r.eqn.invars, r.in_taints):
            if hasattr(v, "val"):
                continue
            p = producer.get(id(v))
            if p is not None and p.idx in member_ids:
                continue
            sz = _aval_size(v.aval)
            for tok in t:
                if tok.startswith("g:"):
                    i = int(tok.split(":", 1)[1])
                    if leaf_sizes.get(i) == sz:
                        streams[i].add(r.comp)

    n_streams = max((len(s) for s in streams.values()), default=0)
    expected = expected_streams(plan, trace.optimizer, trace.global_clip)

    findings: List[Finding] = []
    allowlisted: List[Finding] = []
    worst = max(streams, key=lambda i: len(streams[i]), default=None)
    worst_label = trace.param_labels[worst] \
        if worst is not None and worst < len(trace.param_labels) else None
    if n_streams > expected:
        findings.append(Finding(
            PASS, ERROR, "redundant-hbm-stream",
            f"{n_streams} full-gradient HBM streams after the plan "
            f"boundary where the plan + {trace.optimizer} apply "
            f"structurally need {expected}: an extra pass over every "
            f"gradient leaf is {n_streams - expected}× more HBM "
            f"traffic than the step budget", leaf=worst_label))
    elif n_streams == expected and expected > 1:
        parts = []
        if plan.noise is not None:
            parts.append("noise add")
        if trace.global_clip is not None:
            parts.append("global-norm clip")
        parts.append(f"{trace.optimizer} update")
        f = Finding(
            PASS, ERROR, "redundant-hbm-stream",
            f"the apply path streams every gradient {n_streams}× "
            f"({', '.join(parts)} each re-read the full gradient) — "
            f"the known unfused-apply waste; see the ROADMAP fused "
            f"DP-SGD apply item (one Pallas kernel per param, one HBM "
            f"pass)", leaf=worst_label)
        (allowlisted if allow_known_streams else findings).append(f)

    # -- duplicate forward -------------------------------------------------
    fwd_flops = phase_flops[PH_FWD]
    ref_flops = 0.0
    if trace.ref_closed is not None:
        _, ref_flops = program_flops(trace.ref_closed)
        factor = 1.0
        if plan.importance is not None:
            factor += plan.importance.k / float(trace.batch_size)
        if ref_flops > 0 and fwd_flops > FWD_TOL * factor * ref_flops:
            findings.append(Finding(
                PASS, ERROR, "duplicate-forward",
                f"forward-phase flops ({fwd_flops:.3g}) are "
                f"{fwd_flops / ref_flops:.2f}× the plain forward "
                f"({ref_flops:.3g}); the fused plan owes exactly one "
                f"forward ({factor:.1f} regions expected) — a consumer "
                f"is re-running the model"))

    # -- residual sharing --------------------------------------------------
    sharing = -1.0
    if plan.n_backwards == 2 and plan.importance is None and walker.book:
        # the booked form: its contraction reads the book, not forward
        # residuals — the entries a backward made (Z̄, per-example
        # gradients; H is the forward's own) must come from the norms
        # backward whose norms the step returns, not another
        # linearization's
        norms_nb = frozenset().union(*(
            _nb_tokens(t) for t, (field, _) in
            zip(walker.out_taints, trace.out_labels) if field == "sq_norms"))
        made = [(b, _nb_tokens(t)) for b, t in walker.book if _nb_tokens(t)]
        total_b = sum(b for b, _ in made)
        shared = sum(b for b, nb in made if nb <= norms_nb)
        sharing = shared / total_b if total_b > 0 else 0.0
        if sharing < RESIDUAL_TOL:
            findings.append(Finding(
                PASS, ERROR, "dead-residual",
                f"the booked contraction reads {total_b / 1e6:.1f} MB of "
                f"book but only {sharing:.0%} of it was kept by the norms "
                f"backward whose norms the step returns — the book comes "
                f"from a second linearization"))
    elif plan.n_backwards == 2 and plan.importance is None:
        fwd_out = {}
        for r in records:
            if r.phase == PH_FWD:
                for ov in r.eqn.outvars:
                    if type(ov).__name__ != "DropVar" \
                            and _aval_size(ov.aval) > trace.batch_size:
                        fwd_out[id(ov)] = _aval_bytes(ov.aval)
        norms_bytes: Dict[int, float] = {}
        wt_bytes: Dict[int, float] = {}
        for r in records:
            union = frozenset().union(*r.in_taints) if r.in_taints \
                else EMPTY
            is_n = "seed:norms" in union
            is_w = "seed:weighted" in union
            if not (is_n or is_w):
                continue
            for v in r.eqn.invars:
                b = fwd_out.get(id(v))
                if b is None:
                    continue
                if is_n:
                    norms_bytes[id(v)] = b
                if is_w:
                    wt_bytes[id(v)] = b
        total_w = sum(wt_bytes.values())
        shared = sum(b for vid, b in wt_bytes.items()
                     if vid in norms_bytes)
        has_weighted = any(
            "seed:weighted" in (frozenset().union(*r.in_taints)
                                if r.in_taints else EMPTY)
            for r in records)
        if has_weighted:
            # a weighted backward that touches NO forward residuals at
            # all is the anomaly itself — it linearized a second time
            sharing = shared / total_w if total_w > 0 else 0.0
            if sharing < RESIDUAL_TOL:
                findings.append(Finding(
                    PASS, ERROR, "dead-residual",
                    f"the reweighted backward reads "
                    f"{total_w / 1e6:.1f} MB of forward activations but "
                    f"shares only {sharing:.0%} of them with the norms "
                    f"backward — the two backwards are not running over "
                    f"one linearization's residuals (a second vjp "
                    f"doubles residual traffic)"))

    # -- upcast materialization --------------------------------------------
    leaf_size_set = set(leaf_sizes.values())
    for r in real:
        if r.phase != PH_APPLY or r.name != "convert_element_type":
            continue
        if not convert_only.get(r.comp, False):
            continue
        v = r.eqn.invars[0]
        if hasattr(v, "val"):
            continue
        out = r.eqn.outvars[0].aval
        if not (_is_float(out) and _is_float(v.aval)):
            continue
        if out.dtype.itemsize <= v.aval.dtype.itemsize:
            continue
        union = r.in_taints[0]
        if _aval_size(v.aval) in leaf_size_set \
                and any(t.startswith("g:") for t in union):
            findings.append(Finding(
                PASS, ERROR, "upcast-materialization",
                f"a {out.dtype.name} copy of a {v.aval.dtype.name} "
                f"gradient leaf is materialized as its own HBM pass "
                f"(the convert fuses into nothing) — upcast inside the "
                f"consumer loop instead of copying the tree"))
            break

    return TrafficReport(
        granularity=trace.granularity, optimizer=trace.optimizer,
        plan_desc=plan.describe(), n_leaves=len(trace.param_labels),
        flops=sum(r.flops * r.trips for r in real),
        flops_hlo=sum(r.flops for r in real),
        hbm_bytes=hbm_bytes, coll_bytes=coll_bytes,
        phase_flops=tuple(sorted(phase_flops.items())),
        phase_bytes=tuple(sorted(phase_bytes.items())),
        n_streams=n_streams, expected_streams=expected,
        forward_flops=fwd_flops, ref_forward_flops=ref_flops,
        residual_sharing=sharing,
        findings=tuple(findings), allowlisted=tuple(allowlisted))


def check_train_step(loss_fn, params, batch, consumers, **kw):
    """Convenience: trace one training step and analyze it."""
    allow = kw.pop("allow_known_streams", True)
    return analyze_trace(
        _J.trace_train_step(loss_fn, params, batch, consumers, **kw),
        allow_known_streams=allow)
