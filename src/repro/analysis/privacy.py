"""Privacy-flow verification of a traced DP step (pexlint pass,
DESIGN.md §12).

The DP-SGD guarantees of the plan layer are *program* properties: every
trained leaf's gradient must be scaled by the per-example clip
coefficient before any batch sum, Gaussian noise must enter exactly
once — after the cross-device psum — at stddev σ·C, and no PRNG key
may be consumed twice. This pass proves them on the closed jaxpr of a
full ``Engine.step`` (``analysis._jaxpr.trace_step``), anchored on the
``pex_mark`` provenance markers production code plants on every
privacy-critical value (``core.provenance``):

**Lineage lattice.** A forward taint walk labels every variable with
the subset of {``seed:plain``, ``seed:norms``, ``seed:weighted``,
``clip``, ``noise:<site>``, ``key``} it derives from. Markers are the
semantics: a ``clip_coef`` marker *replaces* its input taint with
{``clip``} (the coefficient is a function of the norms, but values
scaled by it are clipped — the laundering is what distinguishes
"derived from the norms backward" from "an unweighted seed reached the
gradient"); a ``grad_seed`` marker keeps only the clip evidence and
adds its kind; a ``noise`` marker replaces taint with its own site
token (each leaf's noise is one marker — counting tokens per gradient
leaf is the exactly-once proof); ``rng_use`` markers and the consumer
key invars carry ``key``; a ``book`` marker (an array of the norms
backward's book, ``core.book``, where the weighted contraction reads
it) replaces its taint with {``book``}: per-example values, not yet
summed, whose lineage to the norms seed is not a seed reaching the
gradient — the contraction must still bring ``clip`` and
``seed:weighted`` in; a ``book_fold`` marker adds ``fold`` where its
input is a book value (``book``) already scaled by the weighted seed
(``seed:weighted``) and still holds its batch axis (leading size =
the marker's batch) — the evidence that the weights came in before
the contraction summed over the batch.

**Checks** (conditional on what the plan declares):

  * clip ⇒ every gradient leaf carries ``clip`` and ``seed:weighted``
    and no plain/norms seed — i.e. the only backward that built it was
    the clip-weighted one, which scales per example *before* the batch
    sum by AD linearity (or the book's contraction, which folds the
    same weighted seed into each per-example entry before it sums over
    the batch: a leaf that carries ``book`` must carry ``fold``); the
    clip marker's own input must carry
    ``seed:norms`` (coefficients computed from the per-example norms
    backward) and its meta must match the plan's C;
  * noise ⇒ exactly one noise token per leaf, one marker per leaf
    overall, every marker at shard_map depth 0 (outside the region ⇒
    after the gradient psum), meta (σ, scale) equal to the plan's σ
    and sensitivity (C when defaulted);
  * keys: every ``rng_use`` marker's lineage is resolved backwards
    (through splits, folds, slices) to an origin — two markers sharing
    an origin is a key reuse; ``random_bits`` fed by anything not
    key-tainted, or a ``random_seed`` born inside the step, is unkeyed
    randomness (irreproducible under replay).

Trace-only; abstract params/batches/keys all work.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

from repro.analysis import _jaxpr as _J
from repro.analysis.findings import ERROR, WARNING, Finding
from repro.core.provenance import (KNOWN_TAGS, MARK_PRIMITIVE, TAG_BOOK,
                                   TAG_CLIP, TAG_FOLD, TAG_GLEAF, TAG_NOISE,
                                   TAG_RNG, TAG_SAMPLE, TAG_SEED, meta_dict)

PASS = "privacy"
_EMPTY = _J.EMPTY

#: taint tokens
T_CLIP = "clip"
T_KEY = "key"
T_BOOK = "book"
T_FOLD = "fold"


def _seed_tok(kind: str) -> str:
    return f"seed:{kind}"


@dataclasses.dataclass(frozen=True)
class MarkSite:
    """One provenance marker met during the walk."""
    index: int
    tag: str
    meta: dict
    depth: int                      # enclosing shard_map regions
    in_taint: frozenset
    token: Optional[str] = None     # the taint token this site emits


@dataclasses.dataclass(frozen=True)
class LeafLineage:
    """What one gradient leaf's value derives from."""
    path: str
    taint: frozenset

    @property
    def noise_tokens(self) -> Tuple[str, ...]:
        return tuple(sorted(t for t in self.taint if t.startswith("noise:")))


@dataclasses.dataclass(frozen=True)
class PrivacyReport:
    marks: Tuple[MarkSite, ...]
    leaves: Tuple[LeafLineage, ...]
    findings: Tuple[Finding, ...]

    @property
    def errors(self) -> Tuple[Finding, ...]:
        return tuple(f for f in self.findings if f.severity == ERROR)

    @property
    def ok(self) -> bool:
        return not self.errors

    def summary(self) -> str:
        by_tag: Dict[str, int] = {}
        for m in self.marks:
            by_tag[m.tag] = by_tag.get(m.tag, 0) + 1
        head = (f"privacy: {len(self.leaves)} gradient leaves, markers "
                + (", ".join(f"{k}×{v}" for k, v in sorted(by_tag.items()))
                   or "none"))
        return "\n".join([head] + [f"  {f.render()}" for f in self.findings])


# ---------------------------------------------------------------------------
# the walker
# ---------------------------------------------------------------------------

class _PrivacyWalker(_J.Walker):
    """Marker-anchored taint propagation + random-primitive audit."""

    def __init__(self):
        super().__init__()
        self.marks: List[MarkSite] = []
        self.findings: List[Finding] = []
        self._noise_tok: Dict[int, str] = {}   # id(eqn) -> stable token

    def hook(self, eqn, in_t):
        name = eqn.primitive.name
        if name == MARK_PRIMITIVE:
            return self._mark(eqn, in_t)
        if name == "random_bits" and self.recording:
            if not any(T_KEY in t for t in in_t):
                self.findings.append(Finding(
                    PASS, ERROR, "unkeyed-randomness",
                    "a random_bits draw is fed by no consumer-supplied "
                    "PRNG key: its output is not a function of the step's "
                    "declared keys, so the step is not replayable"))
            return None
        if name == "random_seed" and self.recording:
            self.findings.append(Finding(
                PASS, ERROR, "unkeyed-randomness",
                "a PRNG key is created from a raw seed inside the traced "
                "step; keys must enter as consumer arguments "
                "(Noise/Importance rng) so replay and the single-use "
                "check can see their lineage"))
            return None
        return None

    def _mark(self, eqn, in_t):
        tag = eqn.params["tag"]
        meta = meta_dict(eqn.params["meta"])
        t_in = in_t[0]
        if tag == TAG_CLIP:
            token = T_CLIP
            out = frozenset({T_CLIP})
        elif tag == TAG_SEED:
            token = _seed_tok(meta.get("kind", "?"))
            # keep only the clip evidence: a weighted seed built from
            # clip coefficients proves per-example scaling; the rest of
            # its data lineage (norms, batch) is not seed lineage
            out = (t_in & frozenset({T_CLIP})) | {token}
        elif tag == TAG_NOISE:
            token = self._noise_tok.setdefault(
                id(eqn), f"noise:{len(self._noise_tok)}")
            out = frozenset({token})
        elif tag == TAG_RNG:
            token = T_KEY
            out = t_in | {T_KEY}
        elif tag == TAG_BOOK:
            # per-example book values: their norms-seed lineage is what
            # they are, not an unweighted seed reaching the batch sum
            token = T_BOOK
            out = frozenset({T_BOOK})
        elif tag == TAG_FOLD:
            # the weights met a book value while its batch axis was whole
            shape = eqn.invars[0].aval.shape
            folded = T_BOOK in t_in and _seed_tok("weighted") in t_in \
                and shape[:1] == (meta.get("batch"),)
            token = T_FOLD if folded else None
            out = t_in | {T_FOLD} if folded else t_in
        elif tag == TAG_GLEAF:
            # the plan/apply boundary marker (traffic pass anchor) —
            # pure identity for privacy lineage: the leaf's taint is
            # whatever the plan built it from
            token = None
            out = t_in
        elif tag == TAG_SAMPLE:
            # selection boundary: which examples were drawn depends on
            # the norms, but a gather does not *scale* anything — seed
            # lineage is laundered so norm-guided sampling is not
            # mistaken for an unclipped contribution
            token = None
            out = _EMPTY
        else:
            token = None
            out = t_in
            if self.recording:
                self.findings.append(Finding(
                    PASS, ERROR, "unknown-marker",
                    f"pex_mark tag {tag!r} is not one of {sorted(KNOWN_TAGS)}"
                    f"; a marker was added without teaching the privacy "
                    f"pass its semantics"))
        if self.recording:
            self.marks.append(MarkSite(len(self.marks), tag, meta,
                                       self.region_depth, t_in, token))
        return [out]


# ---------------------------------------------------------------------------
# rng key lineage — single-use resolution
# ---------------------------------------------------------------------------

#: primitives a key passes through unchanged (lineage-transparent)
_PASSTHROUGH = frozenset({
    MARK_PRIMITIVE, "squeeze", "reshape", "broadcast_in_dim",
    "convert_element_type", "copy", "random_wrap", "random_unwrap",
})
#: primitives that *derive* a fresh key — lineage stops here; distinct
#: outputs of one derivation are told apart by the slice path
_DERIVE = frozenset({"random_split", "random_fold_in", "random_seed",
                     "threefry2x32"})


def _origin(var, producer, path):
    """Resolve a key variable back to (origin, slice-path)."""
    while True:
        eqn = producer.get(var)
        if eqn is None:                      # invar / constvar
            return ("free", id(var)), tuple(path)
        name = eqn.primitive.name
        if name in _PASSTHROUGH:
            var = eqn.invars[0]
            continue
        if name == "slice":
            path.append(("slice", tuple(eqn.params["start_indices"])))
            var = eqn.invars[0]
            continue
        if name == "dynamic_slice":
            starts = tuple(
                v.val.item() if hasattr(v, "val") and hasattr(v.val, "item")
                else None
                for v in eqn.invars[1:])
            if any(s is None for s in starts):
                return ("opaque", id(eqn)), tuple(path)
            path.append(("slice", starts))
            var = eqn.invars[0]
            continue
        if name == "random_fold_in":
            # fold_in with a *literal* datum is a pure function of its
            # input key — treat it like a slice so two separate
            # fold_in(master, 7) calls resolve to the SAME origin (true
            # reuse, detected) while fold_in(master, 7) vs
            # fold_in(master, 8) stay distinct (the per-tenant key
            # derivation the multi-tenant Noise path relies on). A
            # traced datum (e.g. a vmapped tenant-id array) stays an
            # opaque derivation point below.
            datum = eqn.invars[1]
            if hasattr(datum, "val"):
                try:
                    path.append(("fold", int(datum.val)))
                    var = eqn.invars[0]
                    continue
                except (TypeError, ValueError):
                    pass
            return (name, id(eqn)), tuple(path)
        if name in _DERIVE:
            return (name, id(eqn)), tuple(path)
        return ("opaque", id(eqn)), tuple(path)


def _rng_use_origins(jaxpr, out, _depth=0):
    """Collect (origin, purpose-meta) for every rng_use marker, per
    jaxpr level (lineage is resolved within the marker's own jaxpr;
    keys entering a sub-jaxpr stop at its invar — two markers on the
    same sub-jaxpr invar still collide, which is the sound direction)."""
    jaxpr = _J.as_open(jaxpr)
    producer = {}
    for eqn in jaxpr.eqns:
        for ov in eqn.outvars:
            if type(ov).__name__ != "DropVar":
                producer[ov] = eqn
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == MARK_PRIMITIVE and \
                eqn.params["tag"] == TAG_RNG:
            origin = _origin(eqn.invars[0], producer, [])
            out.append((origin, meta_dict(eqn.params["meta"])))
        for sub in _J.sub_jaxprs(eqn.params):
            _rng_use_origins(sub, out, _depth + 1)
    return out


# ---------------------------------------------------------------------------
# the pass
# ---------------------------------------------------------------------------

def analyze_trace(trace: _J.StepTrace) -> PrivacyReport:
    """Run the privacy-flow checks on one ``StepTrace``."""
    plan = trace.plan
    jaxpr = trace.closed.jaxpr
    walker = _PrivacyWalker()
    in_t = [_EMPTY] * len(jaxpr.invars)
    for pos in trace.rng_positions:
        in_t[pos] = frozenset({T_KEY})
    out_t = walker.run(jaxpr, in_t)

    findings = list(walker.findings)
    marks = walker.marks
    clip_marks = [m for m in marks if m.tag == TAG_CLIP]
    noise_marks = [m for m in marks if m.tag == TAG_NOISE]
    rng_marks = [m for m in marks if m.tag == TAG_RNG]

    # -- leaf lineage ------------------------------------------------------
    leaves = [LeafLineage(rest, out_t[i])
              for i, (head, rest) in enumerate(trace.out_labels)
              if head == "grads"]

    # -- clip: per-example scaling before the batch sum --------------------
    if plan.clip is not None:
        gran = plan.clip.granularity
        if not clip_marks:
            findings.append(Finding(
                PASS, ERROR, "clip-missing",
                f"plan declares Clip({plan.clip.clip_norm}) but the trace "
                f"contains no clip_coef marker: no per-example clip "
                f"coefficient was ever computed"))
        for m in clip_marks:
            if m.meta.get("clip_norm") != plan.clip.clip_norm:
                findings.append(Finding(
                    PASS, ERROR, "clip-norm-mismatch",
                    f"clip coefficients use C={m.meta.get('clip_norm')} "
                    f"but the plan declares C={plan.clip.clip_norm}"))
            if m.meta.get("granularity") != gran:
                findings.append(Finding(
                    PASS, ERROR, "clip-granularity-mismatch",
                    f"clip coefficients are "
                    f"{m.meta.get('granularity')}-granular but the plan "
                    f"declares {gran} clipping"))
            if _seed_tok("norms") not in m.in_taint:
                findings.append(Finding(
                    PASS, ERROR, "clip-not-from-norms",
                    "clip coefficients are not derived from the "
                    "norms-seeded backward: min(1, C/‖g‖) must be a "
                    "function of the per-example gradient norms"))
        for lf in leaves:
            # frozen leaf: constant-zero gradient — no backward seed
            # ever reaches it, so its only lineage (if any) is the
            # noise sample added to every leaf (a frozen LoRA base
            # under stop_gradient). Nothing per-example flows into the
            # batch sum through it; skip the clip requirement.
            if not any(t in (T_CLIP, T_BOOK) or t.startswith("seed:")
                       for t in lf.taint):
                continue
            if T_CLIP not in lf.taint or \
                    _seed_tok("weighted") not in lf.taint or \
                    (T_BOOK in lf.taint and T_FOLD not in lf.taint):
                findings.append(Finding(
                    PASS, ERROR, "unclipped-leaf",
                    "gradient is not scaled by the per-example clip "
                    "coefficient before the batch sum (no clip-weighted "
                    "seed in its lineage) — DP sensitivity is unbounded "
                    "for this leaf", leaf=lf.path))
            stray = {_seed_tok("plain"), _seed_tok("norms")} & lf.taint
            if stray:
                findings.append(Finding(
                    PASS, ERROR, "unclipped-leaf",
                    f"an unweighted backward seed ({', '.join(sorted(stray))}"
                    f") reaches this gradient: some per-example "
                    f"contribution enters the batch sum unclipped",
                    leaf=lf.path))
    elif clip_marks:
        findings.append(Finding(
            PASS, WARNING, "unexpected-clip",
            f"{len(clip_marks)} clip_coef marker(s) in a plan that "
            f"declares no Clip consumer"))

    # -- noise: exactly once, after the psum, at σ·C -----------------------
    want_noise = plan.noise is not None and plan.needs_grads
    if want_noise:
        sens = plan.noise.scale if plan.noise.scale is not None \
            else plan.clip.clip_norm
        if len(noise_marks) != len(leaves):
            findings.append(Finding(
                PASS, ERROR, "noise-count",
                f"{len(noise_marks)} noise marker(s) for {len(leaves)} "
                f"gradient leaves; DP-SGD noises every leaf exactly once"))
        for m in noise_marks:
            if m.depth > 0:
                findings.append(Finding(
                    PASS, ERROR, "noise-before-psum",
                    "noise is injected inside a shard_map region — i.e. "
                    "per shard, BEFORE the cross-device gradient psum: "
                    "summing shard-local noise inflates the variance by "
                    "the shard count and breaks the σ·C calibration"))
            if m.meta.get("noise_std") != plan.noise.noise_std:
                findings.append(Finding(
                    PASS, ERROR, "noise-scale-mismatch",
                    f"noise marker carries σ={m.meta.get('noise_std')} but "
                    f"the plan declares σ={plan.noise.noise_std}"))
            if m.meta.get("scale") != sens:
                findings.append(Finding(
                    PASS, ERROR, "noise-scale-mismatch",
                    f"noise marker carries sensitivity "
                    f"{m.meta.get('scale')} but the plan's sensitivity is "
                    f"{sens} (σ·C calibration)"))
        for lf in leaves:
            n = len(lf.noise_tokens)
            if n == 0 and lf.taint:
                findings.append(Finding(
                    PASS, ERROR, "noise-missing",
                    "no noise sample reaches this gradient leaf",
                    leaf=lf.path))
            elif n > 1:
                findings.append(Finding(
                    PASS, ERROR, "double-noise",
                    f"{n} independent noise samples reach this gradient "
                    f"leaf; noising twice doubles the variance while the "
                    f"accountant assumes σ·C", leaf=lf.path))
    else:
        if noise_marks:
            findings.append(Finding(
                PASS, ERROR, "unexpected-noise",
                f"{len(noise_marks)} noise marker(s) in a plan that "
                f"declares no Noise consumer"))
        for lf in leaves:
            if lf.noise_tokens:
                findings.append(Finding(
                    PASS, ERROR, "unexpected-noise",
                    "a noise sample reaches this gradient leaf but the "
                    "plan declares no Noise consumer", leaf=lf.path))

    # -- keys: single use --------------------------------------------------
    origins: list = []
    _rng_use_origins(jaxpr, origins)
    by_origin: Dict[tuple, list] = {}
    for origin, meta in origins:
        by_origin.setdefault(origin, []).append(meta)
    for origin, metas in by_origin.items():
        if len(metas) > 1:
            uses = ", ".join(
                f"{m.get('purpose')}[{m.get('index')}]" for m in metas)
            findings.append(Finding(
                PASS, ERROR, "key-reuse",
                f"one PRNG key lineage is consumed {len(metas)} times "
                f"({uses}): reusing a key correlates draws that DP "
                f"accounting assumes independent"))
    if want_noise and not any(m.meta.get("purpose") == "noise"
                              for m in rng_marks):
        findings.append(Finding(
            PASS, ERROR, "unkeyed-randomness",
            "the plan declares Noise but no rng_use(purpose=noise) marker "
            "appears: the noise key is consumed outside the audited path"))
    if plan.importance is not None and not any(
            m.meta.get("purpose") == "importance" for m in rng_marks):
        findings.append(Finding(
            PASS, ERROR, "unkeyed-randomness",
            "the plan declares Importance but no "
            "rng_use(purpose=importance) marker appears"))

    return PrivacyReport(tuple(marks), tuple(leaves), tuple(findings))


def check_step(loss_fn, params, batch, consumers, **trace_kw):
    """Convenience: trace ``Engine.step`` and analyze it."""
    return analyze_trace(_J.trace_step(loss_fn, params, batch, consumers,
                                       **trace_kw))
