"""Collective-layout verification of sharded step traces (pexlint
pass, DESIGN.md §12).

The mesh pipeline's layout contract (``dist.pex`` docstring) is what
makes the accumulator technique free on a mesh: per-example quantities
— the (B,) loss vector, the (B, G)/(B, S) norms, the weight products —
stay batch-sharded end to end and must NEVER be reduced over the data
axes (a psum there silently averages per-example statistics across
examples on other shards), while replicated outputs (the summed
gradients) must cross devices in EXACTLY one psum over exactly the
data axes (zero ⇒ each host trains on shard-local gradients and the
replicas drift; two ⇒ gradients scaled by the shard count).

This pass checks that contract per ``shard_map`` region of a traced
step (``analysis._jaxpr.trace_step``):

  * every ``psum``'s axes name real mesh axes of the region, are
    manual (not auto) axes, and use no ``axis_index_groups``;
  * outputs whose ``out_specs`` shard a dimension over a data axis are
    per-example: no data-axis psum may appear in their lineage;
  * outputs replicated over the data axes carry exactly one data-axis
    psum, covering ALL the data axes (a partial reduction leaves the
    gradient different across the unreduced axis).

``expected_schedule`` states the same contract as data — including its
2-D DP×TP form (model-axis extent > 1), where per-example norms and
losses additionally need a psum over the *model* axes (each tensor
shard holds only part of every example's norm) and gradients reduce
over data and model both. The executable pipeline still rejects big
model axes (it replicates parameters into every shard, ROADMAP R1), but
the static half of the DP×TP contract is pinned here and checked
degenerate-form against today's 1-D traces.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Sequence, Tuple

from repro.analysis import _jaxpr as _J
from repro.analysis.findings import ERROR, Finding
from repro.core import plan as plan_mod

PASS = "collectives"
_EMPTY = _J.EMPTY


# ---------------------------------------------------------------------------
# the declared contract
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ScheduleEntry:
    """One output of the fused region and the collectives it is owed."""
    output: str
    per_example: bool               # stays batch-sharded over data axes
    psum_axes: Tuple[str, ...]      # () = must never be reduced


def expected_schedule(plan: plan_mod.Plan, mesh,
                      data_axes: Sequence[str]) -> Tuple[ScheduleEntry, ...]:
    """The collective schedule a plan's fused region owes on ``mesh``.

    With every non-data axis at extent 1 this degenerates to today's
    executable contract: per-example outputs un-reduced, gradients
    psum'd once over the data axes. With a real model axis (DP×TP) the
    per-example entries gain a model-axis psum — each tensor shard
    holds only its slice of every example's norm and loss — and the
    gradient reduces over both; that form is what a DP×TP pipeline
    must emit to pass this pass unchanged.
    """
    data = tuple(data_axes)
    model = tuple(a for a in mesh.axis_names
                  if a not in data and mesh.shape[a] > 1)
    entries: List[ScheduleEntry] = [
        ScheduleEntry("loss_vec", True, model)]
    if plan.needs_norms:
        entries.append(ScheduleEntry("sq_norms", True, model))
    if plan.weighted or plan.token_weighted:
        # weights are functions of already-complete norms: no collective
        entries.append(ScheduleEntry("weights", True, ()))
    if plan.needs_grads:
        entries.append(ScheduleEntry("grads", False, data + model))
    return tuple(entries)


# ---------------------------------------------------------------------------
# report datatypes
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PsumSite:
    index: int
    axes: Tuple[str, ...]
    grouped: bool                   # axis_index_groups is not None


@dataclasses.dataclass(frozen=True)
class RegionOutput:
    position: int
    sharded_over_data: bool
    data_psums: int                 # distinct data-axis psums in lineage


@dataclasses.dataclass(frozen=True)
class RegionReport:
    index: int
    mesh_axes: Tuple[str, ...]
    manual_axes: Tuple[str, ...]
    psums: Tuple[PsumSite, ...]
    outputs: Tuple[RegionOutput, ...]


@dataclasses.dataclass(frozen=True)
class CollectivesReport:
    regions: Tuple[RegionReport, ...]
    schedule: Tuple[ScheduleEntry, ...]
    findings: Tuple[Finding, ...]

    @property
    def errors(self) -> Tuple[Finding, ...]:
        return tuple(f for f in self.findings if f.severity == ERROR)

    @property
    def ok(self) -> bool:
        return not self.errors

    def summary(self) -> str:
        head = f"collectives: {len(self.regions)} sharded region(s)"
        for r in self.regions:
            n_pe = sum(o.sharded_over_data for o in r.outputs)
            head += (f"\n  region {r.index}: mesh={r.mesh_axes} "
                     f"{n_pe} per-example + "
                     f"{len(r.outputs) - n_pe} replicated outputs, "
                     f"{len(r.psums)} psum(s)")
        return "\n".join([head] + [f"  {f.render()}" for f in self.findings])


# ---------------------------------------------------------------------------
# the walker — psum-site lineage inside one region body
# ---------------------------------------------------------------------------

class _PsumWalker(_J.Walker):
    def __init__(self):
        super().__init__()
        self.sites: Dict[int, PsumSite] = {}    # id(eqn) -> site

    def hook(self, eqn, in_t):
        if eqn.primitive.name != "psum":
            return None
        key = id(eqn)
        if key not in self.sites:
            self.sites[key] = PsumSite(
                len(self.sites), tuple(eqn.params.get("axes", ())),
                eqn.params.get("axis_index_groups") is not None)
        tok = f"ps:{key}"
        return [t | {tok} for t in in_t]


def _spec_axes(dim) -> frozenset:
    """Mesh axes one PartitionSpec entry shards its dimension over."""
    if dim is None:
        return frozenset()
    return frozenset((dim,) if isinstance(dim, str) else dim)


def _analyze_region(eqn, data_axes: Tuple[str, ...], index: int,
                    findings: List[Finding]) -> RegionReport:
    mesh = eqn.params["mesh"]
    manual_axes = eqn.params["manual_axes"]
    manual = tuple(a for a in mesh.axis_names if a in manual_axes)
    auto = frozenset(mesh.axis_names) - frozenset(manual)
    body = eqn.params["jaxpr"]

    walker = _PsumWalker()
    n_in = len(_J.as_open(body).invars)
    out_t = walker.run(body, [_EMPTY] * n_in)
    sites = {f"ps:{k}": s for k, s in walker.sites.items()}

    for s in sites.values():
        unknown = [a for a in s.axes if a not in mesh.axis_names]
        if unknown:
            findings.append(Finding(
                PASS, ERROR, "unknown-axis",
                f"psum over {s.axes} names axes {unknown} that are not on "
                f"the region's mesh {tuple(mesh.axis_names)}"))
        bad_auto = [a for a in s.axes if a in auto]
        if bad_auto:
            findings.append(Finding(
                PASS, ERROR, "auto-axis-psum",
                f"psum over {s.axes} reduces auto (non-manual) axes "
                f"{bad_auto}: inside this region those axes are still "
                f"compiler-managed and the reduction is ill-defined"))
        if s.grouped:
            findings.append(Finding(
                PASS, ERROR, "grouped-psum",
                f"psum over {s.axes} uses axis_index_groups: a partial "
                f"group reduction cannot implement the full data-axis "
                f"gradient sum"))

    outputs = []
    data = frozenset(data_axes)
    for pos, (spec, taint) in enumerate(zip(eqn.params["out_specs"], out_t)):
        sharded = any(data & _spec_axes(dim) for dim in spec)
        dpsums = [sites[t] for t in taint
                  if t in sites and data & set(sites[t].axes)]
        outputs.append(RegionOutput(pos, sharded, len(dpsums)))
        if sharded:
            if dpsums:
                findings.append(Finding(
                    PASS, ERROR, "per-example-psum",
                    f"region output {pos} is batch-sharded over "
                    f"{tuple(sorted(data))} (a per-example quantity) but "
                    f"its lineage contains a psum over "
                    f"{dpsums[0].axes}: per-example statistics must never "
                    f"be reduced over the data axes"))
        else:
            if not dpsums:
                findings.append(Finding(
                    PASS, ERROR, "replicated-unreduced",
                    f"region output {pos} is declared replicated but no "
                    f"data-axis psum appears in its lineage: each shard "
                    f"would return shard-local values that silently "
                    f"differ across hosts"))
            elif len(dpsums) > 1:
                findings.append(Finding(
                    PASS, ERROR, "double-psum",
                    f"region output {pos} crosses {len(dpsums)} distinct "
                    f"data-axis psums: the gradient is scaled by the "
                    f"shard count once per extra reduction"))
            else:
                missing = data - set(dpsums[0].axes)
                if missing:
                    findings.append(Finding(
                        PASS, ERROR, "partial-psum",
                        f"region output {pos} is reduced over "
                        f"{dpsums[0].axes} but the data axes are "
                        f"{tuple(sorted(data))}: the axes "
                        f"{tuple(sorted(missing))} are left unreduced"))
    return RegionReport(index, tuple(mesh.axis_names), manual,
                        tuple(sorted(sites.values(),
                                     key=lambda s: s.index)),
                        tuple(outputs))


# ---------------------------------------------------------------------------
# the pass
# ---------------------------------------------------------------------------

def analyze_trace(trace: _J.StepTrace) -> CollectivesReport:
    """Check the collective layout of every shard_map region in one
    ``StepTrace``. A mesh-less trace has no regions and passes
    trivially (the local path has no collectives to get wrong)."""
    findings: List[Finding] = []
    regions = []
    schedule: Tuple[ScheduleEntry, ...] = ()
    for eqn, _depth in _J.iter_eqns(trace.closed.jaxpr):
        if eqn.primitive.name != "shard_map":
            continue
        if not schedule:
            schedule = expected_schedule(trace.plan, eqn.params["mesh"],
                                         trace.data_axes)
        regions.append(_analyze_region(eqn, trace.data_axes,
                                       len(regions), findings))
    if trace.meshed and not regions:
        findings.append(Finding(
            PASS, ERROR, "missing-region",
            "the step was traced through the mesh path but contains no "
            "shard_map region: the fused core is not actually sharded"))
    return CollectivesReport(tuple(regions), schedule, tuple(findings))


def check_step(loss_fn, params, batch, consumers, **trace_kw):
    """Convenience: trace ``Engine.step`` and analyze its collectives."""
    return analyze_trace(_J.trace_step(loss_fn, params, batch, consumers,
                                       **trace_kw))
