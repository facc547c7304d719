"""The step's device time by phase, and the device's idle time by the
host action in which it fell, from a profiler trace and the compiled
step's HLO text.

The program marks its step's phases with ``jax.named_scope``
(``pex.forward``, ``pex.norms_backward``, ``pex.stats``,
``pex.weight_backward``, ``pex.noise``, ``optim.adamw``), which reach
the ``op_name`` metadata of the compiled HLO, and the host parts of
``Trainer.run_step`` with ``jax.profiler.TraceAnnotation``
(``trainer.dispatch``, ``trainer.fetch_loss``, ``trainer.finite_check``,
``trainer.norm_stats``, ``trainer.quarantine``), which land on the
trace's host plane inside the benchmark's ``run_step`` span. A device
plane's ``XLA Ops`` line names each operation by its HLO instruction;
its ``XLA Modules`` line holds one event per executed program
(``jit_step_fn(<fingerprint>)``).

For each device used, within the ``window`` span (``trace.py``
averages the same way over devices):

  * ``modules``: operation self time by the program it ran in, the
    module event that holds its start;
  * ``phases``: the step program's (``jit_step_fn``) self time by the
    innermost phase scope that its instruction's ``op_name`` names in
    the step's HLO text: ``unscoped`` where none does, ``unmatched``
    where the text does not hold the instruction;
  * ``idle``: each idle gap charged to the host span whose own part
    (the time no span nested in it covers) overlaps the gap most, so a
    ``trainer.*`` span takes the gaps within it from the ``run_step``
    span that holds it (``other`` where no span overlaps);
  * ``idle_in_modules``: the idle time that lies inside a module
    event, by program: the device holds the program but runs none of
    its operations (the rest of the idle time lies between programs);
  * ``collectives``: the step program's self time in its all-reduce,
    all-gather and reduce-scatter instructions, by the instruction's
    opcode in the step's HLO text (JAX names a ``psum`` ``psum.<n>``).

All are seconds a window step (``run_step`` or ``engine_step``
spans in the window). The harness runs ``split`` in a ``--trace 1`` run
beside ``trace.reduce_profile`` for the per-layer readers;
``tools/phase_split.py`` runs a cell and prints it.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

from chipbench import trace

Span = Tuple[str, float, float]

#: the program's own host spans, read by this prefix
PROGRAM_SPANS = "trainer."
MODULES_LINE = "XLA Modules"
#: the program that ``Trainer.run_step`` runs
STEP_MODULE = "jit_step_fn"
#: a phase scope as one component of an ``op_name``: bare, or wrapped
#: by JAX in the transformations it ran under (``jvp(pex.stats)``),
#: though not in a ``transpose``, which marks the backward of the
#: scope's operations (``transpose(jvp(pex.forward))``)
PHASE_SCOPE = re.compile(r"^(?:(?!transpose\()\w+\()*"
                         r"((?:pex|optim)\.\w+)\)*$")
UNSCOPED = "unscoped"
#: step operations whose instruction the HLO text does not hold
UNMATCHED = "unmatched"


# ---------------------------------------------------------------------------
# idle gaps by the innermost host span
# ---------------------------------------------------------------------------

def own_parts(spans: Sequence[Span]) -> List[Tuple[int, float, float]]:
    """Disjoint pieces (index into ``spans``, start, end) of the spans,
    each labelled with the innermost span that covers it. Spans of one
    host thread nest; where two only partly overlap, the later one
    holds the overlap."""
    order = sorted(range(len(spans)),
                   key=lambda i: (spans[i][1], -spans[i][2]))
    out: List[Tuple[int, float, float]] = []
    stack: List[int] = []
    t = float("-inf")

    def upto(x):
        nonlocal t
        if stack and x > t:
            out.append((stack[-1], t, x))
        t = max(t, x)

    def close():
        upto(spans[stack[-1]][2])
        stack.pop()

    for i in order:
        while stack and spans[stack[-1]][2] <= spans[i][1]:
            close()
        upto(spans[i][1])
        stack.append(i)
    while stack:
        close()
    return out


def charge_gaps(gaps: Sequence[trace.Interval],
                spans: Sequence[Span]) -> Dict[str, float]:
    """Gap length charged to the host span whose own part overlaps each
    gap most; on a tie the span that starts later wins. Where no span
    nests in another this is ``trace.charge_gaps``."""
    out: Dict[str, float] = defaultdict(float)
    pieces = own_parts(spans)
    starts = [s for _, s, _ in pieces]
    for g in gaps:
        ov: Dict[int, float] = defaultdict(float)
        i = bisect.bisect_left(starts, g[1]) - 1
        while i >= 0 and pieces[i][2] > g[0]:
            ov[pieces[i][0]] += trace.overlap(g, pieces[i][1:])
            i -= 1
        best = max(ov, key=lambda k: (ov[k], spans[k][1]), default=None)
        name = spans[best][0] if best is not None and ov[best] > 0 \
            else "other"
        out[name] += g[1] - g[0]
    return dict(out)


# ---------------------------------------------------------------------------
# device time by program and by phase
# ---------------------------------------------------------------------------

def module_of(ops: Sequence[Span], modules: Sequence[Span]) -> List[str]:
    """The program each operation ran in: the module event of the same
    device that holds its start (``?`` where none does)."""
    modules = sorted(modules, key=lambda m: m[1])
    starts = [s for _, s, _ in modules]
    out = []
    for _, s, _ in ops:
        i = bisect.bisect_right(starts, s) - 1
        out.append(modules[i][0] if i >= 0 and s < modules[i][2] else "?")
    return out


_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(r"\b(?:calls|body|condition|to_apply|"
                     r"branch_computations)=(\{[^}]*\}|%?[\w.\-]+)")
_NAME = re.compile(r"%?([\w.\-]+)")
#: an instruction's opcode: the first lower-case word before ``(`` that
#: follows a space, past the result's type and layout
_OPCODE = re.compile(r" = .*?\s([a-z][\w\-]*)\(")
COLLECTIVE_OPS = re.compile(r"^(?:all-reduce|all-gather|reduce-scatter)"
                            r"(?:-start|-done)?$")


def innermost_scope(op_name: str) -> str:
    """The last phase scope among the components of an ``op_name``
    (``jit(step_fn)/pex.norms_backward/transpose(jvp(pex.forward))/
    jvp(pex.stats)/reduce_sum`` → ``pex.stats``), ``unscoped`` where
    none is."""
    found = [m.group(1) for m in map(PHASE_SCOPE.match, op_name.split("/"))
             if m]
    return found[-1] if found else UNSCOPED


def step_phases(hlo_text: str) -> Dict[str, str]:
    """Instruction name → phase, for every instruction of a compiled
    module's HLO text (names are unique in a module): the innermost
    phase scope of its ``op_name``. One without metadata takes the
    phase of the root of the first computation it calls (a fusion's
    fused root), as XLA gives a fusion its root's metadata;
    ``unscoped`` where that finds none either."""
    own: Dict[str, Optional[str]] = {}
    calls: Dict[str, List[str]] = {}
    roots: Dict[str, str] = {}
    comp = None
    for line in hlo_text.splitlines():
        if not line.startswith(" "):
            m = _COMPUTATION.match(line)
            comp = m.group(1) if m else None
            continue
        m = _INSTRUCTION.match(line)
        if m is None:
            continue
        name = m.group(2)
        if m.group(1) and comp is not None:
            roots[comp] = name
        meta = _OP_NAME.search(line)
        own[name] = innermost_scope(meta.group(1)) if meta else None
        calls[name] = [c for group in _CALLED.findall(line)
                       for c in _NAME.findall(group)]

    def phase(name: str, depth: int = 0) -> str:
        if own.get(name) is not None or depth > 16:
            return own.get(name) or UNSCOPED
        root = next((roots[c] for c in calls.get(name, ()) if c in roots),
                    None)
        return UNSCOPED if root is None else phase(root, depth + 1)

    return {name: phase(name) for name in own}


def collective_instructions(hlo_text: str) -> set:
    """Names of a compiled module's all-reduce, all-gather and
    reduce-scatter instructions (and their async halves)."""
    out = set()
    for line in hlo_text.splitlines():
        m, op = _INSTRUCTION.match(line), _OPCODE.search(line)
        if m and op and COLLECTIVE_OPS.match(op.group(1)):
            out.add(m.group(2))
    return out


def device_split(ops: Sequence[Span], modules: Sequence[Span], lo: float,
                 hi: float, spans: Sequence[Span],
                 phase_of: Optional[Dict[str, str]] = None,
                 collective: Optional[set] = None) -> dict:
    """One device's busy time, self time by module and (with
    ``phase_of``, from ``step_phases``) the step module's by phase and
    (with ``collective``, from ``collective_instructions``) in its
    collectives, idle gaps by host span and by the module they lie in,
    and the longest gap, within [lo, hi], in nanoseconds. ``ops`` are
    named as ``trace.op_name`` names them."""
    ops = [(n, max(s, lo), min(e, hi)) for n, s, e in ops
           if min(e, hi) > max(s, lo)]
    busy = trace.union((s, e) for _, s, e in ops)
    gaps = trace.complement(busy, lo, hi)
    mods = module_of(ops, modules)
    idle = trace.length(gaps)
    longest = max(gaps, key=lambda g: g[1] - g[0], default=(lo, lo))
    (longest_span,) = charge_gaps([longest], spans)
    (longest_in,) = module_of([("", *longest)], modules)
    out = {
        "busy_ns": trace.length(busy),
        "modules_ns": trace.self_times(
            [(m, s, e) for m, (_, s, e) in zip(mods, ops)]),
        "gaps_ns": charge_gaps(gaps, spans),
        # idle time while a program runs on the device with no operation
        # of its own, by program; the rest lies between programs
        "idle_in_modules_ns": {
            name: idle - trace.length(trace.subtract(gaps, trace.clip(
                trace.union((s, e) for n, s, e in modules if n == name),
                lo, hi)))
            for name in {n for n, _, _ in modules}},
        "longest_gap": (longest[1] - longest[0], longest_span, longest_in),
    }
    if phase_of is not None:
        out["phases_ns"] = trace.self_times(
            [(phase_of.get(n.split(" ", 1)[0], UNMATCHED), s, e)
             for m, (n, s, e) in zip(mods, ops) if m == STEP_MODULE])
    if collective is not None:
        out["collectives_ns"] = trace.self_times(
            [("collective" if n.split(" ", 1)[0] in collective else "", s, e)
             for m, (n, s, e) in zip(mods, ops) if m == STEP_MODULE]
        ).get("collective", 0.0)
    return out


# ---------------------------------------------------------------------------
# reading the profile
# ---------------------------------------------------------------------------

def read_profile(path: str, device_ids: Sequence[int]):
    """(host spans: ``window``, ``trace.HOST_SPANS`` and ``trainer.*``;
    {device id: operations}; {device id: modules, named without their
    fingerprint}) from an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    spans, ops, modules = [], {}, {}
    wanted = set(trace.HOST_SPANS) | {trace.WINDOW_SPAN}
    for plane in data.planes:
        m = trace.DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) in device_ids:
            i = int(m.group(1))
            ops[i], modules[i] = [], []
            for line in plane.lines:
                if line.name == trace.OPS_LINE:
                    ops[i] = list(trace._events(line))
                elif line.name == MODULES_LINE:
                    modules[i] = [(n.split("(", 1)[0], s, e)
                                  for n, s, e in trace._events(line)]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(ev for ev in trace._events(line)
                             if ev[0] in wanted
                             or ev[0].startswith(PROGRAM_SPANS))
    return spans, ops, modules


def split(path: str, device_ids: Sequence[int],
          step_hlo: Optional[str] = None) -> dict:
    """``window_s``, ``busy_s``, ``steps``; in seconds a window step
    averaged over the devices, ``modules``, ``idle``,
    ``idle_in_modules`` and (given the step's HLO text; else None)
    ``phases`` and ``collectives``; and the longest idle gap of any
    device (seconds, the span it is charged to, the program whose
    module event holds its start or ``?``)."""
    spans, ops, modules = read_profile(path, device_ids)
    windows = [s for s in spans if s[0] == trace.WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"{path}: expected one '{trace.WINDOW_SPAN}' "
                         f"span, found {len(windows)}")
    missing = sorted(set(device_ids) - set(ops))
    if missing:
        raise ValueError(f"{path}: no device plane for devices {missing}")
    _, lo, hi = windows[0]
    inner = [s for s in spans if s[0] != trace.WINDOW_SPAN
             and s[1] >= lo and s[2] <= hi]
    steps = sum(1 for s in inner if s[0] in trace.STEP_SPANS)
    if not steps:
        raise ValueError(f"{path}: no step span {trace.STEP_SPANS} in the "
                         f"window")
    phase_of = step_phases(step_hlo) if step_hlo else None
    collective = collective_instructions(step_hlo) if step_hlo else None
    per = [device_split(ops[i], modules[i], lo, hi, inner, phase_of,
                        collective) for i in device_ids]
    scale = 1e-9 / len(per) / steps

    def pool(key):
        out: Dict[str, float] = defaultdict(float)
        for d in per:
            for k, v in d[key].items():
                out[k] += v * scale
        return dict(out)

    ns, span, module = max(d["longest_gap"] for d in per)
    return {"window_s": (hi - lo) * 1e-9,
            "busy_s": sum(d["busy_ns"] for d in per) * 1e-9 / len(per),
            "steps": steps, "modules": pool("modules_ns"),
            "idle": pool("gaps_ns"),
            "idle_in_modules": {k: v for k, v in pool(
                "idle_in_modules_ns").items() if v > 0},
            "longest_gap": {"s": ns * 1e-9, "span": span, "module": module},
            "phases": pool("phases_ns") if phase_of is not None else None,
            "collectives": sum(d["collectives_ns"] for d in per) * scale
            if collective is not None else None}


def coverage(s: dict) -> dict:
    """What ``split`` accounts for, as shares: the step phases and the
    other modules over busy time; ``unscoped`` and ``unmatched`` over
    the step module's time; the named ``trainer.*`` spans' idle over
    the idle within ``run_step``."""
    phases = s["phases"] or {}
    step = s["modules"].get(STEP_MODULE, 0.0)
    other = sum(v for k, v in s["modules"].items() if k != STEP_MODULE)
    named = sum(v for k, v in s["idle"].items()
                if k.startswith(PROGRAM_SPANS))
    in_step = named + s["idle"].get("run_step", 0.0)
    return {
        "accounted_over_busy": (sum(phases.values()) + other) * s["steps"]
        / s["busy_s"],
        "unscoped_over_step": phases.get(UNSCOPED, 0.0) / step
        if step else None,
        "unmatched_over_step": phases.get(UNMATCHED, 0.0) / step
        if step else None,
        "trainer_spans_over_run_step_idle": named / in_step
        if in_step else None,
    }


# ---------------------------------------------------------------------------
# what the per-layer readers take from a run's split
# ---------------------------------------------------------------------------

def phase_ms(run: dict, scope: str) -> Optional[float]:
    """The step program's self time under phase ``scope``, in ms a
    step, from a traced run's split; None where the run has no phase
    split or the scope ran nothing."""
    split = run.get("phases")
    seconds = (split.get("phases") or {}).get(scope) if split else None
    return 1e3 * seconds if seconds else None

