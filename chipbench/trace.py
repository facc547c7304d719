"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

The harness writes host spans with ``jax.profiler.TraceAnnotation``:
one ``window`` span around the measured loop, and inside it one span
per piece of host work (``loader``, ``run_step`` or ``engine_step``,
``host_fetch``). On the device planes (``/device:TPU:<n>``) the line
``XLA Ops`` holds one event per executed operation. Host and device
events share one clock in the trace.

For each device used, within the window:

  * busy: the union of its operation intervals;
  * idle gaps: the window less busy, each gap charged to the host span
    that overlaps it most (``other`` where none does);
  * operation time by operation name, as self time: an operation that
    holds others on the same line (a ``while`` loop and its body) is
    charged only the time none of them covers;
  * all-reduce time: the union of its all-reduce operations, and the
    exposed part of it, during which no other operation runs there.

Device numbers are averaged over the devices used. Everything below
``reduce_profile`` works on plain (start, end) nanosecond intervals, so
that tests can check it on hand-made ones.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Sequence, Tuple

Interval = Tuple[float, float]

WINDOW_SPAN = "window"
HOST_SPANS = ("loader", "run_step", "engine_step", "host_fetch")
#: the spans of which the window holds one a step
STEP_SPANS = ("run_step", "engine_step")
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ALLREDUCE = re.compile(r"all-reduce", re.IGNORECASE)


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def length(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def complement(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """Gaps of the (sorted, disjoint) ``busy`` within [lo, hi]."""
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, min(s, hi)))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    return [g for g in gaps if g[1] > g[0]]


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """a \\ b for sorted, disjoint interval lists."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            bs, be = b[k]
            if bs > s:
                out.append((s, bs))
            s = max(s, be)
            k += 1
        if e > s:
            out.append((s, e))
    return out


def overlap(a: Interval, b: Interval) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


def charge_gaps(gaps: Sequence[Interval],
                spans: Sequence[Tuple[str, float, float]]) -> Dict[str, float]:
    """Gap length charged to the host span that overlaps each gap most.
    The spans follow one another (one host thread drives the loop)."""
    out: Dict[str, float] = defaultdict(float)
    spans = sorted(spans, key=lambda x: x[1])
    starts = [s for _, s, _ in spans]
    for g in gaps:
        best, best_ov = "other", 0.0
        i = bisect.bisect_left(starts, g[1]) - 1
        while i >= 0 and spans[i][2] > g[0]:
            ov = overlap(g, spans[i][1:])
            if ov > best_ov:
                best, best_ov = spans[i][0], ov
            i -= 1
        out[best] += g[1] - g[0]
    return dict(out)


def self_times(ops: Sequence[Tuple[str, float, float]]) -> Dict[str, float]:
    """Time of each operation name less the time of the operations it
    holds (events of one line nest; they do not partly overlap)."""
    out: Dict[str, float] = defaultdict(float)
    stack: list = []                        # [name, start, end, held]

    def close(top):
        out[top[0]] += (top[2] - top[1]) - top[3]

    for n, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][2] <= s:
            close(stack.pop())
        if stack:
            stack[-1][3] += min(e, stack[-1][2]) - s
        stack.append([n, s, e, 0.0])
    while stack:
        close(stack.pop())
    return dict(out)


def device_numbers(ops: Sequence[Tuple[str, float, float]], lo: float,
                   hi: float, spans: Sequence[Tuple[str, float, float]]) -> dict:
    """Numbers of one device from its operation events (name, start,
    end) within [lo, hi], in nanoseconds."""
    ops = [(n, max(s, lo), min(e, hi)) for n, s, e in ops
           if min(e, hi) > max(s, lo)]
    busy = union((s, e) for _, s, e in ops)
    ar = union((s, e) for n, s, e in ops if ALLREDUCE.search(n))
    other = union((s, e) for n, s, e in ops if not ALLREDUCE.search(n))
    gaps = complement(busy, lo, hi)
    return {
        "busy_ns": length(busy),
        "ops_ns": self_times(ops),
        "allreduce_ns": length(ar),
        "allreduce_exposed_ns": length(subtract(ar, other)),
        "gaps_ns": charge_gaps(gaps, spans),
        "longest_gap_ns": max((e - s for s, e in gaps), default=0.0),
    }


def summarize(per_device: Sequence[dict], lo: float, hi: float,
              n_steps: int) -> dict:
    """Average the devices' numbers; seconds throughout."""
    n = len(per_device)
    window_s = (hi - lo) * 1e-9
    mean = lambda k: sum(d[k] for d in per_device) / n * 1e-9
    ops: Dict[str, float] = defaultdict(float)
    gaps: Dict[str, float] = defaultdict(float)
    for d in per_device:
        for k, v in d["ops_ns"].items():
            ops[k] += v * 1e-9 / n
        for k, v in d["gaps_ns"].items():
            gaps[k] += v * 1e-9 / n
    busy_s = mean("busy_ns")
    top = lambda d: [[k, v] for k, v in
                     sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s,
        "allreduce_s": mean("allreduce_ns"),
        "allreduce_exposed_s": mean("allreduce_exposed_ns"),
        "steps": n_steps,
        "devices": n,
        "device_ops": top(ops),
        "idle_gaps": top(gaps),
    }


# ---------------------------------------------------------------------------
# reading the profile
# ---------------------------------------------------------------------------

def op_name(text: str) -> str:
    """``fusion.12 bf16[8,1024]`` from an event named by its HLO
    instruction (``%fusion.12 = bf16[8,1024]{1,0} fusion(...), ...``):
    the instruction and, unless it is a tuple, the type of its result;
    other names as they are."""
    head, sep, rest = text.partition(" = ")
    if not sep:
        return text
    name = head.lstrip("%")
    result = rest.split("{", 1)[0].split(" ", 1)[0]
    return name if result.startswith("(") else f"{name} {result}"


def _events(line):
    for e in line.events:
        yield (op_name(e.name), float(e.start_ns),
               float(e.start_ns + e.duration_ns))


def read_profile(path: str, device_ids: Sequence[int]):
    """(host spans [(name, start, end)], {device id: [(op, start, end)]})
    from an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    spans, devices = [], {}
    wanted = set(HOST_SPANS) | {WINDOW_SPAN}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) in device_ids:
            devices[int(m.group(1))] = [
                ev for line in plane.lines if line.name == OPS_LINE
                for ev in _events(line)]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(ev for ev in _events(line) if ev[0] in wanted)
    return spans, devices


def reduce_profile(path: str, device_ids: Sequence[int]) -> dict:
    spans, devices = read_profile(path, device_ids)
    windows = [s for s in spans if s[0] == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"{path}: expected one '{WINDOW_SPAN}' span, "
                         f"found {len(windows)}")
    missing = sorted(set(device_ids) - set(devices))
    if missing:
        raise ValueError(f"{path}: no '{OPS_LINE}' for devices {missing}; "
                         f"planes hold {sorted(devices)}")
    _, lo, hi = windows[0]
    inner = [s for s in spans if s[0] != WINDOW_SPAN and s[1] >= lo
             and s[2] <= hi]
    n_steps = sum(1 for s in inner if s[0] in STEP_SPANS)
    per_device = [device_numbers(devices[i], lo, hi, inner)
                  for i in device_ids]
    return summarize(per_device, lo, hi, n_steps)
