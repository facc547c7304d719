"""Collective-byte accounting from compiled HLO text.

``cost_analysis`` has no collective term, so we parse the module: every
all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute op contributes its *operand* bytes (falling back to
result bytes when the operand definition isn't resolvable, e.g. fusion
parameters). Ops inside ``while`` bodies appear once — the caller
multiplies by trip count via the probe-extrapolation scheme
(roofline/analysis.py).
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "token": 0,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_DEF_RE = re.compile(r"^\s*(%?[\w.\-]+)\s*=\s*(\([^=]*?\)|\S+)\s+([\w\-]+)")
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")


def _type_bytes(type_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Sum operand bytes per collective kind over the whole module."""
    # pass 1: bytes of every named value
    sizes: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _DEF_RE.match(line)
        if m:
            name, type_str, _ = m.groups()
            sizes[name.lstrip("%")] = _type_bytes(type_str)

    out: Dict[str, float] = defaultdict(float)
    for line in hlo_text.splitlines():
        m = _DEF_RE.match(line)
        if not m:
            continue
        name, type_str, op = m.groups()
        kind = None
        for c in _COLLECTIVES:
            if op == c or op.startswith(c + "-"):  # e.g. all-reduce-start
                kind = c
                break
        if kind is None:
            continue
        # operand list: first (...) after the op name
        rest = line[m.end():]
        paren = rest.find("(")
        operand_bytes = 0
        if paren >= 0:
            depth, j = 0, paren
            for j in range(paren, len(rest)):
                if rest[j] == "(":
                    depth += 1
                elif rest[j] == ")":
                    depth -= 1
                    if depth == 0:
                        break
            operands = rest[paren + 1:j]
            for tok in re.findall(r"%?([\w.\-]+)", operands):
                if tok in sizes:
                    operand_bytes += sizes[tok]
        if operand_bytes == 0:
            operand_bytes = _type_bytes(type_str)   # fallback: result bytes
        out[kind] += operand_bytes
    out["total"] = float(sum(v for k, v in out.items() if k != "total"))
    return dict(out)


def collective_counts(hlo_text: str) -> Dict[str, int]:
    out: Dict[str, int] = defaultdict(int)
    for line in hlo_text.splitlines():
        m = _DEF_RE.match(line)
        if not m:
            continue
        op = m.group(3)
        for c in _COLLECTIVES:
            if op == c or op.startswith(c + "-"):
                if not op.endswith("-done"):     # count start+done pairs once
                    out[c] += 1
    return dict(out)


def compiled_cost(compiled) -> Tuple[float, float]:
    """(flops, bytes accessed) from a compiled executable's
    cost_analysis; an absent analysis gives zeros."""
    ca = compiled.cost_analysis() or {}
    return float(ca.get("flops", 0.0)), float(ca.get("bytes accessed", 0.0))
