"""Logical-axis sharding rules (DESIGN.md §4).

Model code names *logical* axes ("batch", "mlp", "heads_act", ...);
how those map onto mesh axes is a per-run decision carried by a rules
dict inside a ``use_rules`` context:

    with use_rules(mesh, {"batch": ("data",), "mlp": "model"}):
        param_sh = sharding_tree(axes_of(boxed_params))   # params
        y = shard(y, "batch", None, "mlp")                # activations

Outside any context (tests, single-device examples, benches) every
helper degrades to the identity, so the same model code runs unsharded
with zero overhead — ``shard(x, ...) is x``.

Rule values may be ``None`` (replicate), a mesh-axis name, or a tuple
of mesh-axis names (e.g. ``("pod", "data")`` for multi-pod data
parallelism). A logical axis absent from the rules replicates.
"""
from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Any, Dict, Iterator, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

AxisRule = Union[None, str, Tuple[str, ...]]


def pad_to(n: int, multiple: int) -> int:
    """Round ``n`` up to the next multiple of ``multiple``."""
    if multiple <= 0:
        raise ValueError(f"multiple must be positive, got {multiple}")
    return ((n + multiple - 1) // multiple) * multiple


# --- rules context ---------------------------------------------------------

class _RulesStack(threading.local):
    def __init__(self):
        self.stack = []


_ACTIVE = _RulesStack()


@contextmanager
def use_rules(mesh: Optional[Mesh],
              rules: Dict[str, AxisRule]) -> Iterator[None]:
    """Activate a (mesh, logical→mesh rules) pair for the dynamic extent.

    ``mesh=None`` keeps ``shard`` an identity while still letting
    ``spec`` resolve rules (useful for spec-only unit tests).
    Contexts nest; the innermost wins.
    """
    _ACTIVE.stack.append((mesh, dict(rules)))
    try:
        yield
    finally:
        _ACTIVE.stack.pop()


def current_rules() -> Tuple[Optional[Mesh], Dict[str, AxisRule]]:
    """(mesh, rules) of the innermost active context, or (None, {})."""
    if _ACTIVE.stack:
        return _ACTIVE.stack[-1]
    return None, {}


def active_mesh() -> Optional[Mesh]:
    return current_rules()[0]


# --- specs -----------------------------------------------------------------

def _resolve(rule: AxisRule) -> AxisRule:
    if isinstance(rule, (list, tuple)):
        flat = tuple(a for a in rule if a is not None)
        if not flat:
            return None
        return flat if len(flat) > 1 else flat[0]
    return rule


def spec(*axes: Optional[str]) -> PartitionSpec:
    """Logical axis names (None ⇒ replicated dim) → PartitionSpec.

    Names missing from the active rules replicate — new model code can
    introduce logical axes before every launch config maps them.
    """
    _, rules = current_rules()
    return PartitionSpec(
        *[None if ax is None else _resolve(rules.get(ax)) for ax in axes])


def shard(x: jax.Array, *axes: Optional[str]) -> jax.Array:
    """Constrain ``x`` to the active mesh along logical ``axes``.

    Identity (returns ``x`` itself) when no mesh is active, so model
    code is sharding-annotated unconditionally at zero cost.
    """
    mesh = active_mesh()
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, spec(*axes)))


def sharding_for(axes: Sequence[Optional[str]],
                 mesh: Optional[Mesh] = None) -> NamedSharding:
    """NamedSharding for one logical-axes tuple (params' ``Boxed.axes``)."""
    mesh = mesh if mesh is not None else active_mesh()
    if mesh is None:
        raise RuntimeError("sharding_for requires a mesh "
                           "(pass one or enter use_rules(mesh, ...))")
    return NamedSharding(mesh, spec(*axes))


def sharding_tree(axes_tree: Any, mesh: Optional[Mesh] = None) -> Any:
    """Map a tree of logical-axes tuples (``param.axes_of``) to
    NamedShardings under the active rules."""
    return jax.tree_util.tree_map(
        lambda axes: sharding_for(axes, mesh), axes_tree,
        is_leaf=lambda x: isinstance(x, tuple))


# --- mesh construction -----------------------------------------------------

def make_mesh(shape: Sequence[int], axes: Sequence[str]) -> Mesh:
    """``jax.make_mesh`` with Auto axis types: the rules-based
    placements here are GSPMD-partitioned, not explicitly sharded."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_mesh_over(devices: Sequence, shape: Sequence[int],
                   axes: Sequence[str]) -> Mesh:
    """Mesh over an *explicit* device subset — the elastic
    contraction/expansion primitive: after a host loss the surviving
    devices (in renumbered order) become the new data axis, without
    touching the dead ones ``jax.make_mesh`` would insist on using.
    ``len(devices)`` must equal ``prod(shape)``."""
    import math

    import numpy as np
    n = math.prod(shape)
    if len(devices) != n:
        raise ValueError(f"{len(devices)} devices cannot fill a mesh of "
                         f"shape {tuple(shape)} (= {n})")
    arr = np.empty(n, dtype=object)
    for i, d in enumerate(devices):
        arr[i] = d
    return Mesh(arr.reshape(tuple(shape)), tuple(axes))


# --- mesh arithmetic -------------------------------------------------------

def axis_size(axes: AxisRule, mesh: Optional[Mesh] = None) -> int:
    """Product of mesh extents over ``axes`` (str | tuple | None)."""
    mesh = mesh if mesh is not None else active_mesh()
    if axes is None or mesh is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        if a is not None:
            n *= mesh.shape[a]
    return n


def local_batch(global_batch: int, data_axes: AxisRule,
                mesh: Optional[Mesh] = None) -> int:
    """Per-shard batch under data parallelism; must divide evenly (the
    data pipeline pads with ``pad_to`` before it ever reaches a mesh)."""
    n = axis_size(data_axes, mesh)
    if global_batch % n:
        raise ValueError(
            f"global batch {global_batch} not divisible by the "
            f"{n}-way data-parallel extent {data_axes!r}; pad with "
            f"pad_to({global_batch}, {n}) upstream")
    return global_batch // n
