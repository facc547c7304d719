"""Tap-coverage verifier: prove, at trace time, that the taps cover
the parameter tree (pexlint pass 1, DESIGN.md §10).

The paper's exactness claim is only as good as the instrumentation: a
parameter whose gradient path bypasses every ``pex`` custom_vjp
contributes to training but not to the per-example norms — DP clipping
silently under-clips and GNS/importance estimates bias. Nothing about
running the model can catch that (the norms are merely *smaller*), but
the traced jaxpr can: this pass walks the closed jaxpr of
``loss_fn(params, batch, tap)`` with a live tap and classifies every
parameter leaf by taint analysis.

**Taint propagation.** Each jaxpr variable carries the set of parameter
leaves it (transitively) depends on. Ordinary equations union their
operands' taint into their outputs — the structural recursion into
``jit``, ``scan``, ``while``/``cond``, and foreign ``custom_vjp``
calls lives in the shared front end (``analysis._jaxpr.Walker``). A
pex equation (identified by its registered backward rule —
``taps.PEX_OPS``) is the one place taint is *blocked*: the weight-slot
operand's taint is captured as a tap site and does NOT flow into the
op's output, while data-slot taint flows through. After propagation:

  * leaf taint reaches the loss        ⇒ **untapped-but-trained**: some
    gradient path avoids every tap (ERROR unless allowlisted);
  * leaf captured at a tap site only   ⇒ **tapped** (OK);
  * leaf taint reaches nothing          ⇒ **frozen/unused** (OK).

A leaf that is both captured *and* reaches the loss (e.g. a weight
used through ``tap.dense`` in one layer and a plain einsum in another)
is still an error — its norm undercounts the plain path.

Everything here is ``jax.make_jaxpr`` — no XLA compilation, no
execution; abstract (``ShapeDtypeStruct``) params and batches work.

**Allowlist.** Intentionally untapped parameters (DESIGN.md §5: the
weight-shared zamba2 block, ssm conv/decay tensors, rwkv mix/decay
bases — and the upcoming scoped-tap modes) must be *declared*, not
accidental: ``allow`` entries are substrings matched against the
leaf's key path (``models.registry.UNTAPPED_ALLOWLIST`` holds the
per-arch declarations; ``tests/helpers.py`` derives its oracle scope
filter from the same table, so the analyzer and the exactness tests
can never disagree about scope). The converse is checked too: an
``allow`` entry that matches NO parameter path is *stale* — the
parameter it once declared was renamed or removed, and the entry now
silently waits to mask a future regression — reported in
``CoverageReport.stale_allow`` and surfaced as a pexlint WARNING.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.analysis import _jaxpr as _J
from repro.analysis._jaxpr import AnalysisError  # re-export  # noqa: F401
from repro.core.taps import (ExampleLayout, PexSpec, Tap, identify_pex_bwd)

_EMPTY = _J.EMPTY


# ---------------------------------------------------------------------------
# report datatypes
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TapSite:
    """One instrumented op in the traced program."""
    index: int
    op: str                         # dense | bias_add | scale | ...
    param_leaves: frozenset         # leaf ids captured in the weight slot
    operand_avals: Tuple            # (shape, dtype name) per operand


#: classification outcomes
TAPPED = "tapped"
UNTAPPED = "untapped-but-trained"
FROZEN = "frozen/unused"


@dataclasses.dataclass(frozen=True)
class LeafReport:
    path: str                       # display path (keystr)
    shape: Tuple[int, ...]
    status: str                     # TAPPED | UNTAPPED | FROZEN
    allowlisted: bool
    sites: Tuple[int, ...]          # TapSite indices capturing this leaf

    @property
    def is_error(self) -> bool:
        return self.status == UNTAPPED and not self.allowlisted


@dataclasses.dataclass(frozen=True)
class CoverageReport:
    leaves: Tuple[LeafReport, ...]
    sites: Tuple[TapSite, ...]
    token_loss_registered: bool
    stale_allow: Tuple[str, ...] = ()   # allow entries matching no leaf

    @property
    def errors(self) -> Tuple[LeafReport, ...]:
        return tuple(l for l in self.leaves if l.is_error)

    @property
    def ok(self) -> bool:
        return not self.errors

    def counts(self) -> dict:
        out = {TAPPED: 0, UNTAPPED: 0, FROZEN: 0, "allowlisted": 0}
        for l in self.leaves:
            if l.status == UNTAPPED and l.allowlisted:
                out["allowlisted"] += 1
            else:
                out[l.status] += 1
        return out

    def summary(self) -> str:
        c = self.counts()
        head = (f"{len(self.sites)} tap sites; {c[TAPPED]} tapped, "
                f"{c['allowlisted']} allowlisted-untapped, "
                f"{c[FROZEN]} frozen, {c[UNTAPPED]} ERROR")
        lines = [head]
        for l in self.errors:
            lines.append(
                f"  ERROR untapped-but-trained: {l.path} {l.shape} — its "
                f"gradient path reaches the loss without crossing any pex "
                f"op, so per-example norms undercount it; tap it or add "
                f"it to the allowlist")
        for a in self.stale_allow:
            lines.append(
                f"  WARNING stale allowlist entry {a!r}: matches no "
                f"parameter path in this model — remove it, or it will "
                f"silently mask the next parameter named like it")
        return "\n".join(lines)

    def raise_if_errors(self) -> "CoverageReport":
        if not self.ok:
            raise AnalysisError("tap coverage failed:\n" + self.summary())
        return self


# ---------------------------------------------------------------------------
# the jaxpr walker — pex tap semantics over the shared front end
# ---------------------------------------------------------------------------

class _CoverageWalker(_J.Walker):
    """Union-taint walker that blocks weight taint at pex tap sites."""

    def __init__(self):
        super().__init__()
        self.sites: list = []

    def hook(self, eqn, in_t):
        if eqn.primitive.name == "stop_gradient":
            # no gradient flows back through stop_gradient, so taint
            # must not flow forward: a frozen LoRA base weight
            # (nn.linear's stop_grad(W) path) is FROZEN, not
            # untapped-ERROR — its value reaches the loss, its
            # gradient path does not
            return [_EMPTY for _ in eqn.outvars]
        if eqn.primitive.name not in ("custom_vjp_call_jaxpr",
                                      "custom_vjp_call"):
            return None
        info = identify_pex_bwd(eqn.params.get("bwd"))
        num_consts = eqn.params.get("num_consts", 0)
        if info is not None and \
                len(eqn.invars) - num_consts == info.n_operands:
            ops_t = in_t[num_consts:]
            ops_v = eqn.invars[num_consts:]
            captured = _EMPTY
            for ws in info.weight_slots:
                captured = captured | ops_t[ws]
            if self.recording:
                avals = tuple(
                    (tuple(v.aval.shape), jnp.dtype(v.aval.dtype).name)
                    for v in ops_v)
                self.sites.append(
                    TapSite(len(self.sites), info.name, captured, avals))
            data = _EMPTY
            for ds in info.data_slots:
                data = data | ops_t[ds]
            # outputs are (z, acc): weight taint is *blocked* — covered
            # gradient paths end at the tap
            return [data] + [ops_t[-1]] * (len(eqn.outvars) - 1)
        # foreign custom_vjp (e.g. flash attention): recurse
        fun = eqn.params.get("fun_jaxpr") or eqn.params.get("call_jaxpr")
        if fun is not None and len(_J.as_open(fun).invars) == len(in_t):
            return self.run(fun, in_t)
        return None


# ---------------------------------------------------------------------------
# the pass
# ---------------------------------------------------------------------------

def _leading_dim(tree) -> int:
    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:
        raise ValueError("cannot infer batch size from an empty batch")
    return leaves[0].shape[0]


def stale_allow_entries(allow: Sequence[str],
                        paths: Sequence[Tuple[str, str]]) -> Tuple[str, ...]:
    """``allow`` entries that match no leaf path. ``paths`` holds
    (raw, pretty) path pairs — the same two forms the allowlist is
    matched against, so an entry is stale iff it can never fire."""
    return tuple(a for a in allow
                 if not any(a in raw or a in pretty for raw, pretty in paths))


def trace_coverage(loss_fn: Callable, params, batch, *,
                   spec: Optional[PexSpec] = None, layout=None,
                   batch_size: Optional[int] = None,
                   allow: Sequence[str] = (),
                   tap_factory: Optional[Callable] = None) -> CoverageReport:
    """Classify every parameter leaf of ``loss_fn(params, batch, tap)``
    as tapped / untapped-but-trained / frozen. Trace-only: works on
    concrete arrays or ``ShapeDtypeStruct`` trees, never compiles.

    ``allow`` entries are substrings matched against the leaf's key
    path (both the raw ``(DictKey(...), ...)`` form — compatible with
    the historical scope filters — and the ``keystr`` form).
    ``tap_factory(spec, acc=..., layout=...)`` substitutes a custom
    collector (the mutation-corpus tests inject site-deleting taps)."""
    spec = spec if spec is not None else PexSpec(enabled=True)
    if not spec.enabled:
        raise ValueError(
            "tap coverage needs a live tap: spec.enabled=False would "
            "classify every trained parameter as untapped")
    layout = layout if layout is not None else ExampleLayout(spec.n_groups)
    b = batch_size if batch_size is not None else _leading_dim(batch)
    factory = tap_factory if tap_factory is not None else Tap

    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    n_leaves = len(flat)
    state = {}

    def run(p, bt):
        tap = factory(spec, acc=layout.init(b), layout=layout)
        loss_vec, _aux = loss_fn(p, bt, tap)
        state["token"] = tap.token_losses() is not None
        return jnp.sum(loss_vec)

    closed = jax.make_jaxpr(run)(params, batch)
    jaxpr = closed.jaxpr

    walker = _CoverageWalker()
    in_taints = [frozenset((i,)) if i < n_leaves else _EMPTY
                 for i in range(len(jaxpr.invars))]
    out_taints = walker.run(jaxpr, in_taints)
    sites = walker.sites
    loss_taint = out_taints[0] if out_taints else _EMPTY

    leaves = []
    path_pairs = []
    for i, (path, leaf) in enumerate(flat):
        raw, pretty = str(path), jax.tree_util.keystr(path)
        path_pairs.append((raw, pretty))
        captured = tuple(s.index for s in sites if i in s.param_leaves)
        if i in loss_taint:
            status = UNTAPPED
        elif captured:
            status = TAPPED
        else:
            status = FROZEN
        allowed = status == UNTAPPED and any(
            a in raw or a in pretty for a in allow)
        leaves.append(LeafReport(pretty, tuple(leaf.shape), status,
                                 allowed, captured))
    return CoverageReport(tuple(leaves), tuple(sites),
                          bool(state.get("token")),
                          stale_allow_entries(allow, path_pairs))
