"""Cotangent-accumulator taps — the paper's mechanism, JAX-native.

The paper observes that backprop already computes, for every dense
layer, the pair ``(H, Z̄)`` from which per-example gradient norms follow
for free. JAX (like the frameworks the paper complains about) does not
expose ``Z̄``, so each instrumented op here is a ``jax.custom_vjp``
whose backward pass computes the standard cotangents *and* adds the
layer's per-example stat to the cotangent of an accumulator threaded
through the forward pass.

pex v2 (DESIGN.md §7): the accumulator is owned by a trace-time ``Tap``
collector created once per traced function (by ``core.engine.Engine``
or directly) and handed to the model — layers call

    z = tap.dense(h, w, group="mlp")

and never see the accumulator. ``jax.grad`` w.r.t. the tap's initial
accumulator then recovers ``Σ_i s⁽ⁱ⁾`` in the same single backward
pass that produces the parameter gradients (paper §4–§5).

How each op's stat lands in the accumulator is pluggable via the tap's
**layout**:

  * ``ExampleLayout(n_groups)`` — a ``(B, G)`` accumulator; each op's
    per-example stat is scattered into its group's column (v1
    semantics, the paper's per-example norms);
  * ``TokenLayout(seq)`` — a ``(B, S)`` accumulator; the paper's §4
    factorization applied at token granularity, where it is exact for
    *every* dense/bias/scale/embedding op (token t's contribution to
    ``∂L/∂W`` is the rank-1 outer product ``h_t z̄_tᵀ``), replacing the
    old parallel ``core.token_norms`` stack.

Key properties (unchanged from v1):
  * works under ``jit``, ``lax.scan`` (via ``pex.scan`` / the tap
    ``carry()`` contract), ``jax.checkpoint`` (via ``pex.checkpoint``),
    ``vmap`` and ``pjit`` — each op is just a custom_vjp;
  * when gradients w.r.t. the accumulator are *not* requested, the stat
    computation is dead code and is removed by jaxpr/XLA DCE — the
    instrumented model costs the same as the plain one (asserted in
    tests/test_dce.py);
  * when only norms are requested (importance sampling), the ``dW``
    chains are dead code instead — the pass costs forward +
    activation-backprop + O(mnp), as in paper §5.

The v1 explicit-accumulator functions (``dense(h, w, acc, *, spec)``
etc.) and their one-release deprecation shims are gone; all code goes
through ``Tap`` / ``repro.pex``.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import book as B
from repro.core import norms as N
from repro.dist.sharding import shard as _shard

#: ``jax.named_scope`` around each backward rule's per-example stat
#: (``layout.add_*``), for the trace reduction's phase split
STATS = "pex.stats"

_ACC_DTYPE = jnp.float32


@dataclasses.dataclass(frozen=True)
class PexSpec:
    """Static instrumentation policy (hashable; safe to close over in jit).

    enabled:     master switch. Off ⇒ every op is its plain counterpart.
    method:      'auto' | 'gram' | 'direct' | 'factorized' for dense taps.
                 'factorized' applies the paper's formula mechanically to
                 flattened (S·p) rows — exact only when S==1 (kept as the
                 paper-faithful baseline mode; see DESIGN.md §2).
    use_pallas:  route dense stats through the Pallas kernels — the
                 triangular tile-pair gram kernel or the blocked HᵀZ̄
                 direct kernel, whichever the backend-aware cost model
                 picks (``method='auto'`` covers both regimes). Expert
                 taps consult the same flag: their segmented-direct stat
                 runs the sort-based Pallas kernel when the segmented
                 cost model favours it.
    seg_method:  'auto' | 'xla' | 'pallas' for the MoE expert taps'
                 segmented-direct stat. 'auto' (default) defers to the
                 two-sided segmented cost model under ``use_pallas``;
                 the explicit values pin a backend (regression tests).
    groups:      acc column names; per-group norms (e.g. attn/mlp/embed).
                 ``"all"`` / ``"other"`` act as catch-all columns; an op
                 tapping a group not in ``groups`` (and with no catch-all
                 present) raises at trace time — a typo'd group name must
                 not silently corrupt another column's stats.
    tap_embeddings / tap_head: include embedding / lm-head params in the
                 norm (exact but vocab-sized work; cf. DESIGN.md §5).
    """
    enabled: bool = True
    method: str = "auto"
    use_pallas: bool = False
    seg_method: str = "auto"
    groups: Tuple[str, ...] = ("all",)
    tap_embeddings: bool = True
    tap_head: bool = True

    def __post_init__(self):
        """Group patterns resolve by first match, so a duplicate or a
        shadowed catch-all would silently merge two groups' stats into
        one column — reject at construction, naming the conflict."""
        seen = {}
        dups = []
        for i, g in enumerate(self.groups):
            if g in seen:
                dups.append(f"{g!r} (columns {seen[g]} and {i})")
            else:
                seen[g] = i
        if dups:
            raise ValueError(
                f"duplicate pex group pattern(s): {', '.join(dups)}; "
                f"each entry of groups={self.groups} must name a distinct "
                f"accumulator column — stats for a repeated name would all "
                f"land in the first occurrence")
        catch_alls = [g for g in ("all", "other") if g in seen]
        if len(catch_alls) > 1:
            raise ValueError(
                f"shadowing catch-all group patterns {catch_alls} in "
                f"groups={self.groups}: 'all' always wins the catch-all "
                f"lookup, so the 'other' column could never receive a "
                f"stat — keep exactly one catch-all")

    def group_index(self, group: Optional[str]) -> int:
        if group is None:
            return 0
        if group in self.groups:
            return self.groups.index(group)
        for catch_all in ("all", "other"):
            if catch_all in self.groups:
                return self.groups.index(catch_all)
        raise ValueError(
            f"unknown pex group {group!r}: spec.groups={self.groups} has "
            f"no catch-all column ('all' or 'other'); add {group!r} to "
            f"groups or include a catch-all")

    @property
    def n_groups(self) -> int:
        return len(self.groups)


DISABLED = PexSpec(enabled=False)


# ---------------------------------------------------------------------------
# accumulator layouts — where a tap's stat lands
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ExampleLayout:
    """(B, n_groups) accumulator: per-example, per-group squared norms
    (the paper's object). Dense stats go through the estimator zoo
    (core.norms) and scatter into the op's group column."""
    n_groups: int = 1

    def init(self, batch: int) -> jax.Array:
        """Fresh accumulator, constrained to the batch axis under an
        active mesh (dist.sharding rules) so the (B, G) norm vector
        lives wherever the examples live — collective-free under DP."""
        return _shard(jnp.zeros((batch, self.n_groups), _ACC_DTYPE),
                      "batch", None)

    def add_dense(self, acc_bar, h, zbar, group, method, use_pallas):
        stat = N.stat_dense(h, zbar, method=method, use_pallas=use_pallas)
        return acc_bar.at[:, group].add(stat.astype(acc_bar.dtype))

    def add_bias(self, acc_bar, zbar, group):
        return acc_bar.at[:, group].add(
            N.stat_bias(zbar).astype(acc_bar.dtype))

    def add_scale(self, acc_bar, h, zbar, group):
        return acc_bar.at[:, group].add(
            N.stat_elementwise(h, zbar).astype(acc_bar.dtype))

    def add_embedding(self, acc_bar, ids, zbar, group):
        stat = N.stat_embedding(ids.reshape(ids.shape[0], -1),
                                zbar.reshape(zbar.shape[0], -1,
                                             zbar.shape[-1]))
        return acc_bar.at[:, group].add(stat.astype(acc_bar.dtype))

    def add_example_stat(self, acc_bar, stat, group):
        """Scatter an already-(B,)-shaped stat into a group column."""
        return acc_bar.at[:, group].add(stat.astype(acc_bar.dtype))

    def add_dense_batched(self, acc_bar, h, zbar, group, method,
                          use_pallas):
        """Per-example-weight dense stat: h (B,[S,]p_in) against a
        batched weight (B,p_in,p_out) — the multi-tenant LoRA form,
        where example j owns weight slice j (its tenant's gathered
        adapter factor). Example j's gradient is h_jᵀ z̄_j, the same
        outer product as the shared-weight case, so:

          * 2-D h — one token-row per example ⇒ the paper's §4
            factorization is EXACT: s_j = ‖h_j‖²‖z̄_j‖².
          * 3-D h — flatten tokens and run the segmented-direct
            estimator with example ids as segments (ONE launch across
            all examples/tenants; sorted runs since ids are
            repeat(arange(B), S))."""
        if h.ndim == 2:
            stat = N.stat_factorized(h, zbar)
            return self.add_example_stat(acc_bar, stat, group)
        b, s = h.shape[0], h.shape[1]
        h2 = h.reshape(b * s, h.shape[-1])
        z2 = zbar.reshape(b * s, zbar.shape[-1])
        seg = jnp.repeat(jnp.arange(b, dtype=jnp.int32), s)
        stat = N.stat_direct_segmented(h2, z2, seg, b, method=method,
                                       use_pallas=use_pallas)
        return self.add_example_stat(acc_bar, stat, group)

    def add_expert(self, acc_bar, x, zbar, seg, tok, group, n_examples,
                   method, use_pallas):
        """MoE expert-buffer stat: x (E,C,d), zbar (E,C,f), seg (E,C)
        example ids (≥ n_examples ⇒ dropped). Example j's gradient for
        expert e is a separate d×f block of the stacked weight — cross-
        expert outer products must NOT merge before squaring, hence the
        (expert, example) composite segments. ``tok`` (slot → token
        position) is not needed at example granularity."""
        e, c, d = x.shape
        composite = (jnp.arange(e, dtype=seg.dtype)[:, None]
                     * (n_examples + 1) + jnp.minimum(seg, n_examples))
        stat_ec = N.stat_direct_segmented(
            x.reshape(e * c, d), zbar.reshape(e * c, -1),
            composite.reshape(e * c), e * (n_examples + 1),
            method=method, use_pallas=use_pallas)
        stat = stat_ec.reshape(e, n_examples + 1)[:, :n_examples].sum(axis=0)
        return self.add_example_stat(acc_bar, stat, group)

    def add_expert_grouped(self, acc_bar, x, zbar, seg, tok, group, bg,
                           method, use_pallas):
        """Grouped (GShard-local) expert stat: x (G,E,C,d), seg (G,E,C)
        GROUP-LOCAL example ids (≥ bg ⇒ padding row); group g's stats
        land at acc rows [g·bg, (g+1)·bg). An example's rows live only
        in its own group, so (group, expert, example) composite segments
        are exact. The Pallas route flattens all groups into ONE kernel
        launch (vmapping a scalar-prefetched pallas_call is not
        supported); the XLA oracle keeps its per-group vmap form."""
        ng, e, c, d = x.shape
        f = zbar.shape[-1]
        if method == "auto":
            # price the candidates as they would actually launch: the
            # Pallas route is ONE flattened kernel over all groups, the
            # XLA route ng vmapped per-group scans
            if not use_pallas:
                method = "xla"
            else:
                c_pl = N.segmented_cost(ng * e * c, d, f,
                                        ng * e * (bg + 1), use_pallas=True)
                c_xla = ng * N.segmented_cost(e * c, d, f, e * (bg + 1))
                method = "pallas" if c_pl <= c_xla else "xla"
        if method == "pallas":
            ge = (jnp.arange(ng, dtype=seg.dtype)[:, None, None] * e
                  + jnp.arange(e, dtype=seg.dtype)[None, :, None])
            composite = ge * (bg + 1) + jnp.minimum(seg, bg)
            stat_all = N.stat_direct_segmented(
                x.reshape(ng * e * c, d), zbar.reshape(ng * e * c, f),
                composite.reshape(ng * e * c), ng * e * (bg + 1),
                method="pallas")
            stat = stat_all.reshape(ng, e, bg + 1)[:, :, :bg].sum(axis=1)
            stat = stat.reshape(ng * bg)
            return self.add_example_stat(acc_bar, stat, group)

        def one_group(xg, zg, sg):
            composite = (jnp.arange(e, dtype=sg.dtype)[:, None] * (bg + 1)
                         + jnp.minimum(sg, bg))
            stat_ec = N.stat_direct_segmented(
                xg.reshape(e * c, d), zg.reshape(e * c, f),
                composite.reshape(e * c), e * (bg + 1), method="xla")
            return stat_ec.reshape(e, bg + 1)[:, :bg].sum(axis=0)  # (bg,)

        stat = jax.vmap(one_group)(x, zbar, seg).reshape(ng * bg)
        return self.add_example_stat(acc_bar, stat, group)


def _sumsq_tail(x, keep: int = 2):
    """Σ x² over all axes past the first ``keep`` (f32)."""
    axes = tuple(range(keep, x.ndim))
    return jnp.sum(jnp.square(x.astype(_ACC_DTYPE)), axis=axes)


@dataclasses.dataclass(frozen=True)
class TokenLayout:
    """(B, S) accumulator: the paper's §4 factorization at token
    granularity, where it is exact for every dense layer in every
    sequence model — token t's contribution to ``∂L/∂W`` is the rank-1
    outer product ``h_t z̄_tᵀ``, so ``s_{j,t} = ‖h_{j,t}‖²·‖z̄_{j,t}‖²``.
    Group columns do not apply; all taps fold into the one (B, S) map.
    Uses: token-level data filtering / curriculum, influence
    diagnostics, per-token clipping."""
    seq: int

    def init(self, batch: int) -> jax.Array:
        return _shard(jnp.zeros((batch, self.seq), _ACC_DTYPE),
                      "batch", None)

    def add_dense(self, acc_bar, h, zbar, group, method, use_pallas):
        if h.ndim != 3:
            raise ValueError(
                f"TokenLayout dense tap needs (B, S, p) activations, got "
                f"shape {h.shape}; per-token factorization is only exact "
                f"when each token is one row of the matmul")
        return acc_bar + _sumsq_tail(h) * _sumsq_tail(zbar)

    def add_dense_batched(self, acc_bar, h, zbar, group, method,
                          use_pallas):
        """Batched-weight dense stat at token granularity: token t's
        contribution to its example's weight slice is still the rank-1
        outer product h_t z̄_tᵀ — the §4 factorization is exact per
        token, regardless of which example owns the weight."""
        if h.ndim != 3:
            raise ValueError(
                f"TokenLayout dense_batched tap needs (B, S, p) "
                f"activations, got shape {h.shape}")
        return acc_bar + _sumsq_tail(h) * _sumsq_tail(zbar)

    def add_bias(self, acc_bar, zbar, group):
        # token t's bias contribution is z̄_t itself
        self._check_rank(zbar, "bias_add")
        return acc_bar + _sumsq_tail(zbar)

    def add_scale(self, acc_bar, h, zbar, group):
        # token t's gain contribution is h_t ⊙ z̄_t
        self._check_rank(zbar, "scale")
        return acc_bar + _sumsq_tail(h.astype(_ACC_DTYPE) *
                                     zbar.astype(_ACC_DTYPE))

    def add_embedding(self, acc_bar, ids, zbar, group):
        # one-hot row ⇒ ‖h_t‖² = 1 ⇒ stat is ‖z̄_t‖²
        self._check_rank(zbar, "embedding")
        return acc_bar + _sumsq_tail(zbar)

    def _check_rank(self, zbar, op: str) -> None:
        if zbar.ndim < 3:
            raise ValueError(
                f"TokenLayout {op} tap needs (B, S, ...) activations, got "
                f"shape {zbar.shape}; a rank-2 stat would silently "
                f"broadcast into the (B, S) accumulator")

    def _scatter_slot_stats(self, acc_bar, stat, target, valid):
        """Scatter per-slot stats into the flat (B·S) token map; invalid
        slots (capacity padding) are masked AND redirected out of bounds
        so ``mode="drop"`` discards them explicitly."""
        b, s = acc_bar.shape
        tgt = jnp.where(valid, target, b * s).reshape(-1)
        upd = jnp.where(valid, stat, 0.0).reshape(-1)
        flat = acc_bar.reshape(-1).at[tgt].add(upd.astype(acc_bar.dtype),
                                               mode="drop")
        return flat.reshape(b, s)

    def add_expert(self, acc_bar, x, zbar, seg, tok, group, n_examples,
                   method, use_pallas):
        """Token-granularity expert stat. Every capacity slot holds ONE
        token's row, so token t's contribution to the expert weight is
        the rank-1 outer product x_slot z̄_slotᵀ — the §4 factorization
        is *exact* per slot, and a token's slots land in distinct expert
        matrices (top-k experts are distinct), so summing its slot stats
        into the (B, S) map is exact too. ``tok`` is the slot → flat
        token position table from the dispatch sort (tok ∈ [0, B·S);
        out-of-range ⇒ padding slot); no segmented estimator needed —
        per-token expert norms are O(T·p)."""
        b, s = acc_bar.shape
        stat = _sumsq_tail(x, 2) * _sumsq_tail(zbar, 2)          # (E, C)
        valid = jnp.logical_and(tok >= 0, tok < b * s)
        return self._scatter_slot_stats(acc_bar, stat, tok, valid)

    def add_expert_grouped(self, acc_bar, x, zbar, seg, tok, group, bg,
                           method, use_pallas):
        """Grouped-dispatch variant: ``tok`` (G,E,C) carries GROUP-LOCAL
        flat token ids (∈ [0, bg·S); ≥ bg·S ⇒ padding slot); group g
        covers the flat tokens [g·bg·S, (g+1)·bg·S)."""
        b, s = acc_bar.shape
        ng = x.shape[0]
        tg = bg * s
        stat = _sumsq_tail(x, 3) * _sumsq_tail(zbar, 3)          # (G, E, C)
        valid = jnp.logical_and(tok >= 0, tok < tg)
        glob = jnp.arange(ng, dtype=jnp.int32)[:, None, None] * tg + tok
        return self._scatter_slot_stats(acc_bar, stat, glob, valid)


def _int_zero_cotangent(x):
    return np.zeros(x.shape, dtype=jax.dtypes.float0)


# ---------------------------------------------------------------------------
# custom_vjp op registry (layout-parameterized)
# dense: z = h @ w        (the paper's layer; h (B,[S,]p_in), w (p_in,p_out))
# ---------------------------------------------------------------------------

# ``book`` (static) and ``slot`` (the record pass's zero slot, None
# otherwise): what the backward rule returns as the slot's cotangent —
# the site's book entry (core/book.py). Dense books ``(H, Z̄)``, or
# ``Z̄`` alone (``book="z"``) where an earlier site booked the same H.

@partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4))
def _pex_dense(method: str, use_pallas: bool, group: int, layout, book,
               h: jax.Array, w: jax.Array, acc: jax.Array, slot):
    return jnp.einsum("...i,io->...o", h, w), acc


def _pex_dense_fwd(method, use_pallas, group, layout, book, h, w, acc,
                   slot):
    z = jnp.einsum("...i,io->...o", h, w)
    return (z, acc), (h, w)


def _pex_dense_bwd(method, use_pallas, group, layout, book, res, cts):
    h, w = res
    zbar, acc_bar = cts
    dh = jnp.einsum("...o,io->...i", zbar, w).astype(h.dtype)
    dw = jnp.einsum("...i,...o->io", h, zbar).astype(w.dtype)
    with jax.named_scope(STATS):
        dacc = layout.add_dense(acc_bar, h, zbar, group, method, use_pallas)
    entry = None if book is None else \
        ((zbar,) if book == "z" else (h, zbar))
    return dh, dw, dacc, entry


_pex_dense.defvjp(_pex_dense_fwd, _pex_dense_bwd)


# ---------------------------------------------------------------------------
# dense_batched: z = einsum('b...i,bio->b...o')  (per-example weights —
#   the multi-tenant LoRA form, where example j's matmul uses weight
#   slice j, the gather of its tenant's adapter factor)
# ---------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _pex_dense_batched(method: str, use_pallas: bool, group: int, layout,
                       h: jax.Array, w: jax.Array, acc: jax.Array):
    return jnp.einsum("b...i,bio->b...o", h, w), acc


def _pex_dense_batched_fwd(method, use_pallas, group, layout, h, w, acc):
    z = jnp.einsum("b...i,bio->b...o", h, w)
    return (z, acc), (h, w)


def _pex_dense_batched_bwd(method, use_pallas, group, layout, res, cts):
    h, w = res
    zbar, acc_bar = cts
    dh = jnp.einsum("b...o,bio->b...i", zbar, w).astype(h.dtype)
    dw = jnp.einsum("b...i,b...o->bio", h, zbar).astype(w.dtype)
    with jax.named_scope(STATS):
        dacc = layout.add_dense_batched(acc_bar, h, zbar, group, method,
                                        use_pallas)
    return dh, dw, dacc


_pex_dense_batched.defvjp(_pex_dense_batched_fwd, _pex_dense_batched_bwd)


# ---------------------------------------------------------------------------
# dense_expert: z = einsum('ecd,edf->ecf')  (MoE expert matmuls; rows of the
#   (E, C) capacity buffer belong to arbitrary examples, so stats use the
#   segmented-direct estimator with per-row example ids — or, at token
#   granularity, the per-slot factorization scattered by token position)
# ---------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4))
def _pex_dense_expert(group: int, n_examples: int, method: str,
                      use_pallas: bool, layout,
                      x: jax.Array, w: jax.Array, seg: jax.Array,
                      tok: jax.Array, acc: jax.Array):
    return jnp.einsum("ecd,edf->ecf", x, w), acc


def _pex_dense_expert_fwd(group, n_examples, method, use_pallas, layout,
                          x, w, seg, tok, acc):
    return (jnp.einsum("ecd,edf->ecf", x, w), acc), (x, w, seg, tok)


def _pex_dense_expert_bwd(group, n_examples, method, use_pallas, layout,
                          res, cts):
    x, w, seg, tok = res
    zbar, acc_bar = cts
    dx = jnp.einsum("ecf,edf->ecd", zbar, w).astype(x.dtype)
    dw = jnp.einsum("ecd,ecf->edf", x, zbar).astype(w.dtype)
    with jax.named_scope(STATS):
        dacc = layout.add_expert(acc_bar, x, zbar, seg, tok, group, n_examples,
                                 method, use_pallas)
    return (dx, dw, _int_zero_cotangent(seg), _int_zero_cotangent(tok),
            dacc)


_pex_dense_expert.defvjp(_pex_dense_expert_fwd, _pex_dense_expert_bwd)


# ---------------------------------------------------------------------------
# dense_expert_grouped: z = einsum('gecd,edf->gecf') — grouped local MoE
#   dispatch (groups aligned with data shards). seg holds GROUP-LOCAL
#   example ids (and tok group-local token ids), so the stats stay
#   device-local; group g's stats land at acc rows [g·bg, (g+1)·bg).
# ---------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4))
def _pex_dense_expert_grouped(group: int, bg: int, method: str,
                              use_pallas: bool, layout,
                              x: jax.Array, w: jax.Array, seg: jax.Array,
                              tok: jax.Array, acc: jax.Array):
    return jnp.einsum("gecd,edf->gecf", x, w), acc


def _pex_dense_expert_grouped_fwd(group, bg, method, use_pallas, layout,
                                  x, w, seg, tok, acc):
    return (jnp.einsum("gecd,edf->gecf", x, w), acc), (x, w, seg, tok)


def _pex_dense_expert_grouped_bwd(group, bg, method, use_pallas, layout,
                                  res, cts):
    x, w, seg, tok = res
    zbar, acc_bar = cts
    dx = jnp.einsum("gecf,edf->gecd", zbar, w).astype(x.dtype)
    dw = jnp.einsum("gecd,gecf->edf", x, zbar).astype(w.dtype)
    with jax.named_scope(STATS):
        dacc = layout.add_expert_grouped(acc_bar, x, zbar, seg, tok, group, bg,
                                         method, use_pallas)
    return (dx, dw, _int_zero_cotangent(seg), _int_zero_cotangent(tok),
            dacc)


_pex_dense_expert_grouped.defvjp(_pex_dense_expert_grouped_fwd,
                                 _pex_dense_expert_grouped_bwd)


# ---------------------------------------------------------------------------
# bias_add: z = x + b      (paper folds b into W as a ones-column; same math)
# ---------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _pex_bias(group: int, layout, book, x: jax.Array, b: jax.Array,
              acc: jax.Array, slot):
    return x + b, acc


def _pex_bias_fwd(group, layout, book, x, b, acc, slot):
    return (x + b, acc), None


def _pex_bias_bwd(group, layout, book, _, cts):
    zbar, acc_bar = cts
    reduce_axes = tuple(range(zbar.ndim - 1))
    db = jnp.sum(zbar, axis=reduce_axes).astype(zbar.dtype)
    with jax.named_scope(STATS):
        dacc = layout.add_bias(acc_bar, zbar, group)
        entry = None if book is None else (B.per_example_sum(zbar),)
    return zbar, db, dacc, entry


_pex_bias.defvjp(_pex_bias_fwd, _pex_bias_bwd)


# ---------------------------------------------------------------------------
# scale: z = g ⊙ h         (elementwise params: RMSNorm gains, decays, ...)
# ---------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _pex_scale(group: int, layout, book, h: jax.Array, g: jax.Array,
               acc: jax.Array, slot):
    return h * g, acc


def _pex_scale_fwd(group, layout, book, h, g, acc, slot):
    return (h * g, acc), (h, g)


def _pex_scale_bwd(group, layout, book, res, cts):
    h, g = res
    zbar, acc_bar = cts
    dh = (zbar * g).astype(h.dtype)
    reduce_axes = tuple(range(zbar.ndim - 1))
    dg = jnp.sum(zbar * h, axis=reduce_axes).astype(g.dtype)
    with jax.named_scope(STATS):
        dacc = layout.add_scale(acc_bar, h, zbar, group)
        entry = None if book is None else (B.per_example_sum(
            h.astype(_ACC_DTYPE) * zbar.astype(_ACC_DTYPE)),)
    return dh, dg, dacc, entry


_pex_scale.defvjp(_pex_scale_fwd, _pex_scale_bwd)


# ---------------------------------------------------------------------------
# embedding: z = table[ids]   (one-hot H ⇒ Gram is an equality matrix;
#                              exact via sort + segment-sum, O(S·d))
# ---------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _pex_embed(group: int, layout, book, table: jax.Array, ids: jax.Array,
               acc: jax.Array, slot):
    return jnp.take(table, ids, axis=0), acc


def _pex_embed_fwd(group, layout, book, table, ids, acc, slot):
    # `table` rides along only as a shape/dtype reference for the scatter;
    # it is a live parameter anyway, so this costs no extra memory.
    return (jnp.take(table, ids, axis=0), acc), (ids, table)


def _pex_embed_bwd(group, layout, book, res, cts):
    ids, table = res
    zbar, acc_bar = cts
    flat_ids = ids.reshape(-1)
    flat_z = zbar.reshape(-1, zbar.shape[-1])
    dtable = jnp.zeros_like(table).at[flat_ids].add(flat_z.astype(table.dtype))
    with jax.named_scope(STATS):
        dacc = layout.add_embedding(acc_bar, ids, zbar, group)
    entry = None if book is None else (zbar,)
    return dtable, _int_zero_cotangent(ids), dacc, entry


_pex_embed.defvjp(_pex_embed_fwd, _pex_embed_bwd)


# ---------------------------------------------------------------------------
# tap-site provenance — the metadata the static analyzer consumes
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PexOpInfo:
    """Shape of one instrumented op as it appears in a traced jaxpr.

    Slots index the op's *differentiable* operands, i.e. the
    ``custom_vjp_call_jaxpr`` equation's invars after its ``num_consts``
    prefix. ``weight_slots`` hold the parameter whose per-example stat
    this op registers (the gradient path the tap covers);
    ``data_slots`` are the operands whose gradient flows *through* the
    op to earlier layers; the last operand is always the accumulator.
    ``analysis.coverage`` uses this to decide which taint survives an
    op: a parameter reaching the loss only via weight slots is tapped,
    one that also reaches it through any plain op is undercounted.
    """
    name: str
    weight_slots: Tuple[int, ...]
    data_slots: Tuple[int, ...]
    n_operands: int


#: registered backward rule → op provenance. Keyed by the *function
#: object* jax stores in the equation's ``bwd`` parameter, which is
#: robust to renaming and wrapping (``identify_pex_bwd`` unwraps it).
PEX_OPS = {
    _pex_dense_bwd: PexOpInfo("dense", (1,), (0,), 3),
    _pex_dense_batched_bwd: PexOpInfo("dense_batched", (1,), (0,), 3),
    _pex_dense_expert_bwd: PexOpInfo("dense_expert", (1,), (0, 2, 3), 5),
    _pex_dense_expert_grouped_bwd: PexOpInfo(
        "dense_expert_grouped", (1,), (0, 2, 3), 5),
    _pex_bias_bwd: PexOpInfo("bias_add", (1,), (0,), 3),
    _pex_scale_bwd: PexOpInfo("scale", (1,), (0,), 3),
    _pex_embed_bwd: PexOpInfo("embedding", (0,), (1,), 3),
}

_PEX_OPS_BY_NAME = {fn.__name__: info for fn, info in PEX_OPS.items()}


def identify_pex_bwd(bwd) -> Optional[PexOpInfo]:
    """Resolve a ``custom_vjp_call_jaxpr`` equation's ``bwd`` parameter
    to the pex op it belongs to (None for foreign custom_vjps, e.g. the
    flash-attention kernel). jax stores ``bwd`` as a bound method of a
    ``WrappedFun`` holding the raw registered function in ``.f``; fall
    back to matching the wrapped repr against the registered names so
    a jax-internal relayering doesn't silently blind the analyzer."""
    raw = getattr(getattr(bwd, "__self__", None), "f", None)
    if raw is None:
        raw = getattr(bwd, "f", None)
    info = PEX_OPS.get(raw)
    if info is not None:
        return info
    label = repr(raw) if raw is not None else repr(bwd)
    for name, info in _PEX_OPS_BY_NAME.items():
        if name in label:
            return info
    return None


# ---------------------------------------------------------------------------
# the trace-time collector (pex v2)
# ---------------------------------------------------------------------------

class Tap:
    """Trace-time tap collector: owns the accumulator so models never
    thread it. Create one per traced function (``Engine`` does this),
    pass it down; every op mutates ``tap``'s held accumulator at trace
    time, which keeps the value chain explicit in the jaxpr:

        tap = Tap(spec, acc=layout.init(batch))
        z = tap.dense(h, w, group="mlp")
        ...
        acc_out = tap.carry()     # return so the chain stays live

    Crossing a transform boundary (``lax.scan`` body, ``jax.checkpoint``)
    requires the accumulator to be an explicit input/output of the inner
    function — use ``pex.scan`` / ``pex.checkpoint`` (this module's
    ``scan`` / ``checkpoint``), or ``carry()`` / ``set_carry()`` by hand.

    A tap with ``spec.enabled=False`` or no accumulator is inert: every
    op is its plain counterpart (``NULL`` is the shared inert tap), so
    the same model code serves uninstrumented.

    A live tap handed a ``Booking`` (``book``: the plan layer traces
    the loss for the book, ``core.book``) books its sites: its dense,
    bias, scale and embedding ops list them, hand back their book
    entries, or replay them (``_booked``); the other ops have no booked
    form.
    """
    __slots__ = ("spec", "layout", "_acc", "_token_losses", "_book")

    def __init__(self, spec: PexSpec, acc: Optional[jax.Array] = None,
                 layout=None, book: Optional["B.Booking"] = None):
        self.spec = spec
        self.layout = layout if layout is not None \
            else ExampleLayout(spec.n_groups)
        self._acc = acc
        self._token_losses = None
        self._book = book if self.live else None

    @property
    def live(self) -> bool:
        """True when ops actually register stats."""
        return self.spec.enabled and self._acc is not None

    # -- accumulator plumbing (transform boundaries) --------------------
    def carry(self):
        """Current accumulator value (to return / put in a scan carry)."""
        return self._acc

    def set_carry(self, acc) -> None:
        """Rebind the accumulator (entering a scan body / after a scan)."""
        self._acc = acc

    # -- per-token loss registration (plan layer) ------------------------
    def token_loss(self, token_losses: jax.Array) -> jax.Array:
        """Register the per-token loss map ℓ_{j,t} (shape (B, S, ...))
        — an identity op. Canonical losses call this on the token
        losses *before* reducing them to the (B,) loss vector; the
        plan layer (``core.plan``) then seeds its token-weighted
        reweighting backward through this output, which is what makes
        ``Clip(C, granularity="token")`` one fused pass. Inert taps
        record nothing (``NULL`` stays stateless across traces)."""
        if self.live:
            self._token_losses = token_losses if self._token_losses is None \
                else self._token_losses + token_losses
        return token_losses

    def token_losses(self) -> Optional[jax.Array]:
        """The registered per-token loss map for this trace (or None)."""
        return self._token_losses

    # -- the book (core/book.py) -----------------------------------------
    def _booked(self, kind: str, op, w, bookable: bool, h=None, ids=None):
        """The site ``op(w)`` under the booking: ``(done, z, book,
        slot)``. Discover lists the site (``op(stop_gradient(w))`` keeps
        the forward's data path and cuts the weight's); replay routes
        the site's weighted contraction to ``w``; record returns what
        the op's backward rule books (``book``) and the zero slot that
        carries it."""
        b = self._book
        if b is None:
            return False, None, None, None
        if b.mode == B.DISCOVER:
            z = op(jax.lax.stop_gradient(w))
            if not bookable:
                b.refuse()
            elif kind == "dense":
                b.site(kind, ((h.shape, h.dtype), (z.shape, z.dtype)), h=h)
            elif kind == "embedding":
                b.site(kind, ((z.shape, z.dtype),))
            else:                       # the (B, p) per-example gradient
                b.site(kind, (((z.shape[0], w.shape[0]), _ACC_DTYPE),))
            return True, z, None, None
        if b.mode == B.REPLAY:
            return True, B.replay(b, self, kind, w, jax.eval_shape(op, w),
                                  ids=ids), None, None
        site, slot = b.site(kind, h=h)
        return False, None, ("z" if site.h_from is not None else "hz"), slot

    # -- ops -------------------------------------------------------------
    def dense(self, h, w, *, group: str = "all",
              method: Optional[str] = None) -> jax.Array:
        """Instrumented matmul. Plain einsum when the tap is inert."""
        if not self.live:
            return jnp.einsum("...i,io->...o", h, w)
        done, z, book, slot = self._booked(
            "dense", lambda v: jnp.einsum("...i,io->...o", h, v), w,
            w.ndim == 2 and h.ndim >= 2, h=h)
        if done:
            return z
        m = method or self.spec.method
        z, self._acc = _pex_dense(m, self.spec.use_pallas,
                                  self.spec.group_index(group), self.layout,
                                  book, h, w, self._acc, slot)
        return z

    def dense_batched(self, h, w, *, group: str = "all",
                      method: Optional[str] = None) -> jax.Array:
        """Instrumented per-example-weight matmul: h (B,[S,]p_in),
        w (B,p_in,p_out) — example j multiplies weight slice j (its
        tenant's gathered LoRA factor). Stats default to the spec's
        ``seg_method`` (the 3-D path runs the segmented-direct
        estimator, same knob as the MoE expert taps)."""
        if not self.live:
            return jnp.einsum("b...i,bio->b...o", h, w)
        if self._book is not None:          # no booked form
            self._book.refuse()
            return jnp.einsum("b...i,bio->b...o", h, w)
        m = method or self.spec.seg_method
        z, self._acc = _pex_dense_batched(
            m, self.spec.use_pallas, self.spec.group_index(group),
            self.layout, h, w, self._acc)
        return z

    def bias_add(self, x, b, *, group: str = "all") -> jax.Array:
        if not self.live:
            return x + b
        done, z, book, slot = self._booked(
            "bias_add", lambda v: x + v, b,
            b.ndim == 1 and x.ndim >= 2 and x.shape[-1] == b.shape[0])
        if done:
            return z
        z, self._acc = _pex_bias(self.spec.group_index(group), self.layout,
                                 book, x, b, self._acc, slot)
        return z

    def scale(self, h, g, *, group: str = "all") -> jax.Array:
        if not self.live:
            return h * g
        done, z, book, slot = self._booked(
            "scale", lambda v: h * v, g,
            g.ndim == 1 and h.ndim >= 2 and h.shape[-1] == g.shape[0])
        if done:
            return z
        z, self._acc = _pex_scale(self.spec.group_index(group), self.layout,
                                  book, h, g, self._acc, slot)
        return z

    def embedding(self, table, ids, *, group: str = "embed") -> jax.Array:
        if not (self.live and self.spec.tap_embeddings):
            return jnp.take(table, ids, axis=0)
        done, z, book, slot = self._booked(
            "embedding", lambda v: jnp.take(v, ids, axis=0), table,
            table.ndim == 2 and ids.ndim >= 1, ids=ids)
        if done:
            return z
        z, self._acc = _pex_embed(self.spec.group_index(group), self.layout,
                                  book, table, ids, self._acc, slot)
        return z

    def _expert_tok(self, seg, tok):
        """Validate/default the slot→token-position table: required at
        token granularity (the capacity shuffle loses positions without
        it — nn.moe threads its dispatch sort's table automatically);
        at example granularity an absent table becomes an inert
        sentinel so legacy call sites keep working."""
        if tok is not None:
            return tok
        if isinstance(self.layout, TokenLayout):
            raise ValueError(
                "granularity='token' expert taps need token positions: "
                "pass tok= (slot → flat token id, as produced by "
                "nn.moe's dispatch sort); without it the (B, S) map "
                "cannot be scattered")
        return jnp.full_like(seg, -1)

    def dense_expert(self, x, w, seg, tok=None, *,
                     group: str = "moe") -> jax.Array:
        """Instrumented per-expert matmul. x (E,C,d), w (E,d,f), seg (E,C)
        int example ids (>= batch ⇒ padding row, excluded from stats);
        tok (E,C) flat token positions (>= B·S ⇒ padding row), required
        for TokenLayout."""
        if not self.live:
            return jnp.einsum("ecd,edf->ecf", x, w)
        if self._book is not None:          # no booked form
            self._book.refuse()
            return jnp.einsum("ecd,edf->ecf", x, w)
        tok = self._expert_tok(seg, tok)
        z, self._acc = _pex_dense_expert(
            self.spec.group_index(group), self._acc.shape[0],
            self.spec.seg_method, self.spec.use_pallas, self.layout,
            x, w, seg, tok, self._acc)
        return z

    def dense_expert_grouped(self, x, w, seg, bg: int, tok=None, *,
                             group: str = "moe") -> jax.Array:
        """Grouped instrumented expert matmul. x (G,E,C,d), w (E,d,f),
        seg (G,E,C) group-local example ids (>= bg ⇒ padding row); tok
        (G,E,C) group-local flat token ids (>= bg·S ⇒ padding row),
        required for TokenLayout."""
        if not self.live:
            return jnp.einsum("gecd,edf->gecf", x, w)
        if self._book is not None:          # no booked form
            self._book.refuse()
            return jnp.einsum("gecd,edf->gecf", x, w)
        tok = self._expert_tok(seg, tok)
        z, self._acc = _pex_dense_expert_grouped(
            self.spec.group_index(group), bg,
            self.spec.seg_method, self.spec.use_pallas, self.layout,
            x, w, seg, tok, self._acc)
        return z


#: Shared inert tap: every op is its plain counterpart. Serving /
#: oracle paths pass this instead of constructing a disabled Tap.
NULL = Tap(DISABLED)


def scan(body, init, xs, *, tap: Optional[Tap] = None, length=None,
         reverse: bool = False, unroll=1, remat: bool = False, policy=None):
    """``lax.scan`` with the tap's accumulator threaded through the
    carry — the v2 replacement for hand-carrying ``acc``.

    ``body(carry, x) -> (carry, y)`` uses ``tap`` from its enclosing
    scope exactly like straight-line code; this wrapper makes the
    accumulator an explicit carry element so the tap chain survives the
    scan boundary. ``remat=True`` applies ``jax.checkpoint`` (with
    ``policy``) to the (acc-explicit) body. With an inert/absent tap
    this is a plain ``lax.scan`` — the DCE property is untouched.

    Under a booking (``core.book``) the body is a level of the book of
    its own: the scan takes the level's slots or entries as extra
    ``xs``, so the record pass's entries come back stacked on the
    scan's axis. The replay needs no remat: its body keeps no
    activations.
    """
    if tap is None or not tap.live:
        fn = jax.checkpoint(body, policy=policy) if remat else body
        return jax.lax.scan(fn, init, xs, length=length, reverse=reverse,
                            unroll=unroll)

    book = tap._book
    if book is None:
        def threaded(carry, x):
            c, acc = carry
            tap.set_carry(acc)
            c, y = body(c, x)
            return (c, tap.carry()), y
    else:
        entries = book.scan_xs()
        level = []
        xs = (xs, entries)
        remat = remat and book.mode != B.REPLAY

        def threaded(carry, x):
            c, acc = carry
            x, e = x
            book.enter(e)
            tap.set_carry(acc)
            c, y = body(c, x)
            level[:] = book.leave()
            return (c, tap.carry()), y

    fn = jax.checkpoint(threaded, policy=policy) if remat else threaded
    (c, acc), ys = jax.lax.scan(fn, (init, tap.carry()), xs, length=length,
                                reverse=reverse, unroll=unroll)
    tap.set_carry(acc)
    if book is not None:
        n = length if length is not None else \
            jax.tree_util.tree_leaves(xs[0])[0].shape[0]
        book.close_scan(n, level)
    return c, ys


def checkpoint(fn, *, tap: Optional[Tap] = None, policy=None):
    """``jax.checkpoint`` with the tap's accumulator made explicit, so a
    rematerialized block neither leaks tracers nor severs the tap chain.
    Returns a function with ``fn``'s signature."""
    if tap is None or not tap.live:
        return jax.checkpoint(fn, policy=policy)

    def explicit(acc, *args, **kw):
        tap.set_carry(acc)
        out = fn(*args, **kw)
        return out, tap.carry()

    inner = jax.checkpoint(explicit, policy=policy)

    def outer(*args, **kw):
        out, acc = inner(tap.carry(), *args, **kw)
        tap.set_carry(acc)
        return out

    return outer


