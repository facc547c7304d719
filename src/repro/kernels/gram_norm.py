"""Tile-pair Gram kernel: per-example ||H_jᵀZ̄_j||_F² without the S×S Gram.

TPU adaptation of the paper's §4 quantity for weight-shared (sequence)
layers. The identity

    s_j = Σ_{t,t'} <h_t, h_t'> <z̄_t, z̄_t'>

is evaluated tile-by-tile: for each example b and each pair of sequence
tiles (i, j), two Ts×Ts MXU dots build the H-gram and Z̄-gram partials,
chunked over the (possibly different) feature dims; their elementwise
product is reduced into a per-example scalar. Nothing of size S×S ever
exists — the working set is four (Ts × C) row panels + two Ts×Ts f32
scratch accumulators in VMEM.

Symmetry halving (the default): both Grams are symmetric, so the
summand for (i, j) equals the one for (j, i). The triangular grid
``(B, n_s(n_s+1)/2, K)`` visits only pairs with i ≤ j — the flat pair
index is decoded through two scalar-prefetched i32 row/col tables
(``pltpu.PrefetchScalarGridSpec``), which the BlockSpec index maps read
to steer the panel DMAs — and folds off-diagonal contributions with
weight 2. MXU FLOPs and HBM panel traffic drop by 2n_s/(n_s+1) → 2×
versus the full (B, n_s, n_s, K) grid, which is kept (``triangular=
False``) as the regression oracle for the symmetry optimisation.

Feature chunking: K = max(p_in/C_in, p_out/C_out) — the H-gram and
Z̄-gram are chunked *independently* (C_in over p_in, C_out over p_out),
so asymmetric feature dims each pad only to their own chunk size
instead of the larger tensor's. The k axis is the innermost (fastest)
so the scratch accumulators for a given pair complete before the
product is folded into the output. Feature chunks beyond a tensor's
own chunk count are masked with ``pl.when`` (their index map clamps,
so the loads stay in bounds).

VMEM budget at Ts=128, C_in=C_out=512, bf16 inputs:
    4 panels · 128·512·2 B = 512 KiB   + 2 scratch · 128·128·4 B = 128 KiB
well under the ~16 MiB/core budget; MXU dims (128, 512) are aligned to
the 128×128 systolic array.

Both grids attach a ``pl.CostEstimate`` built from :func:`flop_estimate`
so ``compiled.cost_analysis()`` on TPU reports the true (halved) MXU
work. (On CPU the interpreter lowers the grid to a loop whose body XLA
counts once — see roofline/analysis.py — so the flop model, not the
CPU cost_analysis, is the source of truth for the 2× claim.)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: lane width of the per-example output row (one TPU vreg lane row)
OUT_LANES = 128


def flop_estimate(b: int, s: int, p_in: int, p_out: int, *,
                  tile_s: int = 128, triangular: bool = True) -> int:
    """MXU+fold flops the kernel issues for padded shapes (b, s, p_*).

    Per visited tile pair: 2·Ts²·p_in (H-gram dots) + 2·Ts²·p_out
    (Z̄-gram dots) + ~2·Ts² for the elementwise product-and-reduce fold.
    The triangular grid visits n_s(n_s+1)/2 pairs instead of n_s².
    """
    n_s = s // tile_s
    pairs = n_s * (n_s + 1) // 2 if triangular else n_s * n_s
    per_pair = 2 * tile_s * tile_s * (p_in + p_out) + 2 * tile_s * tile_s
    return int(b * pairs * per_pair)


def bytes_estimate(b: int, s: int, p_in: int, p_out: int, *,
                   tile_s: int = 128, triangular: bool = True,
                   itemsize: int = 4) -> int:
    """HBM panel traffic: four (Ts × chunk) panels per (pair, k) step."""
    n_s = s // tile_s
    pairs = n_s * (n_s + 1) // 2 if triangular else n_s * n_s
    panel_rows = 2 * tile_s * (p_in + p_out)  # h_i+h_j, z_i+z_j over all k
    return int(b * pairs * panel_rows * itemsize + b * 4)


def launch_contract(b: int, s: int, p_in: int, p_out: int, *,
                    tile_s: int = 128, chunk_in: int = 512,
                    chunk_out: int = 512, triangular: bool = True,
                    dtype=jnp.float32):
    """Static launch geometry of :func:`gram_norm` at padded shapes —
    the analyzer-checkable contract (kernels/contract.py)."""
    from repro.kernels.contract import Block, Divisibility, LaunchContract
    n_s = max(s // tile_s, 1)
    n_k = max(p_in // chunk_in, p_out // chunk_out, 1)
    pairs = n_s * (n_s + 1) // 2 if triangular else n_s * n_s
    grid = (b, pairs, n_k) if triangular else (b, n_s, n_s, n_k)
    return LaunchContract(
        kernel="gram_norm",
        grid=grid,
        blocks=(
            Block("h_i", (1, tile_s, chunk_in), dtype),
            Block("h_j", (1, tile_s, chunk_in), dtype),
            Block("z_i", (1, tile_s, chunk_out), dtype),
            Block("z_j", (1, tile_s, chunk_out), dtype),
            Block("out", (1, 1, OUT_LANES), jnp.float32, kind="out"),
            Block("a_acc", (tile_s, tile_s), jnp.float32, kind="scratch",
                  accumulator=True),
            Block("b_acc", (tile_s, tile_s), jnp.float32, kind="scratch",
                  accumulator=True),
        ),
        divisibility=(
            Divisibility("s", s, tile_s),
            Divisibility("p_in", p_in, chunk_in),
            Divisibility("p_out", p_out, chunk_out),
        ),
        scalar_prefetch=2 if triangular else 0,
        # two (Ts × p) gram contractions per pair (A += H_iH_jᵀ over p_in,
        # B += Z̄_iZ̄_jᵀ over p_out) plus the ⟨A, B⟩ fold
        flops=float(b) * pairs * (2.0 * tile_s * tile_s * (p_in + p_out)
                                  + 2.0 * tile_s * tile_s),
    )


def _tri_maps(n_s: int) -> tuple[np.ndarray, np.ndarray]:
    """Row/col tile indices of the upper triangle, row-major: pair t ↦
    (ti[t], tj[t]) with ti[t] <= tj[t]."""
    ti = np.concatenate([np.full(n_s - i, i, np.int32) for i in range(n_s)])
    tj = np.concatenate([np.arange(i, n_s, dtype=np.int32)
                         for i in range(n_s)])
    return ti, tj


def _kernel_tri(k_in: int, k_out: int, n_k: int, ti_ref, tj_ref,
                h_i_ref, h_j_ref, z_i_ref, z_j_ref, out_ref, a_acc, b_acc):
    t = pl.program_id(1)
    k = pl.program_id(2)
    i = ti_ref[t]
    j = tj_ref[t]

    @pl.when(k == 0)
    def _init_scratch():
        a_acc[...] = jnp.zeros_like(a_acc)
        b_acc[...] = jnp.zeros_like(b_acc)

    @pl.when(k < k_in)
    def _acc_h_gram():
        a_acc[...] += jax.lax.dot_general(
            h_i_ref[0], h_j_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(k < k_out)
    def _acc_z_gram():
        b_acc[...] += jax.lax.dot_general(
            z_i_ref[0], z_j_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _fold():
        # off-diagonal pairs stand in for their mirrored twin
        weight = jnp.where(i == j, 1.0, 2.0).astype(jnp.float32)
        partial = weight * jnp.sum(a_acc[...] * b_acc[...])

        @pl.when(t == 0)
        def _set():
            out_ref[...] = jnp.full(out_ref.shape, partial, jnp.float32)

        @pl.when(t != 0)
        def _add():
            out_ref[...] += partial


def _kernel_full(k_in: int, k_out: int, n_k: int,
                 h_i_ref, h_j_ref, z_i_ref, z_j_ref, out_ref, a_acc, b_acc):
    i = pl.program_id(1)
    j = pl.program_id(2)
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init_scratch():
        a_acc[...] = jnp.zeros_like(a_acc)
        b_acc[...] = jnp.zeros_like(b_acc)

    @pl.when(k < k_in)
    def _acc_h_gram():
        a_acc[...] += jax.lax.dot_general(
            h_i_ref[0], h_j_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(k < k_out)
    def _acc_z_gram():
        b_acc[...] += jax.lax.dot_general(
            z_i_ref[0], z_j_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _fold():
        partial = jnp.sum(a_acc[...] * b_acc[...])

        @pl.when(jnp.logical_and(i == 0, j == 0))
        def _set():
            out_ref[...] = jnp.full(out_ref.shape, partial, jnp.float32)

        @pl.when(jnp.logical_or(i != 0, j != 0))
        def _add():
            out_ref[...] += partial


@functools.partial(jax.jit, static_argnames=("tile_s", "chunk_in",
                                             "chunk_out", "triangular",
                                             "interpret"))
def gram_norm(h: jax.Array, zbar: jax.Array, *, tile_s: int = 128,
              chunk_in: int = 512, chunk_out: int = 512,
              triangular: bool = True,
              interpret: bool = False) -> jax.Array:
    """h: (B, S, p_in), zbar: (B, S, p_out) → (B,) f32.

    Caller guarantees S % tile_s == 0, p_in % chunk_in == 0 and
    p_out % chunk_out == 0 (the ops.py wrapper pads with zeros, which
    contribute nothing). The two feature dims are chunked independently
    so an asymmetric pair never over-pads the smaller one.
    ``triangular=False`` runs the redundant full (i, j) grid — kept as
    the regression oracle for the symmetry halving.
    """
    b, s, p_in = h.shape
    _, _, p_out = zbar.shape
    assert s % tile_s == 0, (s, tile_s)
    assert p_in % chunk_in == 0, (p_in, chunk_in)
    assert p_out % chunk_out == 0, (p_out, chunk_out)
    k_in, k_out = p_in // chunk_in, p_out // chunk_out
    n_k = max(k_in, k_out)
    n_s = s // tile_s

    cost = pl.CostEstimate(
        flops=flop_estimate(b, s, p_in, p_out, tile_s=tile_s,
                            triangular=triangular),
        transcendentals=0,
        bytes_accessed=bytes_estimate(b, s, p_in, p_out, tile_s=tile_s,
                                      triangular=triangular,
                                      itemsize=h.dtype.itemsize),
    )
    scratch = [
        pltpu.VMEM((tile_s, tile_s), jnp.float32),
        pltpu.VMEM((tile_s, tile_s), jnp.float32),
    ]
    # one lane-dense (1, 128) row per example, every lane holding the
    # example's sum: a (1, 1) block breaks Mosaic's (8, 128) tiling rule
    out_shape = jax.ShapeDtypeStruct((b, 1, OUT_LANES), jnp.float32)

    if not triangular:
        def h_map(bi, i, j, k):
            return (bi, i, jnp.minimum(k, k_in - 1))

        def h_map_j(bi, i, j, k):
            return (bi, j, jnp.minimum(k, k_in - 1))

        def z_map(bi, i, j, k):
            return (bi, i, jnp.minimum(k, k_out - 1))

        def z_map_j(bi, i, j, k):
            return (bi, j, jnp.minimum(k, k_out - 1))

        return pl.pallas_call(
            functools.partial(_kernel_full, k_in, k_out, n_k),
            grid=(b, n_s, n_s, n_k),
            in_specs=[
                pl.BlockSpec((1, tile_s, chunk_in), h_map),
                pl.BlockSpec((1, tile_s, chunk_in), h_map_j),
                pl.BlockSpec((1, tile_s, chunk_out), z_map),
                pl.BlockSpec((1, tile_s, chunk_out), z_map_j),
            ],
            out_specs=pl.BlockSpec((1, 1, OUT_LANES),
                                   lambda bi, i, j, k: (bi, 0, 0)),
            out_shape=out_shape,
            scratch_shapes=scratch,
            cost_estimate=cost,
            interpret=interpret,
        )(h, h, zbar, zbar)[:, 0, 0]

    ti, tj = _tri_maps(n_s)
    n_tri = ti.shape[0]

    def h_map_i(bi, t, k, ti_r, tj_r):
        return (bi, ti_r[t], jnp.minimum(k, k_in - 1))

    def h_map_j(bi, t, k, ti_r, tj_r):
        return (bi, tj_r[t], jnp.minimum(k, k_in - 1))

    def z_map_i(bi, t, k, ti_r, tj_r):
        return (bi, ti_r[t], jnp.minimum(k, k_out - 1))

    def z_map_j(bi, t, k, ti_r, tj_r):
        return (bi, tj_r[t], jnp.minimum(k, k_out - 1))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_tri, n_k),
        in_specs=[
            pl.BlockSpec((1, tile_s, chunk_in), h_map_i),
            pl.BlockSpec((1, tile_s, chunk_in), h_map_j),
            pl.BlockSpec((1, tile_s, chunk_out), z_map_i),
            pl.BlockSpec((1, tile_s, chunk_out), z_map_j),
        ],
        out_specs=pl.BlockSpec((1, 1, OUT_LANES),
                               lambda bi, t, k, ti_r, tj_r: (bi, 0, 0)),
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        functools.partial(_kernel_tri, k_in, k_out, n_k),
        grid_spec=grid_spec,
        out_shape=out_shape,
        cost_estimate=cost,
        interpret=interpret,
    )(jnp.asarray(ti), jnp.asarray(tj), h, h, zbar, zbar)[:, 0, 0]
