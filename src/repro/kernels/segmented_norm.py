"""Segmented direct-norm kernel: per-segment ||Σ_{t∈seg} h_t z̄_tᵀ||_F²
for token-major layers — the MoE expert capacity buffers.

The MoE expert taps need the direct estimator over *rows of a capacity
buffer*: row t belongs to example seg(t), and example j's partial
gradient for one expert is G_j = Σ_{t: seg(t)=j} h_t z̄_tᵀ. Until this
kernel existed that stat ran as an XLA ``lax.scan`` over token blocks
with a ``segment_sum`` scatter per block — the last stat hot path with
no Pallas kernel (ROADMAP). The scatter formulation keeps a dense
``(B, chunk_in, p_out)`` carry live through the whole scan and
round-trips it through HBM every block.

Here the segment scatter is replaced by a **sort**: the wrapper
(kernels/ops.py) orders rows by segment id, after which each segment is
a contiguous row run and its partial gradient is an ordinary masked
matmul. The run structure is encoded in five scalar-prefetched i32
tables — one entry per *work item* w, a (token block × segment) run:

    blk[w]   token-block index — steers the H / Z̄ panel DMAs
             (the same BlockSpec-index-map trick as the gram kernel's
             triangular tile-pair tables, but data-dependent: the
             tables are jnp arrays computed from the seg ids at trace
             time, not numpy constants)
    r0/r1[w] the run's row range inside the block (rows outside it
             belong to neighbouring segments and are masked to zero)
    seg[w]   output slot (dropped segments and padded work items carry
             r0 == r1 — an all-zero mask — and never fold)
    first/last[w]  run boundaries: ``first`` zeroes the VMEM
             accumulator, ``last`` squares-and-folds it

The grid is ``(k_in, k_out, n_work)`` with the work axis innermost: for
one (C_in, C_out) feature block the whole sorted sequence streams once,
the per-segment partial gradient living only as a (C_in × C_out) f32
VMEM scratch that is reset at ``first`` and squared at ``last``. The
fold lands in a per-(ci, co) output **row** of shape (1, 1, n_seg) via
a one-hot lane select (no dynamic VMEM store — Mosaic-safe); the
wrapper reduces the (k_in·k_out, 1, n_seg) partials. Nothing of size
``(B, p_in, p_out)`` ever exists in HBM or VMEM.

VMEM budget at Tt=128, C_in=C_out=512, f32 inputs:
    2 panels · 128·512·4 B = 512 KiB + scratch 512·512·4 B = 1 MiB
    + out row n_seg·4 B
well under the ~16 MiB/core budget; MXU dims are 128-aligned.

Consecutive work items in the same token block reuse the resident
panels (Pallas skips the DMA when the block index repeats), so HBM
traffic is the sorted panels once per feature block. The wrapper's
sort + gather of both panels is an O(T·log T + T·(p_in+p_out)) XLA
preamble outside the kernel; ``ops.segmented_cost`` deliberately omits
it — it is lower-order against the kernel's O(T·p_in·p_out) MXU work
whenever the kernel is worth launching, and near the crossover the
dispatch is protected instead by the measured-best assertion in
benchmarks/bench_segmented.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def flop_estimate(n_work: int, tile_t: int, p_in: int, p_out: int,
                  n_seg: int) -> int:
    """MXU+fold flops at the padded launch tiles.

    Every work item — including padded dummies, which still issue the
    masked dot on a zero panel — costs 2·Tt·C_in·C_out per (ci, co)
    step, i.e. 2·Tt·p_in·p_out over the whole feature grid; run
    splitting and padding are thus charged at the grid the kernel
    actually launches. Each segment close adds one square-and-reduce
    (2 flops/element) over its (p_in, p_out) partial gradient.
    """
    return int(2 * n_work * tile_t * p_in * p_out
               + 2 * min(n_seg, n_work) * p_in * p_out)


def bytes_estimate(n_work: int, tile_t: int, p_in: int, p_out: int,
                   n_seg: int, *, chunk_in: int, chunk_out: int,
                   itemsize: int = 4) -> int:
    """HBM traffic: each work item streams one H panel per p_out block
    column and one Z̄ panel per p_in block row (panels resident across
    same-block items are still charged — the estimate is a ceiling),
    plus the (k_in·k_out, 1, n_seg) partial outputs."""
    n_ci = p_in // chunk_in
    n_co = p_out // chunk_out
    panels = n_work * tile_t * (chunk_in * n_ci * n_co + chunk_out * n_ci * n_co)
    return int(panels * itemsize + n_ci * n_co * n_seg * 4)


def launch_contract(t: int, p_in: int, p_out: int, n_seg_pad: int,
                    n_work: int, *, tile_t: int = 128, chunk_in: int = 512,
                    chunk_out: int = 512, dtype=jnp.float32):
    """Static launch geometry of :func:`segmented_norm_sorted` at padded
    shapes — the analyzer-checkable contract (kernels/contract.py)."""
    from repro.kernels.contract import Block, Divisibility, LaunchContract
    return LaunchContract(
        kernel="segmented_norm",
        grid=(max(p_in // chunk_in, 1), max(p_out // chunk_out, 1),
              max(n_work, 1)),
        blocks=(
            Block("h", (tile_t, chunk_in), dtype),
            Block("zbar", (tile_t, chunk_out), dtype),
            Block("out", (1, 1, n_seg_pad), jnp.float32, kind="out",
                  accumulator=True),
            Block("g_acc", (chunk_in, chunk_out), jnp.float32,
                  kind="scratch", accumulator=True),
        ),
        divisibility=(
            Divisibility("t", t, tile_t),
            Divisibility("p_in", p_in, chunk_in),
            Divisibility("p_out", p_out, chunk_out),
            Divisibility("n_seg_pad", n_seg_pad, 128),
        ),
        scalar_prefetch=6,
        # masked HᵀZ̄ contraction per work item across all block columns
        flops=2.0 * max(n_work, 1) * tile_t * p_in * p_out,
    )


def _kernel(blk_ref, r0_ref, r1_ref, seg_ref, first_ref, last_ref,
            h_ref, z_ref, out_ref, g_acc):
    w = pl.program_id(2)

    @pl.when(w == 0)
    def _init_out():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(first_ref[w] == 1)
    def _init_scratch():
        g_acc[...] = jnp.zeros_like(g_acc)

    # rows outside [r0, r1) belong to neighbouring segments: zero them on
    # the H side only — a zero row contributes nothing to HᵀZ̄
    rows = jax.lax.broadcasted_iota(jnp.int32, h_ref.shape, 0)
    mask = jnp.logical_and(rows >= r0_ref[w], rows < r1_ref[w])
    hm = jnp.where(mask, h_ref[...], jnp.zeros_like(h_ref))
    g_acc[...] += jax.lax.dot_general(
        hm, z_ref[...], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(last_ref[w] == 1)
    def _fold():
        partial = jnp.sum(jnp.square(g_acc[...]))
        # one-hot lane select instead of a dynamic VMEM store
        lanes = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 2)
        out_ref[...] += jnp.where(lanes == seg_ref[w], partial, 0.0)


@functools.partial(jax.jit, static_argnames=("n_seg_pad", "tile_t",
                                             "chunk_in", "chunk_out",
                                             "interpret"))
def segmented_norm_sorted(blk: jax.Array, r0: jax.Array, r1: jax.Array,
                          seg: jax.Array, first: jax.Array, last: jax.Array,
                          h: jax.Array, zbar: jax.Array, *, n_seg_pad: int,
                          tile_t: int = 128, chunk_in: int = 512,
                          chunk_out: int = 512,
                          interpret: bool = False) -> jax.Array:
    """h: (T, p_in), zbar: (T, p_out) — rows SORTED by segment id — plus
    the six (n_work,) i32 run tables → (n_seg_pad,) f32 per-segment
    ||G_j||². The wrapper in ops.py builds the tables (ops._run_tables)
    and guarantees T % tile_t == 0, p_in % chunk_in == 0,
    p_out % chunk_out == 0 and n_seg_pad % 128 == 0.
    """
    t, p_in = h.shape
    p_out = zbar.shape[-1]
    assert t % tile_t == 0, (t, tile_t)
    assert p_in % chunk_in == 0, (p_in, chunk_in)
    assert p_out % chunk_out == 0, (p_out, chunk_out)
    assert n_seg_pad % 128 == 0, n_seg_pad
    k_in = p_in // chunk_in
    k_out = p_out // chunk_out
    n_work = blk.shape[0]

    cost = pl.CostEstimate(
        flops=flop_estimate(n_work, tile_t, p_in, p_out, n_seg_pad),
        transcendentals=0,
        bytes_accessed=bytes_estimate(n_work, tile_t, p_in, p_out, n_seg_pad,
                                      chunk_in=chunk_in, chunk_out=chunk_out,
                                      itemsize=h.dtype.itemsize),
    )

    def h_map(ci, co, w, blk_r, r0_r, r1_r, seg_r, first_r, last_r):
        return (blk_r[w], ci)

    def z_map(ci, co, w, blk_r, r0_r, r1_r, seg_r, first_r, last_r):
        return (blk_r[w], co)

    def out_map(ci, co, w, blk_r, r0_r, r1_r, seg_r, first_r, last_r):
        return (ci * k_out + co, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(k_in, k_out, n_work),
        in_specs=[
            pl.BlockSpec((tile_t, chunk_in), h_map),
            pl.BlockSpec((tile_t, chunk_out), z_map),
        ],
        # a (1, n_seg_pad) block of a 2-D array breaks Mosaic's (8, 128)
        # tiling rule; the unit middle axis equals its array dim
        out_specs=pl.BlockSpec((1, 1, n_seg_pad), out_map),
        scratch_shapes=[pltpu.VMEM((chunk_in, chunk_out), jnp.float32)],
    )
    partials = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((k_in * k_out, 1, n_seg_pad),
                                       jnp.float32),
        cost_estimate=cost,
        interpret=interpret,
    )(blk, r0, r1, seg, first, last, h, zbar)
    return jnp.sum(partials, axis=(0, 1))
