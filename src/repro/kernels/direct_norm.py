"""Direct per-example norm kernel: ||H_jᵀZ̄_j||_F² block-by-block.

The ``direct`` estimator forms each example's partial gradient
``G_j = H_jᵀ Z̄_j ∈ (p_in, p_out)`` and squares it — exact under weight
sharing and, by the cost model in ``core.norms``, cheaper than the
Gram-pair route whenever S is large relative to the harmonic feature
dim (2·S·p_in·p_out < S²·(p_in+p_out)+…, i.e. every long-sequence
workload). Until this kernel existed that regime silently fell back to
a ``lax.scan`` over feature chunks that round-trips (B, chunk, p_out)
partials through HBM.

Here nothing of size (B, p_in, p_out) ever reaches HBM: the grid is
``(B, p_in/C_in, p_out/C_out, S/Ts)`` with the sequence axis innermost,
and each (C_in × C_out) block of G_j lives only as an f32 VMEM scratch
accumulator. Per step one Ts×C_in and one Ts×C_out row panel stream in,
an MXU dot contracts them over the Ts sequence rows into the block
accumulator, and when the sequence sweep completes the block is
squared-and-summed (VPU) straight into the per-example scalar. HBM
traffic is the input panels — each H panel re-read n_co times and each
Z̄ panel n_ci times — plus B output scalars.

VMEM budget at Ts=128, C_in=C_out=512, bf16 inputs:
    2 panels · 128·512·2 B = 256 KiB + scratch 512·512·4 B = 1 MiB
well under the ~16 MiB/core budget; all MXU dims are 128-aligned.

A ``pl.CostEstimate`` from :func:`flop_estimate` is attached so TPU
``cost_analysis()`` reflects the kernel's true MXU work (on CPU the
interpreter's grid loop is counted once by XLA — see gram_norm.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.gram_norm import OUT_LANES


def flop_estimate(b: int, s: int, p_in: int, p_out: int) -> int:
    """MXU+fold flops for padded shapes: the HᵀZ̄ contraction
    2·S·p_in·p_out plus one square-and-reduce (2 flops/element) over
    each example's (p_in, p_out) partial gradient. Chunking only
    reorders this work, so it does not appear here (unlike
    :func:`bytes_estimate`, where it sets the re-read factors)."""
    return int(b * (2 * s * p_in * p_out + 2 * p_in * p_out))


def bytes_estimate(b: int, s: int, p_in: int, p_out: int, *,
                   chunk_in: int = 512, chunk_out: int = 512,
                   itemsize: int = 4) -> int:
    """HBM traffic: H panels re-read once per p_out block column and Z̄
    panels once per p_in block row; G blocks never leave VMEM."""
    n_ci = p_in // chunk_in
    n_co = p_out // chunk_out
    return int(b * s * (p_in * n_co + p_out * n_ci) * itemsize + b * 4)


def launch_contract(b: int, s: int, p_in: int, p_out: int, *,
                    tile_s: int = 128, chunk_in: int = 512,
                    chunk_out: int = 512, dtype=jnp.float32):
    """Static launch geometry of :func:`direct_norm` at padded shapes —
    the analyzer-checkable contract (kernels/contract.py)."""
    from repro.kernels.contract import Block, Divisibility, LaunchContract
    return LaunchContract(
        kernel="direct_norm",
        grid=(b, max(p_in // chunk_in, 1), max(p_out // chunk_out, 1),
              max(s // tile_s, 1)),
        blocks=(
            Block("h", (1, tile_s, chunk_in), dtype),
            Block("zbar", (1, tile_s, chunk_out), dtype),
            Block("out", (1, 1, OUT_LANES), jnp.float32, kind="out"),
            Block("g_acc", (chunk_in, chunk_out), jnp.float32,
                  kind="scratch", accumulator=True),
        ),
        divisibility=(
            Divisibility("s", s, tile_s),
            Divisibility("p_in", p_in, chunk_in),
            Divisibility("p_out", p_out, chunk_out),
        ),
        # per-example HᵀZ̄ (2·S·p_in·p_out) plus the Σ(G²) fold
        flops=float(b) * (2.0 * s * p_in * p_out + 2.0 * p_in * p_out),
    )


def _kernel(n_s: int, h_ref, z_ref, out_ref, g_acc):
    ci = pl.program_id(1)
    co = pl.program_id(2)
    si = pl.program_id(3)

    @pl.when(si == 0)
    def _init_scratch():
        g_acc[...] = jnp.zeros_like(g_acc)

    # (C_in × Ts) · (Ts × C_out): contract over the sequence-tile rows
    g_acc[...] += jax.lax.dot_general(
        h_ref[0], z_ref[0], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(si == n_s - 1)
    def _fold():
        partial = jnp.sum(jnp.square(g_acc[...]))

        @pl.when(jnp.logical_and(ci == 0, co == 0))
        def _set():
            out_ref[...] = jnp.full(out_ref.shape, partial, jnp.float32)

        @pl.when(jnp.logical_or(ci != 0, co != 0))
        def _add():
            out_ref[...] += partial


@functools.partial(jax.jit, static_argnames=("tile_s", "chunk_in",
                                             "chunk_out", "interpret"))
def direct_norm(h: jax.Array, zbar: jax.Array, *, tile_s: int = 128,
                chunk_in: int = 512, chunk_out: int = 512,
                interpret: bool = False) -> jax.Array:
    """h: (B, S, p_in), zbar: (B, S, p_out) → (B,) f32.

    Caller guarantees S % tile_s == 0, p_in % chunk_in == 0 and
    p_out % chunk_out == 0 (the ops.py wrapper pads with zeros — zero
    sequence rows add nothing to HᵀZ̄ and zero feature columns add zero
    rows/columns to it, so padding is exact).
    """
    b, s, p_in = h.shape
    _, _, p_out = zbar.shape
    assert s % tile_s == 0, (s, tile_s)
    assert p_in % chunk_in == 0, (p_in, chunk_in)
    assert p_out % chunk_out == 0, (p_out, chunk_out)
    n_s = s // tile_s
    n_ci = p_in // chunk_in
    n_co = p_out // chunk_out

    cost = pl.CostEstimate(
        flops=flop_estimate(b, s, p_in, p_out),
        transcendentals=0,
        bytes_accessed=bytes_estimate(b, s, p_in, p_out, chunk_in=chunk_in,
                                      chunk_out=chunk_out,
                                      itemsize=h.dtype.itemsize),
    )
    return pl.pallas_call(
        functools.partial(_kernel, n_s),
        grid=(b, n_ci, n_co, n_s),
        in_specs=[
            pl.BlockSpec((1, tile_s, chunk_in),
                         lambda bi, ci, co, si: (bi, si, ci)),
            pl.BlockSpec((1, tile_s, chunk_out),
                         lambda bi, ci, co, si: (bi, si, co)),
        ],
        # lane-dense per-example row (see gram_norm.OUT_LANES)
        out_specs=pl.BlockSpec((1, 1, OUT_LANES),
                               lambda bi, ci, co, si: (bi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, 1, OUT_LANES), jnp.float32),
        scratch_shapes=[pltpu.VMEM((chunk_in, chunk_out), jnp.float32)],
        cost_estimate=cost,
        interpret=interpret,
    )(h, zbar)[:, 0, 0]
