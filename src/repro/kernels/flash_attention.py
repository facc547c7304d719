"""Flash attention (causal, GQA, optional softcap/window) — Pallas TPU.

The §Perf Cell-C analysis (EXPERIMENTS.md) shows the dense-arch memory
term is dominated by the S² f32 score tensor hitting HBM. This kernel
keeps scores VMEM-resident with the online-softmax recurrence:

    m ← max(m, rowmax(S_blk));  P = exp(S_blk − m)
    l ← l·corr + rowsum(P);     acc ← acc·corr + P·V_blk

Grid (B, Hq, Sq/bq, Sk/bk), k-block innermost; scratch (acc, m, l)
carries the running state per (b, h, qi). GQA is handled in the index
maps (kv head = q head // rep — no KV broadcast materialized). Causal
blocks above the diagonal contribute nothing and are masked; gemma2's
softcap folds into the score transform; a static sliding window adds a
second mask term.

VMEM at bq=bk=256, D=128, bf16 in / f32 scratch:
  q(64 KB) + k(64 KB) + v(64 KB) + acc(128 KB) + m,l(2 KB) ≪ 16 MB.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e30


def _scores(q_ref, k_ref, scale, softcap, window, qi, kj, bq, bk):
    """Masked (softcapped) score block + mask, shared by fwd and bwd."""
    q = q_ref[0, 0].astype(jnp.float32)
    k = k_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = kpos <= qpos
    if window is not None:
        mask &= (qpos - kpos) < window
    return jnp.where(mask, s, NEG_INF), mask


def _kernel(scale: float, softcap: Optional[float], window: Optional[int],
            bq: int, bk: int, n_k: int,
            q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref):
    qi = pl.program_id(2)
    kj = pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # skip fully-masked blocks (strictly above the causal diagonal)
    @pl.when(kj * bk <= qi * bq + bq - 1)
    def _compute():
        s, _ = _scores(q_ref, k_ref, scale, softcap, window, qi, kj, bq, bk)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(kj == n_k - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = m_ref[...] + jnp.log(l)


def launch_contracts(b: int, hq: int, hkv: int, sq: int, sk: int, d: int, *,
                     block_q: int = 256, block_k: int = 256,
                     dtype=jnp.float32):
    """Static launch geometry of the forward, dq, and dkv kernels — the
    analyzer-checkable contracts (kernels/contract.py). Returns one
    contract per ``pallas_call`` the differentiable attention op
    issues."""
    from repro.kernels.contract import Block, Divisibility, LaunchContract
    rep = max(hq // max(hkv, 1), 1)
    n_q = max(sq // block_q, 1)
    n_k = max(sk // block_k, 1)
    div = (
        Divisibility("hq % hkv", hq, max(hkv, 1)),
        Divisibility("sq", sq, block_q),
        Divisibility("sk", sk, block_k),
    )
    # per (batch, query-head): QKᵀ and PV are each 2·sq·sk·d; the
    # backward kernels recompute P and add the dP/dQ (resp. dK/dV)
    # contractions on top
    attn_flops = float(b) * hq * sq * sk * d
    fwd = LaunchContract(
        kernel="flash_attention.fwd",
        grid=(b, hq, n_q, n_k),
        blocks=(
            Block("q", (1, 1, block_q, d), dtype),
            Block("k", (1, 1, block_k, d), dtype),
            Block("v", (1, 1, block_k, d), dtype),
            Block("o", (1, 1, block_q, d), dtype, kind="out"),
            Block("lse", (1, 1, block_q, 1), jnp.float32, kind="out"),
            Block("acc", (block_q, d), jnp.float32, kind="scratch",
                  accumulator=True),
            Block("m", (block_q, 1), jnp.float32, kind="scratch",
                  accumulator=True),
            Block("l", (block_q, 1), jnp.float32, kind="scratch",
                  accumulator=True),
        ),
        divisibility=div,
        flops=4.0 * attn_flops,
    )
    dq = LaunchContract(
        kernel="flash_attention.bwd_dq",
        grid=(b, hq, n_q, n_k),
        blocks=(
            Block("q", (1, 1, block_q, d), dtype),
            Block("k", (1, 1, block_k, d), dtype),
            Block("v", (1, 1, block_k, d), dtype),
            Block("do", (1, 1, block_q, d), dtype),
            Block("lse", (1, 1, block_q, 1), jnp.float32),
            Block("delta", (1, 1, block_q, 1), jnp.float32),
            Block("dq", (1, 1, block_q, d), dtype, kind="out"),
            Block("dq_acc", (block_q, d), jnp.float32, kind="scratch",
                  accumulator=True),
        ),
        divisibility=div,
        flops=6.0 * attn_flops,
    )
    dkv = LaunchContract(
        kernel="flash_attention.bwd_dkv",
        grid=(b, max(hkv, 1), n_k, rep, n_q),
        blocks=(
            Block("q", (1, 1, block_q, d), dtype),
            Block("k", (1, 1, block_k, d), dtype),
            Block("v", (1, 1, block_k, d), dtype),
            Block("do", (1, 1, block_q, d), dtype),
            Block("lse", (1, 1, block_q, 1), jnp.float32),
            Block("delta", (1, 1, block_q, 1), jnp.float32),
            Block("dk", (1, 1, block_k, d), dtype, kind="out"),
            Block("dv", (1, 1, block_k, d), dtype, kind="out"),
            Block("dk_acc", (block_k, d), jnp.float32, kind="scratch",
                  accumulator=True),
            Block("dv_acc", (block_k, d), jnp.float32, kind="scratch",
                  accumulator=True),
        ),
        divisibility=div,
        flops=8.0 * attn_flops,
    )
    return (fwd, dq, dkv)


@functools.partial(jax.jit, static_argnames=(
    "scale", "softcap", "window", "block_q", "block_k", "interpret",
    "return_lse"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    scale: float, softcap: Optional[float] = None,
                    window: Optional[int] = None, block_q: int = 256,
                    block_k: int = 256, interpret: bool = False,
                    return_lse: bool = False):
    """q (B,Hq,Sq,D), k/v (B,Hkv,Sk,D), Hq % Hkv == 0 → (B,Hq,Sq,D)
    [+ log-sum-exp (B,Hq,Sq,1) when return_lse, for the backward kernels;
    the unit minor axis keeps its (block_q, 1) block tile-legal].

    Causal; caller pads Sq/Sk to block multiples (padded k rows land
    above the diagonal or in masked tail — exact)."""
    b, hq, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    assert hq % hkv == 0 and sq % block_q == 0 and sk % block_k == 0
    rep = hq // hkv
    n_k = sk // block_k
    grid = (b, hq, sq // block_q, n_k)

    o, lse = pl.pallas_call(
        functools.partial(_kernel, scale, softcap, window, block_q, block_k,
                          n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, h, qi, kj: (bi, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, h, qi, kj: (bi, h // rep, kj, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, h, qi, kj: (bi, h // rep, kj, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, h, qi, kj: (bi, h, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda bi, h, qi, kj: (bi, h, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b, hq, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return (o, lse) if return_lse else o


# ---------------------------------------------------------------------------
# backward kernels: dq (grid over q blocks) and dk/dv (grid over k blocks)
#
#   P   = exp(S − lse)
#   dP  = dO Vᵀ ;  dS = P ⊙ (dP − D),  D = rowsum(dO ⊙ O)
#   (softcap chain rule: dS_raw = dS · (1 − (S_cap/cap)²))
#   dQ  = dS K · scale ;  dK = dSᵀ Q · scale ;  dV = Pᵀ dO
# ---------------------------------------------------------------------------

def _bwd_scores(q_ref, k_ref, scale, softcap, window, qi, kj, bq, bk):
    """Returns (p-basis scores BEFORE exp (already capped), mask, and the
    softcap chain factor on raw scores)."""
    q = q_ref[0, 0].astype(jnp.float32)
    k = k_ref[0, 0].astype(jnp.float32)
    s_raw = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        t = jnp.tanh(s_raw / softcap)
        s = softcap * t
        chain = 1.0 - t * t
    else:
        s = s_raw
        chain = None
    qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = kpos <= qpos
    if window is not None:
        mask &= (qpos - kpos) < window
    return jnp.where(mask, s, NEG_INF), mask, chain


def _dq_kernel(scale, softcap, window, bq, bk, n_k,
               q_ref, k_ref, v_ref, do_ref, lse_ref, dvec_ref, dq_ref,
               acc_ref):
    qi = pl.program_id(2)
    kj = pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(kj * bk <= qi * bq + bq - 1)
    def _compute():
        s, mask, chain = _bwd_scores(q_ref, k_ref, scale, softcap, window,
                                     qi, kj, bq, bk)
        p = jnp.exp(s - lse_ref[0, 0])
        do = do_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - dvec_ref[0, 0])
        if chain is not None:
            ds = ds * chain
        ds = jnp.where(mask, ds, 0.0)
        k = k_ref[0, 0].astype(jnp.float32)
        acc_ref[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(kj == n_k - 1)
    def _finalize():
        dq_ref[0, 0] = acc_ref[...].astype(dq_ref.dtype)


def _dkv_kernel(scale, softcap, window, bq, bk, n_q, rep,
                q_ref, k_ref, v_ref, do_ref, lse_ref, dvec_ref,
                dk_ref, dv_ref, dk_acc, dv_acc):
    kj = pl.program_id(2)
    r = pl.program_id(3)
    qi = pl.program_id(4)

    @pl.when(jnp.logical_and(qi == 0, r == 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(kj * bk <= qi * bq + bq - 1)
    def _compute():
        s, mask, chain = _bwd_scores(q_ref, k_ref, scale, softcap, window,
                                     qi, kj, bq, bk)
        p = jnp.exp(s - lse_ref[0, 0])
        do = do_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        q = q_ref[0, 0].astype(jnp.float32)
        dv_acc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - dvec_ref[0, 0])
        if chain is not None:
            ds = ds * chain
        ds = jnp.where(mask, ds, 0.0)
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(jnp.logical_and(qi == n_q - 1, r == rep - 1))
    def _finalize():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "scale", "softcap", "window", "block_q", "block_k", "interpret"))
def flash_attention_bwd(q, k, v, o, lse, do, *, scale,
                        softcap: Optional[float] = None,
                        window: Optional[int] = None, block_q: int = 256,
                        block_k: int = 256, interpret: bool = False):
    """Backward kernels. Returns (dq, dk, dv). GQA: consecutive q heads
    sharing a kv head accumulate into the same dk/dv block (the h axis
    iterates sequentially; scratch carries the partial across the rep
    group)."""
    b, hq, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    rep = hq // hkv
    n_q, n_k = sq // block_q, sk // block_k
    dvec = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1,
                   keepdims=True)

    q_map = lambda bi, h, qi, kj: (bi, h, qi, 0)
    k_map = lambda bi, h, qi, kj: (bi, h // rep, kj, 0)
    lse_map = lambda bi, h, qi, kj: (bi, h, qi, 0)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale, softcap, window, block_q,
                          block_k, n_k),
        grid=(b, hq, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), q_map),
            pl.BlockSpec((1, 1, block_k, d), k_map),
            pl.BlockSpec((1, 1, block_k, d), k_map),
            pl.BlockSpec((1, 1, block_q, d), q_map),
            pl.BlockSpec((1, 1, block_q, 1), lse_map),
            pl.BlockSpec((1, 1, block_q, 1), lse_map),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d), q_map),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, dvec)

    # dk/dv: grid (b, hkv, kj, r, qi) — the rep-group (r) and q-block (qi)
    # loops are innermost so the scratch accumulation for one kv block is
    # contiguous; the block is emitted once per (b, hkv, kj)
    qk_map = lambda bi, hk, kj, r, qi: (bi, hk * rep + r, qi, 0)
    kk_map = lambda bi, hk, kj, r, qi: (bi, hk, kj, 0)
    lsek_map = lambda bi, hk, kj, r, qi: (bi, hk * rep + r, qi, 0)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale, softcap, window, block_q,
                          block_k, n_q, rep),
        grid=(b, hkv, n_k, rep, n_q),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), qk_map),
            pl.BlockSpec((1, 1, block_k, d), kk_map),
            pl.BlockSpec((1, 1, block_k, d), kk_map),
            pl.BlockSpec((1, 1, block_q, d), qk_map),
            pl.BlockSpec((1, 1, block_q, 1), lsek_map),
            pl.BlockSpec((1, 1, block_q, 1), lsek_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, d), kk_map),
            pl.BlockSpec((1, 1, block_k, d), kk_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, do, lse, dvec)
    return dq, dk, dv
