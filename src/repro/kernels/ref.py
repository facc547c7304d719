"""Pure-jnp oracles for every Pallas kernel (the ``ref.py`` contract).

These are the ground truth for the kernel allclose sweeps in
``tests/test_kernels.py`` and are themselves validated against the
naive per-example-gradient oracle in ``tests/test_pex_correctness.py``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_F32 = jnp.float32


def gram_norm_ref(h: jax.Array, zbar: jax.Array) -> jax.Array:
    """s_j = Σ_{t,t'} <h_t,h_t'><z̄_t,z̄_t'>  (== ||H_jᵀZ̄_j||_F²).

    h: (B, S, p_in), zbar: (B, S, p_out) → (B,) f32.
    """
    hh = jnp.einsum("bsi,bti->bst", h, h, preferred_element_type=_F32)
    zz = jnp.einsum("bsi,bti->bst", zbar, zbar, preferred_element_type=_F32)
    return jnp.sum(hh * zz, axis=(1, 2))


def segmented_norm_ref(h: jax.Array, zbar: jax.Array, seg_ids: jax.Array,
                       n_seg: int) -> jax.Array:
    """Per-segment ||Σ_{t:seg=j} h_t z̄_tᵀ||_F² by the masked gram
    identity; rows with ``seg_ids >= n_seg`` are dropped.

    h: (T, p_in), zbar: (T, p_out), seg_ids: (T,) int → (n_seg,) f32.
    """
    hh = jnp.einsum("si,ti->st", h, h, preferred_element_type=_F32)
    zz = jnp.einsum("si,ti->st", zbar, zbar, preferred_element_type=_F32)
    same = seg_ids[:, None] == seg_ids[None, :]
    rows = jnp.sum(jnp.where(same, hh * zz, 0.0), axis=1)
    return jax.ops.segment_sum(rows, seg_ids, num_segments=n_seg)


def rowsumsq_ref(x: jax.Array) -> jax.Array:
    """(B, N) → (B,) Σ x² in f32."""
    return jnp.sum(jnp.square(x.astype(_F32)), axis=-1)


def clip_scale_ref(z: jax.Array, c: jax.Array) -> jax.Array:
    """Scale each example's rows: (B, S, p) ⊙ c(B,) → same shape/dtype."""
    return (z * c.reshape((-1,) + (1,) * (z.ndim - 1)).astype(z.dtype))


def flash_attention_ref(q, k, v, *, scale, softcap=None, window=None):
    """Oracle: plain causal GQA attention. q (B,Hq,Sq,D), k/v (B,Hkv,Sk,D)."""
    b, hq, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    rep = hq // hkv
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(_F32), k.astype(_F32)) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    qpos = jnp.arange(sq)[:, None]
    kpos = jnp.arange(sk)[None, :]
    mask = kpos <= qpos
    if window is not None:
        mask &= (qpos - kpos) < window
    s = jnp.where(mask, s, -1.0e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(_F32)).astype(q.dtype)
