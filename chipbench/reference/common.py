"""Plain float32 building blocks shared by the model references.

Everything here is straightforward ``jax.numpy`` in float32 with every
matrix product at ``Precision.HIGHEST``; nothing is imported from the
system under test. ``mode`` selects the precision of the matrix
products:

  * ``"f32"`` — the reference itself;
  * ``"fp8"`` — the control: every operand of every matrix product
    (weights, activations, attention scores) is rounded to float8 with
    a per-tensor scale before an exact product, and so is every
    cotangent on the way back (e4m3 forward, e5m2 backward, the usual
    fp8 training recipe). It stands for the step below the bfloat16
    that the configurations state.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def _fake_quant(x, dtype):
    """Round ``x`` to ``dtype`` under a power-of-two per-tensor scale;
    the result keeps ``x``'s dtype (exact: a float8 value times a power
    of two is a bfloat16 and a float32 value)."""
    amax = jnp.max(jnp.abs(x)).astype(F32)
    scale = jnp.exp2(jnp.ceil(jnp.log2(
        jnp.where(amax > 0, amax / float(jnp.finfo(dtype).max), 1.0))))
    return ((x / scale).astype(dtype).astype(F32) * scale).astype(x.dtype)


@jax.custom_vjp
def fp8(x):
    """Round to float8 e4m3 with a per-tensor scale; the cotangent is
    rounded to e5m2 the same way."""
    return _fake_quant(x, jnp.float8_e4m3fn)


def _fp8_fwd(x):
    return _fake_quant(x, jnp.float8_e4m3fn), None


def _fp8_bwd(_, g):
    return (_fake_quant(g, jnp.float8_e5m2),)


fp8.defvjp(_fp8_fwd, _fp8_bwd)


def einsum(spec: str, a, b, mode: str):
    if mode == "fp8":
        a, b = fp8(a), fp8(b)
    elif mode != "f32":
        raise ValueError(f"unknown reference mode {mode!r}")
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=F32)


def rmsnorm(x, g, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def rope(x, theta: float):
    """Rotary embedding, half-split (``rotate_half``) convention.
    x (S, H, D) at positions 0..S-1."""
    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def causal_gqa(q, k, v, mode: str):
    """Causal softmax attention; q (S, H, D), k/v (S, KV, D), query
    head h reads key/value head h // (H / KV)."""
    s, h, d = q.shape
    rep = h // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    logits = einsum("shd,thd->hst", q, k, mode) * (d ** -0.5)
    mask = jnp.tril(jnp.ones((s, s), bool))
    logits = jnp.where(mask[None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    return einsum("hst,thd->shd", probs, v, mode).reshape(s, h * d)


def token_xent_sum(logits, labels):
    """Σ_t −log softmax(logits_t)[labels_t] for one example."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[:, None], axis=-1))


def normal_tree(key, shapes: dict, dtype):
    """One draw per leaf of ``shapes`` (path → (shape, std) or a
    constant): std·N(0, 1) in ``dtype``, or the constant filled."""
    out = {}
    keys = jax.random.split(key, len(shapes))
    for k, (path, spec) in zip(keys, sorted(shapes.items())):
        shape, std, leaf_dtype = spec
        leaf_dtype = leaf_dtype or dtype
        if isinstance(std, str):            # "ones"
            out[path] = jnp.ones(shape, leaf_dtype)
        else:
            out[path] = (std * jax.random.normal(k, shape, F32)
                         ).astype(leaf_dtype)
    return out


def nest(flat: dict) -> dict:
    """{"a/b/c": x} → {"a": {"b": {"c": x}}}."""
    root: dict = {}
    for path, v in flat.items():
        node = root
        *head, last = path.split("/")
        for part in head:
            node = node.setdefault(part, {})
        node[last] = v
    return root


def leaf_paths(tree) -> list:
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in jax.tree_util.tree_leaves_with_path(tree)]
