"""Provenance markers — identity ops that make the DP pipeline's
privacy-critical values identifiable in a traced jaxpr (DESIGN.md §12).

The plan layer's DP invariants (clip applied per example *before* the
batch sum, noise injected exactly once *after* the gradient psum, PRNG
keys never reused) are properties of the traced program, but a raw
jaxpr gives the static analyzer nothing to anchor on: a clip
coefficient is just a ``min``, a noise sample just a ``mul`` of a
``random_bits``. ``pex_mark`` is a custom primitive that behaves as
the identity everywhere — impl, abstract eval, lowering (it vanishes
from HLO), jvp/transpose (linear pass-through), vmap — while carrying
a static ``(tag, meta)`` payload that survives into the jaxpr:

    e:f32[4] = pex_mark[tag=clip_coef meta=(('clip_norm', 1.0), ...)] d

``analysis.privacy`` walks the step jaxpr and treats each tag as a
semantic anchor:

  * ``clip_coef``  — the per-example (or per-token) clip coefficients,
    meta: clip_norm, eps, granularity;
  * ``grad_seed``  — a cotangent seed entering a backward application,
    meta: kind ∈ {'plain', 'norms', 'weighted'} (weighted = the
    clip × importance × user-weight product);
  * ``noise``      — one leaf's DP noise sample, meta: noise_std,
    scale, leaf index;
  * ``rng_use``    — a PRNG key at its point of consumption, meta:
    purpose + index (the key-lineage single-use check hangs off these);
  * ``book``       — one array of the norms backward's book (``core.
    book``: a site's H, Z̄ or per-example gradient) where the weighted
    contraction reads it, meta: leaf index. Book values are per example,
    not yet summed over the batch;
  * ``book_fold``  — a book array scaled per example by the weights
    (clip coefficient × user loss weight) while its leading batch axis
    is still whole, meta: the batch size.

Marker placement is production code (``core.passes``, ``core.plan``,
``core.clipping``, ``core.importance``) — the analyzer only *reads*
them, and the mutation corpus (tests/test_pexlint_mutation.py) proves
each invariant trips when the marked logic is broken.
"""
from __future__ import annotations

from typing import Optional, Tuple

from jax.extend import core as jex_core
from jax.interpreters import ad, batching, mlir

#: the primitive name as it appears in jaxpr equations
MARK_PRIMITIVE = "pex_mark"

#: known tags (an unknown tag in a trace is an analyzer error — it
#: means a marker was added without teaching the privacy pass about it)
TAG_CLIP = "clip_coef"
TAG_SEED = "grad_seed"
TAG_NOISE = "noise"
TAG_RNG = "rng_use"
TAG_SAMPLE = "sample_idx"
TAG_GLEAF = "grad_leaf"
TAG_BOOK = "book"
TAG_FOLD = "book_fold"
KNOWN_TAGS = frozenset({TAG_CLIP, TAG_SEED, TAG_NOISE, TAG_RNG, TAG_SAMPLE,
                        TAG_GLEAF, TAG_BOOK, TAG_FOLD})

mark_p = jex_core.Primitive(MARK_PRIMITIVE)
mark_p.def_impl(lambda x, *, tag, meta: x)
mark_p.def_abstract_eval(lambda x, *, tag, meta: x)
mlir.register_lowering(mark_p, lambda ctx, x, *, tag, meta: [x])
# linear in x ⇒ jvp passes the tangent through; the transpose drops the
# marker from the cotangent (a marked value's adjoint is not itself a
# clip coefficient / noise sample — re-marking it would lie)
ad.deflinear2(mark_p, lambda ct, x, *, tag, meta: [ct])
batching.primitive_batchers[mark_p] = \
    lambda args, dims, *, tag, meta: (
        mark_p.bind(args[0], tag=tag, meta=meta), dims[0])


def _static(v):
    """Meta values must be hashable jaxpr params; traced values (rare —
    e.g. a σ computed on-device) degrade to None rather than failing
    the trace."""
    if v is None or isinstance(v, (bool, int, float, str)):
        return v
    try:
        return float(v)
    except Exception:
        return None


def mark(x, tag: str, **meta):
    """Identity on ``x``; records ``(tag, meta)`` in the jaxpr."""
    items = tuple(sorted((k, _static(v)) for k, v in meta.items()))
    return mark_p.bind(x, tag=tag, meta=items)


def mark_clip(c, *, clip_norm, eps, granularity: str):
    return mark(c, TAG_CLIP, clip_norm=clip_norm, eps=eps,
                granularity=granularity)


def mark_seed(seed, *, kind: str):
    """kind: 'plain' (unweighted fold / user-only weights absent),
    'norms' (the ones seed of the norms backward), 'weighted' (the
    clip × importance × user-weight product)."""
    return mark(seed, TAG_SEED, kind=kind)


def mark_noise(sample, *, noise_std, scale, leaf: int):
    return mark(sample, TAG_NOISE, noise_std=noise_std, scale=scale,
                leaf=leaf)


def mark_sample(indices, *, k: int):
    """Mark importance-sampling indices at the selection boundary.
    Selection lineage (which examples were drawn depends on the norms)
    is not *scaling* lineage — the privacy pass's clip-before-sum check
    must not confuse a norm-guided gather with an unclipped seed — so
    the analyzer launders seed taint here."""
    return mark(indices, TAG_SAMPLE, k=k)


def mark_grad_leaf(g, *, leaf: int):
    """Mark one summed-gradient leaf at the plan/optimizer boundary —
    after GNS reads the raw gradient, before noise and the apply. The
    traffic pass (``analysis.traffic``) anchors its per-leaf HBM-stream
    counting on these: every fusion component downstream that re-reads
    a full leaf-sized array derived from this marker is one more pass
    over the gradient in HBM."""
    return mark(g, TAG_GLEAF, leaf=leaf)


def mark_grad_tree(grads):
    """``mark_grad_leaf`` over every leaf of a gradient pytree, in the
    tree's flatten order (== the parameter tree's leaf order)."""
    from jax.tree_util import tree_flatten, tree_unflatten
    leaves, tree = tree_flatten(grads)
    return tree_unflatten(
        tree, [mark_grad_leaf(g, leaf=i) for i, g in enumerate(leaves)])


def mark_book(entries):
    """Mark every array of the book (a pytree) where the weighted
    contraction reads it."""
    from jax.tree_util import tree_flatten, tree_unflatten
    leaves, tree = tree_flatten(entries)
    return tree_unflatten(
        tree, [mark(x, TAG_BOOK, leaf=i) for i, x in enumerate(leaves)])


def mark_fold(x, *, batch: int):
    """Mark a book array scaled per example by the weights, before the
    contraction sums it over its leading (batch) axis."""
    return mark(x, TAG_FOLD, batch=batch)


def mark_rng(key, *, purpose: str, index: Optional[int] = None):
    """Mark a PRNG key at its point of consumption. Every key that
    feeds a sampling primitive must pass through exactly one of these;
    two ``rng_use`` marks with the same key *lineage* are a reuse."""
    return mark(key, TAG_RNG, purpose=purpose, index=index)


def meta_dict(meta: Tuple) -> dict:
    """The ``meta`` param of a pex_mark equation, as a dict."""
    return dict(meta)
