"""The book: a clipped gradient from the norms backward's own
``(H, Z̄)`` — the book-keeping form of ghost clipping (Bu et al.,
"Differentially Private Optimization on Large Model at Small Cost",
ICML 2023; DESIGN.md §2, §9).

The two-backward clipping plan replays the whole backward a second time
only to scale each example's ``Z̄`` by its weight ``w_j`` (clip
coefficient × user loss weight) and take ``Σ_j w_j H_jᵀ Z̄_j``. By
linearity — no op mixes examples — the second backward's cotangents
are exactly ``w_j · Z̄_j``, so the norms backward can keep what each
tap site sees and the weighted gradient becomes one contraction per
site, with no second ``vjp_fn`` application. Three steps, each a trace
of the same loss with a ``Booking`` handed to its live ``Tap`` (the
plan layer's ``acc_loss(params, acc, batch, book)``):

  * **discover** (``plan``): a shape-only trace that lists the tap
    sites in call order, per level (a ``taps.scan`` body is a level of
    its own, stacked on its length). It also proves, on the loss's
    forward-mode linearization, that no trained leaf reaches the loss
    except through a site's weight operand — the book cannot give an
    untapped gradient — and sizes the book against the device;
  * **record**: each site's backward rule returns its book entry as the
    cotangent of a zero slot fed to the forward (scan bodies take their
    slots as ``xs``, so the scan's transpose stacks the entries):
    dense ``(H, Z̄)`` — ``Z̄`` alone where an earlier dense site of the
    level read the same ``H`` (q/k/v, gate/up); bias and scale the
    ``(B, p)`` per-example gradient in float32; embedding ``Z̄``;
  * **replay** (``grads``): the loss traced again, each site's op
    reading its entry. A custom VJP routes the site's weighted
    contraction to its weight operand, and JAX's transpose of the
    replay carries it back through the scans' slicing and the casts to
    the parameter leaves. No activation backward is traced; the
    replay's forward output is dead code. Where the contraction scales
    an entry by ``w`` (its batch axis still whole) it marks the result
    ``book_fold``: the privacy pass's evidence that the weights came in
    before the sum over the batch.

``plan`` returns None — the caller keeps the two-backward form — unless
every site has a booked form (MoE expert taps and the per-example
``dense_batched`` do not), every trained leaf is tapped, and the book
fits its room: ``book bytes ≤ room``. The room is the caller's where it
has measured one (``Engine`` passes ``Plan.book_room``; the ``Trainer``
sets it to 0 where its compiled step with the book would not fit the
device, ``step_overflows``), else half the least ``bytes_limit`` of the
local devices — a property of the shapes and the device, not of what
happens to be allocated when the step is traced. A device that reports
no memory limit (the CPU) sets no room.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.provenance import mark_book, mark_fold

_F32 = jnp.float32

DISCOVER = "discover"
RECORD = "record"
REPLAY = "replay"


@dataclasses.dataclass(frozen=True)
class Site:
    """One booked tap site: its op, and the (shape, dtype) of each
    array its entry holds. ``h_from``: for a dense site, the index in
    its level of the earlier dense site whose ``H`` it shares."""
    kind: str
    avals: Tuple[Tuple[Tuple[int, ...], Any], ...]
    h_from: Optional[int] = None


@dataclasses.dataclass(frozen=True)
class Scan:
    """A ``taps.scan`` over ``length`` steps whose body holds ``sites``
    (a level); its entries are stacked on a leading ``length`` axis."""
    length: int
    sites: Tuple


def _arrays(level, lead=()):
    """(shape, dtype) of every array a level's entries hold, leading
    scan axes included, in flatten order."""
    for item in level:
        if isinstance(item, Scan):
            yield from _arrays(item.sites, lead + (item.length,))
        else:
            for shape, dtype in item.avals:
                yield lead + tuple(shape), dtype


def nbytes(level) -> int:
    return sum(int(np.prod(s)) * np.dtype(d).itemsize
               for s, d in _arrays(level))


def zeros(level, lead=()):
    """The record pass's zero slots: a pytree shaped like the book."""
    out = []
    for item in level:
        if isinstance(item, Scan):
            out.append(zeros(item.sites, lead + (item.length,)))
        else:
            out.append(tuple(jnp.zeros(lead + tuple(s), d)
                             for s, d in item.avals))
    return out


# ---------------------------------------------------------------------------
# the booking a loss's Tap is handed
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _Level:
    """One level of the book as a trace walks it: the loss's top level
    or the body of a ``taps.scan``."""
    sites: list              # discover: as met; else: as discovered
    entries: Any = None      # record: the zero slots; replay: the book
    pos: int = 0             # record/replay: the next site's index
    inputs: list = dataclasses.field(default_factory=list)
    # ^ (index, H) of each dense site met so far: who shares an H


class Booking:
    """Trace-time book state of one trace of the loss. ``mode``:
    ``DISCOVER`` lists the sites; ``RECORD`` hands each site its zero
    slot; ``REPLAY`` hands each site its entry and the (B,) weights.
    Levels nest with ``taps.scan`` bodies: ``enter`` / ``leave``."""

    def __init__(self, mode: str, level=None, entries=None, weights=None):
        self.mode = mode
        self.ok = True              # discover: every site has a form
        self.weights = weights
        self._stack = [_Level(list(level or ()), entries)]

    # -- levels --------------------------------------------------------
    def _next(self):
        lv = self._stack[-1]
        if lv.pos >= len(lv.sites):
            raise RuntimeError("book out of step with the loss: more sites "
                               "than were booked")
        return lv, lv.sites[lv.pos]

    def scan_xs(self):
        """Entries (record: zero slots) of the scan the caller is about
        to run, from the current level; None in discover."""
        if self.mode == DISCOVER:
            return None
        lv, item = self._next()
        if not isinstance(item, Scan):
            raise RuntimeError("book out of step with the loss: a scan "
                               "where a site was booked")
        return lv.entries[lv.pos]

    def enter(self, xs=None) -> None:
        """A scan body starts (traced): a level of its own; ``xs`` are
        its entries for one step."""
        sites = [] if self.mode == DISCOVER else \
            list(self._next()[1].sites)
        self._stack.append(_Level(sites, xs))

    def leave(self) -> list:
        lv = self._stack.pop()
        if self.mode != DISCOVER and lv.pos != len(lv.sites):
            raise RuntimeError("book out of step with the loss: a scan "
                               "body used fewer sites than were booked")
        return lv.sites

    def close_scan(self, length: int, body_sites) -> None:
        """After the scan: discover records it; the others step past."""
        lv = self._stack[-1]
        if self.mode == DISCOVER:
            lv.sites.append(Scan(int(length), tuple(body_sites)))
        else:
            lv.pos += 1

    @property
    def level(self) -> Tuple:
        return tuple(self._stack[0].sites)

    # -- sites ---------------------------------------------------------
    def site(self, kind: str, avals=(), h=None):
        """Discover: record a site (a dense one reading the ``H`` of an
        earlier one books ``Z̄`` alone). Record/replay: ``(site, its
        slot or entry)``; a replayed dense entry leads with its ``H``."""
        lv = self._stack[-1]
        if self.mode == DISCOVER:
            h_from = None
            if kind == "dense":
                h_from = next((i for i, x in lv.inputs if x is h), None)
                lv.inputs.append((len(lv.sites), h))
                if h_from is not None:
                    avals = avals[1:]
            lv.sites.append(Site(kind, tuple(avals), h_from))
            return None
        _, spec = self._next()
        if isinstance(spec, Scan) or spec.kind != kind:
            raise RuntimeError(f"book out of step with the loss: a {kind} "
                               f"site where {spec} was booked")
        entry = lv.entries[lv.pos]
        if self.mode == RECORD and kind == "dense":
            lv.inputs.append((lv.pos, h))
            if spec.h_from is not None and \
                    dict(lv.inputs)[spec.h_from] is not h:
                raise RuntimeError("book out of step with the loss: a "
                                   "dense site's H is not the one booked")
        lv.pos += 1
        if self.mode == REPLAY and spec.h_from is not None:
            entry = (lv.entries[spec.h_from][0],) + tuple(entry)
        return spec, entry

    def refuse(self) -> None:
        """A site with no booked form (discover)."""
        self.ok = False


# ---------------------------------------------------------------------------
# what each site books, and the weighted contraction of its entry
# ---------------------------------------------------------------------------

def per_example_sum(x) -> jax.Array:
    """(B, p) float32: Σ over every axis between the first and last."""
    x = x.astype(_F32)
    return jnp.sum(x, axis=tuple(range(1, x.ndim - 1))) if x.ndim > 2 else x


def _fold(x, w):
    """``x`` scaled per example by ``w``, in ``x``'s dtype, marked where
    its batch axis is still whole (``provenance.mark_fold``)."""
    x = (x.astype(_F32) * w.reshape((-1,) + (1,) * (x.ndim - 1))
         ).astype(x.dtype)
    return mark_fold(x, batch=w.shape[0])


def contract(kind: str, entry, w, shape, dtype) -> jax.Array:
    """``Σ_j w_j ∂L_j/∂W`` of one site from its entry: the weight
    gradient the second backward would have built, in the site's weight
    dtype. Dense matmuls accumulate in float32 (``preferred_element_
    type``), as the tapped op's own backward does; the per-example
    weight scales the smaller operand."""
    w = w.astype(_F32)
    if kind == "dense":
        h, z = entry
        if h.size <= z.size:
            h = _fold(h, w)
        else:
            z = _fold(z, w)
        g = jnp.einsum("b...i,b...o->io", h, z, preferred_element_type=_F32)
    elif kind in ("bias_add", "scale"):
        (g,) = entry
        g = jnp.sum(_fold(g, w), axis=0)
    elif kind == "embedding":
        z, ids = entry
        z = _fold(z, w)
        g = jnp.zeros(shape, dtype).at[ids.reshape(-1)].add(
            z.reshape(-1, z.shape[-1]).astype(dtype))
    else:
        raise ValueError(f"no booked form for a {kind} site")
    return g.astype(dtype)


@partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _routed(kind, shape, dtype, w, acc, entry, weights):
    return acc


def _routed_fwd(kind, shape, dtype, w, acc, entry, weights):
    return acc, (entry, weights)


def _routed_bwd(kind, shape, dtype, res, acc_bar):
    entry, weights = res
    return contract(kind, entry, weights, shape, dtype), acc_bar, None, None


_routed.defvjp(_routed_fwd, _routed_bwd)


def replay(booking: Booking, tap, kind: str, w, out, ids=None):
    """A site's op under the replay: route its weighted contraction to
    the weight operand ``w`` (the cotangent of the scalar the replay
    returns is 1) and return a dead zero output shaped as ``out``."""
    _, entry = booking.site(kind)
    if ids is not None:
        entry = tuple(entry) + (ids,)
    tap.set_carry(_routed(kind, tuple(w.shape), jnp.dtype(w.dtype), w,
                          tap.carry(), tuple(entry), booking.weights))
    return jnp.zeros(out.shape, out.dtype)


# ---------------------------------------------------------------------------
# plan and replay, as the plan layer calls them
# ---------------------------------------------------------------------------

def _abstract(tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x)),
        tree)


def _untapped_leaves(closed, n_params: int) -> bool:
    """Does any tangent input (a trained leaf) reach the tangent output
    of the discover trace? Its sites hold their weights behind
    ``stop_gradient``, so only an untapped path can carry one."""
    from jax._src.interpreters import partial_eval as pe
    jaxpr = closed.jaxpr
    _, used = pe.dce_jaxpr(jaxpr, [True] * len(jaxpr.outvars))
    return any(used[n_params:2 * n_params])


def bytes_limit() -> Optional[int]:
    """Least ``bytes_limit`` over the local devices; None where no
    device reports one (the CPU)."""
    limits = [s["bytes_limit"]
              for s in (d.memory_stats() for d in jax.local_devices())
              if s and "bytes_limit" in s]
    return min(limits) if limits else None


def default_room() -> Optional[int]:
    """The book's room where the caller has measured none: half the
    device's memory, the other half for the step's own live set."""
    limit = bytes_limit()
    return None if limit is None else limit // 2


def compiled_bytes(compiled) -> int:
    """Device bytes a compiled program needs, by XLA's own account:
    its arguments, its outputs not aliased to a donated argument, and
    its temporaries."""
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


def step_overflows(jitted, *args) -> bool:
    """Would ``jitted``'s program on ``args`` need more than the least
    device ``bytes_limit``? False where no device reports one. The
    compile is the one the first call would make: a jitted function
    called on the same shapes afterwards runs it."""
    limit = bytes_limit()
    if limit is None:
        return False
    return compiled_bytes(jitted.lower(*args).compile()) > limit


def plan(acc_loss: Callable, params, batch, acc0,
         room: Optional[int] = None) -> Optional[Tuple]:
    """The book's level for this loss, or None where the booked form
    does not apply (module docstring). ``room``: the bytes the book may
    take; None for ``default_room``."""
    leaves = jax.tree_util.tree_leaves(params)
    if not leaves or not all(jnp.issubdtype(jnp.result_type(x),
                                            jnp.floating) for x in leaves):
        return None
    booking = Booking(DISCOVER)

    def tangent(p, t, b, acc):
        return jax.jvp(lambda q: acc_loss(q, acc, b, booking)[0],
                       (p,), (t,))[1]

    closed = jax.make_jaxpr(tangent)(*_abstract((params, params, batch,
                                                 acc0)))
    level = booking.level
    if not booking.ok or not level or _untapped_leaves(closed, len(leaves)):
        return None
    if room is None:
        room = default_room()
    if room is not None and nbytes(level) > room:
        return None
    return level


def grads(acc_loss: Callable, params, batch, level, entries, weights):
    """The weighted gradient from the book: the replay's gradient."""
    entries = mark_book(entries)

    def routed(p):
        booking = Booking(REPLAY, level, entries, weights)
        return acc_loss(p, jnp.zeros((), _F32), batch, booking)[2]

    return jax.grad(routed)(params)
