"""Batched REMIX query engine — pure-JAX reference implementation.

All operations are vectorized over a query batch (Q,). The "iterator" of the
paper becomes an integer *view position*: because the sorted view is
persisted, any position can be decoded to (run, in-run index) with the
group's cursor offsets + selector occurrence counts, so `next` is position+1
— comparison-free, exactly the paper's claim, and gather-friendly on TPU.
This is what makes :class:`repro.db.cursor.RemixCursor` cheap: `seek` runs
once, the position is plain host state, and every later window is a pure
:func:`gather_view` decode (`peek`/`next`/`skip` are position arithmetic —
no key comparison ever re-runs).

Two in-group search modes (paper §3.2 / Fig 11 "full" vs "partial"):
  - ``vector``: decode all D slots, compare in parallel (VPU-native; on TPU
    this replaces the paper's SIMD-assisted *linear* scan and is the fast
    default — a deliberate hardware adaptation);
  - ``binary``: sequential log2(D) probes, each decoding one slot via
    occurrence counting (the paper's CPU-oriented full binary search).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core import keys as K
from repro.core.remix import Remix
from repro.core.runs import RunSet
from repro.core.view import NEWEST_BIT, PLACEHOLDER


def ingroup_for_backend(backend: str | None = None) -> str:
    """The in-group search mode for ``backend`` (default: the attached
    one): binary probes on the CPU, where gathers are scalar-expensive,
    and the all-slot vector compare elsewhere (§Perf)."""
    backend = backend or jax.default_backend()
    return "binary" if backend == "cpu" else "vector"


def decode_groups(remix: Remix, runset: RunSet, g: jnp.ndarray):
    """Decode whole groups. ``g``: any int32 shape (clamped to valid range).

    Returns dict of per-slot arrays with shape g.shape + (D,):
      runid, absidx, newest, pad, keys (.. + (KW,)).
    """
    d, r = remix.d, remix.r
    g = jnp.clip(g, 0, remix.g - 1)
    sels = remix.selectors.reshape(remix.g, d)[g].astype(jnp.int32)  # (..,D)
    pad = sels == PLACEHOLDER
    newest = (sels & NEWEST_BIT) != 0
    runid = jnp.where(pad, 0, sels & 0x7F)
    onehot = (runid[..., None] == jnp.arange(r, dtype=jnp.int32)) & ~pad[..., None]
    onehot = onehot.astype(jnp.int32)  # (.., D, R)
    occ = jnp.cumsum(onehot, axis=-2) - onehot  # exclusive occurrence count
    occ = jnp.sum(occ * onehot, axis=-1)  # (.., D) own-run occurrence
    base = jnp.take_along_axis(remix.cursors[g], runid, axis=-1)  # (.., D)
    absidx = base + occ
    keys, vals, seq, tomb = runset.gather(runid, absidx)
    keys = jnp.where(pad[..., None], K.UINT32_MAX, keys)
    return dict(
        runid=runid, absidx=absidx, newest=newest & ~pad, pad=pad,
        keys=keys, vals=vals, seq=seq, tomb=tomb & ~pad,
    )


def _ingroup_vector(remix, runset, g, queries):
    """First slot in group g with key >= query, all-D parallel compare."""
    dec = decode_groups(remix, runset, g)  # (Q, D, ..)
    ge = ~K.key_lt(dec["keys"], queries[:, None, :])  # (Q, D)
    s = jnp.argmax(ge, axis=1).astype(jnp.int32)
    s = jnp.where(jnp.any(ge, axis=1), s, remix.d)
    # landing on a placeholder means the true lower bound is the next group
    is_pad = jnp.take_along_axis(
        dec["pad"], jnp.clip(s, 0, remix.d - 1)[:, None], axis=1
    )[:, 0]
    s = jnp.where((s < remix.d) & is_pad, remix.d, s)
    return s


def _decode_one_slot(
    remix: Remix, runset: RunSet, g: jnp.ndarray, j: jnp.ndarray, full=False
):
    """Decode slot j of group g via §3.2 occurrence counting. g,j: (Q,)."""
    d = remix.d
    g = jnp.clip(g, 0, remix.g - 1)
    sels = remix.selectors.reshape(remix.g, d)[g].astype(jnp.int32)  # (Q,D)
    pad = sels == PLACEHOLDER
    sel_j = jnp.take_along_axis(sels, j[:, None], axis=1)[:, 0]
    pad_j = sel_j == PLACEHOLDER
    run_j = jnp.where(pad_j, 0, sel_j & 0x7F)
    before = jnp.arange(d)[None, :] < j[:, None]
    occ = jnp.sum(
        ((sels & 0x7F) == run_j[:, None]) & ~pad & before, axis=1
    ).astype(jnp.int32)
    base = jnp.take_along_axis(remix.cursors[g], run_j[:, None], axis=1)[:, 0]
    keys, vals, seq, tomb = runset.gather(run_j, base + occ)
    keys = jnp.where(pad_j[:, None], K.UINT32_MAX, keys)
    if full:
        newest = ((sel_j & NEWEST_BIT) != 0) & ~pad_j
        return keys, vals, newest, tomb & ~pad_j, pad_j
    return keys, pad_j


def _ingroup_binary(remix, runset, g, queries):
    """Paper-faithful in-group binary search (log2 D sequential probes)."""
    d = remix.d
    q = queries.shape[0]
    lo = jnp.zeros((q,), jnp.int32)
    hi = jnp.full((q,), d, jnp.int32)
    steps = max(1, d.bit_length())

    def body(_, state):
        lo, hi = state
        active = lo < hi
        mid = (lo + hi) >> 1
        kmid, _ = _decode_one_slot(remix, runset, g, jnp.clip(mid, 0, d - 1))
        go_right = K.key_lt(kmid, queries)
        lo = jnp.where(active & go_right, mid + 1, lo)
        hi = jnp.where(active & ~go_right, mid, hi)
        return lo, hi

    lo, _ = jax.lax.fori_loop(0, steps, body, (lo, hi))
    # placeholder landing → next group
    _, pad_j = _decode_one_slot(remix, runset, g, jnp.clip(lo, 0, d - 1))
    return jnp.where((lo < d) & pad_j, d, lo)


@partial(jax.jit, static_argnames=("ingroup",))
def seek(remix: Remix, runset: RunSet, queries: jnp.ndarray, ingroup: str = "vector"):
    """Lower-bound view positions for ``queries`` (Q, KW) → (Q,) int32.

    One binary search on the anchors + one in-group search — the paper's
    seek. Returned positions may be ``n_slots`` (end) or point at the head
    of the next group when a group's keys are all smaller.
    """
    queries = jnp.asarray(queries, jnp.uint32)
    g = K.upper_bound(remix.anchors, queries) - 1
    g = jnp.clip(g, 0, remix.g - 1)
    if ingroup == "vector":
        s = _ingroup_vector(remix, runset, g, queries)
    elif ingroup == "binary":
        s = _ingroup_binary(remix, runset, g, queries)
    else:
        raise ValueError(f"unknown ingroup mode {ingroup!r}")
    return jnp.minimum(g * remix.d + s, remix.n_slots)


@partial(jax.jit, static_argnames=("width", "ingroup", "with_vals"))
def scan(
    remix: Remix,
    runset: RunSet,
    queries: jnp.ndarray,
    width: int,
    ingroup: str = "vector",
    with_vals: bool = True,
):
    """Seek + retrieve ``width`` consecutive view slots per query.

    Returns (keys (Q,W,KW), vals (Q,W,VW), valid (Q,W), pos (Q,)). ``valid``
    masks placeholders, old versions, tombstones and end-of-view; the next
    operation itself performs **no key comparisons** — it is a pure decode
    of the persisted selectors (paper §3.3).

    ``with_vals=False`` returns None for vals — callers that only need
    the key stream (e.g. ``scan_batch``'s (keys, valid) shape) drop the
    value gather entirely (XLA dead-code-eliminates it).
    """
    pos = seek(remix, runset, queries, ingroup=ingroup)
    keys, vals, valid = gather_view(remix, runset, pos, width)
    return keys, (vals if with_vals else None), valid, pos


@partial(jax.jit, static_argnames=("width",))
def gather_view(remix: Remix, runset: RunSet, pos: jnp.ndarray, width: int):
    """Decode ``width`` view slots starting at each ``pos`` (comparison-free).

    The cursor window primitive: ``pos`` may come from :func:`seek` *or*
    from a previous window's ``pos + width`` — positions are stable host
    integers, so streaming readers (``db.cursor()``) chain windows
    without ever re-seeking. Slots past ``n_slots`` (or in padded
    groups) simply decode as invalid."""
    d = remix.d
    q = pos.shape[0]
    ng = (width + d - 1) // d + 1
    g0 = jnp.clip(pos // d, 0, remix.g - 1)
    gs = g0[:, None] + jnp.arange(ng, dtype=jnp.int32)[None, :]  # (Q, NG)
    dec = decode_groups(remix, runset, gs)  # (Q, NG, D, ..)

    def flat(x):
        return x.reshape((q, ng * d) + x.shape[3:])

    off = pos - g0 * d  # 0 <= off <= D (off==D when pos is next-group head)

    def slice_one(x, o):
        return jax.lax.dynamic_slice_in_dim(x, o, width, axis=0)

    take = lambda x: jax.vmap(slice_one)(flat(x), off)
    keys, vals = take(dec["keys"]), take(dec["vals"])
    newest, pad, tomb = take(dec["newest"]), take(dec["pad"]), take(dec["tomb"])
    gslot = pos[:, None] + jnp.arange(width, dtype=jnp.int32)[None, :]
    in_view = gslot < jnp.minimum(remix.n_slots, (g0 + ng) * d)[..., None]
    valid = newest & ~pad & ~tomb & in_view
    return keys, vals, valid


@partial(jax.jit, static_argnames=("ingroup",))
def get(remix: Remix, runset: RunSet, queries: jnp.ndarray, ingroup: str = "vector"):
    """Point query: seek + single-slot decode (no bloom filters, paper §4).

    Returns (found (Q,), vals (Q,VW)).
    """
    queries = jnp.asarray(queries, jnp.uint32)
    pos = seek(remix, runset, queries, ingroup=ingroup)
    d = remix.d
    g, j = pos // d, pos % d
    keys, vals, newest, tomb, pad_j = _decode_one_slot(
        remix, runset, g, j, full=True
    )
    found = (
        (pos < remix.n_slots) & newest & ~tomb & K.key_eq(keys, queries)
    )
    return found, vals
