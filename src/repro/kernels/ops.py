"""Jit'd wrappers composing the Pallas kernels into full REMIX operations.

The kernels cover the compute-dense parts (anchor compare-count, selector
occurrence decode); XLA handles the HBM gathers between them (TPU gathers
are XLA's job — fusing them into Pallas would fight the memory system).

Each stage runs under a ``jax.named_scope`` — ``remix_seek`` (anchor
search and in-group lower bound), ``remix_gather`` (selector decode and
the run gathers of a view window) and ``liveness`` (the validity mask)
— so every device op carries its stage in the profiler's op metadata.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import keys as K
from repro.core.remix import Remix
from repro.core.runs import RunSet
from repro.kernels.anchor_search import anchor_search
from repro.kernels.selector_decode import selector_decode


def interpret_for_backend(backend: str | None = None) -> bool:
    """Whether the Pallas kernels run in interpret mode on ``backend``
    (default: the attached one). Each kernel caller decides this once:
    the CPU interprets (the test configuration), the TPU compiles, and
    any other backend has no kernel path."""
    backend = backend or jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(f"no Pallas kernel path for backend {backend!r}")


@partial(jax.jit, static_argnames=("interpret",))
def seek(
    remix: Remix, runset: RunSet, queries: jnp.ndarray, *, interpret: bool
) -> jnp.ndarray:
    """Kernel-backed lower-bound seek; same contract as core.query.seek."""
    with jax.named_scope("remix_seek"):
        queries = jnp.asarray(queries, jnp.uint32)
        d = remix.d
        g = anchor_search(remix.anchors, queries, interpret=interpret)
        sels = remix.selectors.reshape(remix.g, d)[g]  # (Q, D)
        runid, absidx, newest, pad = selector_decode(
            sels, remix.cursors[g], r=remix.r, interpret=interpret
        )
        keys, _, _, _ = runset.gather(runid, absidx)
        keys = jnp.where(pad[..., None], K.UINT32_MAX, keys)
        ge = ~K.key_lt(keys, queries[:, None, :])  # (Q, D)
        s = jnp.argmax(ge, axis=1).astype(jnp.int32)
        s = jnp.where(jnp.any(ge, axis=1), s, d)
        is_pad = jnp.take_along_axis(
            pad, jnp.clip(s, 0, d - 1)[:, None], axis=1
        )[:, 0]
        s = jnp.where((s < d) & is_pad, d, s)
        return jnp.minimum(g * d + s, remix.n_slots)


# ---- the device views' compositions (kernels/device_view.py) ----
#
# Liveness is *not* baked into the runset tombstones: per-row TTL expiry
# words ride along as a (R, Nmax) uint32 array and the window applies
# `tomb | (exp != 0 & exp <= now)` with `now` a traced scalar — so a
# persistent device view never goes stale when the clock passes an
# expiry (the legacy `p.index()` copy rebuilds its runset instead). The
# resolved (run, row) coordinates are returned alongside so the
# index-only residency tier can gather value granules host-side
# (BlockCache) from the same single device round trip.


@partial(jax.jit, static_argnames=("width", "interpret"))
def gather_view_live(
    remix: Remix,
    runset: RunSet,
    exp: jnp.ndarray,  # (R, Nmax) uint32 TTL expiries (0 = none)
    pos: jnp.ndarray,
    now: jnp.ndarray,  # () uint32 traced query-time clock
    width: int,
    *,
    interpret: bool,
):
    """Comparison-free range retrieval of ``width`` view slots from each
    position in ``pos`` (``core.query.gather_view``'s contract), with
    liveness evaluated at ``now`` and each slot's (run, row) emitted."""
    d = remix.d
    q = pos.shape[0]
    ng = (width + d - 1) // d + 1
    with jax.named_scope("remix_gather"):
        g0 = jnp.clip(pos // d, 0, remix.g - 1)
        gs = g0[:, None] + jnp.arange(ng, dtype=jnp.int32)[None, :]
        gsc = jnp.clip(gs, 0, remix.g - 1)
        sels = remix.selectors.reshape(remix.g, d)[gsc].reshape(q * ng, d)
        curs = remix.cursors[gsc].reshape(q * ng, remix.r)
        runid, absidx, newest, pad = selector_decode(
            sels, curs, r=remix.r, interpret=interpret
        )
        keys, vals, _, tomb = runset.gather(runid, absidx)
        keys = jnp.where(pad[..., None], K.UINT32_MAX, keys)
    with jax.named_scope("liveness"):
        # exp gather clips exactly like RunSet.gather so pad slots stay
        # benign
        ex = exp[
            jnp.clip(runid, 0, exp.shape[0] - 1),
            jnp.clip(absidx, 0, exp.shape[1] - 1),
        ]
        dead = tomb | ((ex != 0) & (ex <= now))

    def reshape_q(x):
        return x.reshape((q, ng * d) + x.shape[2:])

    off = pos - g0 * d

    def slice_one(x, o):
        return jax.lax.dynamic_slice_in_dim(x, o, width, axis=0)

    take = lambda x: jax.vmap(slice_one)(reshape_q(x), off)
    with jax.named_scope("remix_gather"):
        keys, vals = take(keys), take(vals)
        newest, pad, dead = take(newest), take(pad), take(dead)
        runid, absidx = take(runid), take(absidx)
    with jax.named_scope("liveness"):
        gslot = pos[:, None] + jnp.arange(width, dtype=jnp.int32)[None, :]
        valid = newest & ~pad & ~dead & (gslot < remix.n_slots)
    return keys, vals, valid, runid, absidx


@partial(jax.jit, static_argnames=("width", "interpret"))
def scan_live(
    remix, runset, exp, queries, now, width: int, *, interpret: bool,
):
    queries = jnp.asarray(queries, jnp.uint32)
    pos = seek(remix, runset, queries, interpret=interpret)
    return (
        *gather_view_live(
            remix, runset, exp, pos, now, width, interpret=interpret
        ),
        pos,
    )


@partial(jax.jit, static_argnames=("interpret",))
def get_live(remix, runset, exp, queries, now, *, interpret: bool):
    """Point lookups of ``queries`` (Q, KW) at clock ``now``, as one flat
    uint32 buffer so the host makes one fetch: per query, ``found`` as
    0/1, the view's value words, then the run id and the absolute row the
    lookup resolved to (:func:`unpack_get`)."""
    queries = jnp.asarray(queries, jnp.uint32)
    pos = seek(remix, runset, queries, interpret=interpret)
    keys, vals, valid, runid, absidx = gather_view_live(
        remix, runset, exp, pos, now, 1, interpret=interpret
    )
    with jax.named_scope("liveness"):
        found = valid[:, 0] & K.key_eq(keys[:, 0], queries)
    u32 = jnp.uint32
    return jnp.concatenate([
        found[:, None].astype(u32), vals[:, 0],
        runid[:, :1].astype(u32), absidx[:, :1].astype(u32),
    ], axis=1).reshape(-1)


def unpack_get(buf: np.ndarray, pad: int, q: int) -> tuple:
    """The first ``q`` of the ``pad`` queries of a fetched
    :func:`get_live` buffer: ``found (q,)`` bool, and views ``vals
    (q, VW)`` of the value words and ``runid``/``absidx (q,)`` int32 of
    the resolved rows, VW being the view's own value width."""
    rows = buf.reshape(pad, -1)[:q]
    return (rows[:, 0].astype(bool), rows[:, 1:-2],
            rows[:, -2].view(np.int32), rows[:, -1].view(np.int32))
