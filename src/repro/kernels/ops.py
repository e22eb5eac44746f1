"""Jit'd wrappers composing the Pallas kernels into full REMIX operations.

The kernels cover the compute-dense parts (anchor compare-count, selector
occurrence decode); XLA handles the HBM gathers between them (TPU gathers
are XLA's job — fusing them into Pallas would fight the memory system).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

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
    queries = jnp.asarray(queries, jnp.uint32)
    d = remix.d
    g = anchor_search(remix.anchors, queries, interpret=interpret)  # (Q,)
    sels = remix.selectors.reshape(remix.g, d)[g]  # (Q, D)
    runid, absidx, newest, pad = selector_decode(
        sels, remix.cursors[g], r=remix.r, interpret=interpret
    )
    keys, _, _, _ = runset.gather(runid, absidx)
    keys = jnp.where(pad[..., None], K.UINT32_MAX, keys)
    ge = ~K.key_lt(keys, queries[:, None, :])  # (Q, D)
    s = jnp.argmax(ge, axis=1).astype(jnp.int32)
    s = jnp.where(jnp.any(ge, axis=1), s, d)
    is_pad = jnp.take_along_axis(pad, jnp.clip(s, 0, d - 1)[:, None], axis=1)[:, 0]
    s = jnp.where((s < d) & is_pad, d, s)
    return jnp.minimum(g * d + s, remix.n_slots)


@partial(jax.jit, static_argnames=("width", "interpret"))
def gather_view(
    remix: Remix,
    runset: RunSet,
    pos: jnp.ndarray,
    width: int,
    *,
    interpret: bool,
):
    """Kernel-backed comparison-free range retrieval from view positions."""
    d = remix.d
    q = pos.shape[0]
    ng = (width + d - 1) // d + 1
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

    def reshape_q(x):
        return x.reshape((q, ng * d) + x.shape[2:])

    off = pos - g0 * d

    def slice_one(x, o):
        return jax.lax.dynamic_slice_in_dim(x, o, width, axis=0)

    take = lambda x: jax.vmap(slice_one)(reshape_q(x), off)
    keys, vals = take(keys), take(vals)
    newest, pad, tomb = take(newest), take(pad), take(tomb)
    gslot = pos[:, None] + jnp.arange(width, dtype=jnp.int32)[None, :]
    valid = newest & ~pad & ~tomb & (gslot < remix.n_slots)
    return keys, vals, valid


@partial(jax.jit, static_argnames=("width", "interpret"))
def scan(remix, runset, queries, width: int, *, interpret: bool):
    pos = seek(remix, runset, queries, interpret=interpret)
    return (*gather_view(remix, runset, pos, width, interpret=interpret), pos)


@partial(jax.jit, static_argnames=("interpret",))
def get(remix, runset, queries, *, interpret: bool):
    queries = jnp.asarray(queries, jnp.uint32)
    pos = seek(remix, runset, queries, interpret=interpret)
    keys, vals, valid = gather_view(remix, runset, pos, 1, interpret=interpret)
    found = valid[:, 0] & K.key_eq(keys[:, 0], queries)
    return found, vals[:, 0]


# ---- device-resident live variants (kernels/device_view.py) ----
#
# Same pipeline, but liveness is *not* baked into the runset tombstones:
# per-row TTL expiry words ride along as a (R, Nmax) uint32 array and the
# window applies `tomb | (exp != 0 & exp <= now)` with `now` a traced
# scalar — so a persistent device view never goes stale when the clock
# passes an expiry (the host path rebuilds its runset instead). The
# resolved (run, row) coordinates are returned alongside so the index-only
# residency tier can gather value granules host-side (BlockCache) from the
# same single device round trip.


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
    """`gather_view` with query-time liveness + (run, row) emission."""
    d = remix.d
    q = pos.shape[0]
    ng = (width + d - 1) // d + 1
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
    # exp gather clips exactly like RunSet.gather so pad slots stay benign
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
    keys, vals = take(keys), take(vals)
    newest, pad, dead = take(newest), take(pad), take(dead)
    runid, absidx = take(runid), take(absidx)
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
    queries = jnp.asarray(queries, jnp.uint32)
    pos = seek(remix, runset, queries, interpret=interpret)
    keys, vals, valid, runid, absidx = gather_view_live(
        remix, runset, exp, pos, now, 1, interpret=interpret
    )
    found = valid[:, 0] & K.key_eq(keys[:, 0], queries)
    return found, vals[:, 0], runid[:, 0], absidx[:, 0]
