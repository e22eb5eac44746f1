"""Pallas TPU kernel: in-group run-selector decode (paper §3.2).

The paper counts selector occurrences with SIMD instructions to place run
cursors inside a group. The TPU-native formulation: for a tile of
selectors, compute each slot's exclusive occurrence count of its own run
(how many earlier slots of the group select the same run), then add the
group's cursor offset for that run to obtain absolute in-run indices.

Block layout: the kernel works on transposed tiles, (D, BQ) selectors and
(R, BQ) cursors, so the group slot is the sublane axis and the query is
the lane axis (dense 128-lane vregs for any group size). The exclusive
count is D unrolled steps, each comparing one slot row against every
later slot — a sublane-row broadcast, no prefix scan and no gathers
inside the kernel. R is static and unrolled for the cursor select.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.view import NEWEST_BIT, PLACEHOLDER


def _decode_kernel(sel_ref, cur_ref, runid_ref, absidx_ref, flags_ref, *,
                   r: int):
    sel = sel_ref[...]  # (D, BQ) int32
    d = sel.shape[0]
    pad = sel == PLACEHOLDER
    newest = (sel & NEWEST_BIT) != 0
    runid = jnp.where(pad, 0, sel & 0x7F)
    live = ~pad & (runid < r)  # slots that count toward a run's cursor
    slot = jax.lax.broadcasted_iota(jnp.int32, sel.shape, 0)
    occ = jnp.zeros_like(runid)
    for j in range(d - 1):  # slot j precedes every slot i > j
        sj = sel_ref[j:j + 1, :]  # (1, BQ)
        rj = jnp.where(sj == PLACEHOLDER, -1, sj & 0x7F)
        occ = occ + ((rj == runid) & (slot > j)).astype(jnp.int32)
    occ = jnp.where(live, occ, 0)
    # base uses runid even on placeholder slots (matches ref.py contract)
    base = jnp.zeros_like(runid)
    for rr in range(r):
        base = jnp.where(runid == rr, cur_ref[rr:rr + 1, :], base)
    runid_ref[...] = runid
    absidx_ref[...] = base + occ
    flags_ref[...] = newest.astype(jnp.int32) | (pad.astype(jnp.int32) << 1)


@functools.partial(jax.jit, static_argnames=("r", "block_q", "interpret"))
def selector_decode(
    selectors: jnp.ndarray,  # (Q, D) uint8/int32 group selector tiles
    cursors: jnp.ndarray,  # (Q, R) int32 cursor offsets at group heads
    *,
    r: int,
    block_q: int = 256,
    interpret: bool,
):
    """Decode selector tiles → (runid (Q,D), absidx (Q,D), newest, pad)."""
    q, d = selectors.shape
    rc = cursors.shape[1]
    bq = min(block_q, -(-q // 128) * 128)
    qp = -(-q // bq) * bq
    sel_t = jnp.pad(selectors.astype(jnp.int32).T, ((0, 0), (0, qp - q)),
                    constant_values=PLACEHOLDER)
    cur_t = jnp.pad(cursors.astype(jnp.int32).T, ((0, 0), (0, qp - q)))
    tile = pl.BlockSpec((d, bq), lambda i: (0, i))
    runid, absidx, flags = pl.pallas_call(
        functools.partial(_decode_kernel, r=r),
        grid=(qp // bq,),
        in_specs=[tile, pl.BlockSpec((rc, bq), lambda i: (0, i))],
        out_specs=[tile, tile, tile],
        out_shape=[jax.ShapeDtypeStruct((d, qp), jnp.int32)] * 3,
        interpret=interpret,
    )(sel_t, cur_t)
    runid, absidx, flags = (x[:, :q].T for x in (runid, absidx, flags))
    newest = (flags & 1) != 0
    pad = (flags & 2) != 0
    return runid, absidx, newest, pad
