"""Pallas TPU kernel: batched anchor search (paper §3.1 step 1).

The CPU paper binary-searches the anchor index per query. Branchy binary
search is hostile to the VPU (data-dependent gathers); the TPU-native
adaptation is *compare-and-count*: the target group of query q is
``(# anchors <= q) - 1``, computed by streaming anchor tiles from HBM
through VMEM against a resident query tile and accumulating lexicographic
compare counts. O(G) work/query but bandwidth-shaped and branch-free;
``anchor_search`` composes a two-level (coarse→fine) hierarchy so the
effective work is O(sqrt(G)) per query tile for big indexes.

Tile layout: anchors are laid out word-major, ``(KW, G)``, so the anchor
axis is the lane axis and each key word is one sublane row; queries are
``(KW, Q, 1)``, so each query word is a sublane column. The (BQ, BG)
compare tile is then a plain row-against-column broadcast. Key words are
compared as int32 after flipping the sign bit, which preserves the
unsigned order.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_SIGN = 0x80000000


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _ordered_i32(words: jnp.ndarray) -> jnp.ndarray:
    """uint32 words → int32 with the same order (sign-bit flip)."""
    w = jnp.asarray(words, jnp.uint32) ^ jnp.uint32(_SIGN)
    return jax.lax.bitcast_convert_type(w, jnp.int32)


def _le_count_kernel(anchors_ref, queries_ref, count_ref, *, kw: int):
    """count[q] += sum_over_tile(anchor <= query)."""
    gi = pl.program_id(1)

    @pl.when(gi == 0)
    def _init():
        count_ref[...] = jnp.zeros_like(count_ref)

    le = None
    eq = None
    for w in range(kw):
        aw = anchors_ref[w:w + 1, :]  # (1, BG)
        qw = queries_ref[w]  # (BQ, 1)
        lt_w, eq_w = aw < qw, aw == qw  # (BQ, BG)
        le = lt_w if le is None else le | (eq & lt_w)
        eq = eq_w if eq is None else eq & eq_w
    le = le | eq
    count_ref[...] += jnp.sum(le.astype(jnp.int32), axis=1, keepdims=True)


@functools.partial(
    jax.jit, static_argnames=("block_q", "block_g", "interpret")
)
def anchor_le_count(
    anchors: jnp.ndarray,  # (G, KW) uint32, ascending (+inf padded tail ok)
    queries: jnp.ndarray,  # (Q, KW) uint32
    *,
    block_q: int = 256,
    block_g: int = 512,
    interpret: bool,
) -> jnp.ndarray:
    """Return (Q,) int32: number of anchors <= query (target group + 1)."""
    g, kw = anchors.shape
    q = queries.shape[0]
    bg = min(block_g, _round_up(g, 128))
    bq = min(block_q, _round_up(q, 8))
    gp, qp = _round_up(g, bg), _round_up(q, bq)
    # +inf pad anchors count only for the reserved all-ones key, and the
    # clamp below restores the unpadded count for it
    a = jnp.pad(_ordered_i32(anchors).T, ((0, 0), (0, gp - g)),
                constant_values=jnp.iinfo(jnp.int32).max)
    qk = jnp.pad(_ordered_i32(queries).T, ((0, 0), (0, qp - q)))[:, :, None]
    counts = pl.pallas_call(
        functools.partial(_le_count_kernel, kw=kw),
        grid=(qp // bq, gp // bg),
        in_specs=[
            pl.BlockSpec((kw, bg), lambda qi, gi: (0, gi)),
            pl.BlockSpec((kw, bq, 1), lambda qi, gi: (0, qi, 0)),
        ],
        out_specs=pl.BlockSpec((bq, 1), lambda qi, gi: (qi, 0)),
        out_shape=jax.ShapeDtypeStruct((qp, 1), jnp.int32),
        interpret=interpret,
    )(a, qk)
    return jnp.minimum(counts[:q, 0], g)


@functools.partial(
    jax.jit, static_argnames=("block_q", "block_g", "fan", "interpret")
)
def anchor_search(
    anchors: jnp.ndarray,
    queries: jnp.ndarray,
    *,
    block_q: int = 256,
    block_g: int = 512,
    fan: int = 128,
    interpret: bool,
) -> jnp.ndarray:
    """Two-level compare-and-count anchor search → (Q,) target group ids.

    Level 1 counts over every ``fan``-th anchor (the B+-tree-like top level
    of the REMIX file, §4.1); level 2 counts inside the selected span.
    Exact same result as ``upper_bound(anchors, q) - 1`` clamped to >= 0.
    """
    g, kw = anchors.shape
    if g <= fan * 4:  # small index: single level
        cnt = anchor_le_count(
            anchors, queries, block_q=block_q, block_g=block_g,
            interpret=interpret,
        )
        return jnp.maximum(cnt - 1, 0)
    top = anchors[fan - 1 :: fan]  # last anchor of each span
    tcnt = anchor_le_count(
        top, queries, block_q=block_q, block_g=block_g, interpret=interpret
    )  # spans fully <= query
    base = tcnt * fan
    # gather the fine span per query and count inside (XLA gather + kernel)
    raw_idx = base[:, None] + jnp.arange(fan)[None, :]
    in_range = raw_idx < g
    span_idx = jnp.minimum(raw_idx, g - 1)
    spans = anchors[span_idx]  # (Q, fan, KW)
    qx = queries[:, None, :]
    le = jnp.zeros(span_idx.shape, jnp.bool_)
    eq = jnp.ones(span_idx.shape, jnp.bool_)
    for w in range(kw):
        le = le | (eq & (spans[..., w] < qx[..., w]))
        eq = eq & (spans[..., w] == qx[..., w])
    fine = jnp.sum((le | eq) & in_range, axis=1).astype(jnp.int32)
    return jnp.maximum(base + fine - 1, 0)
