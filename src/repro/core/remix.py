"""The REMIX index data structure (paper §3.1) and its construction.

A :class:`Remix` persists, per group of D sorted-view slots:
  - ``anchors``     (G, KW)  smallest (newest-version) key of the group,
  - ``cursors``     (G, R)   per-run cursor offsets at the group head,
  - ``selectors``   (G*D,)   uint8 run selectors (| 0x80 newest, 127 pad).

Construction runs on the host at compaction time; query paths are pure JAX
(see :mod:`repro.core.query` and the Pallas kernels in :mod:`repro.kernels`).
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import keys as K
from repro.core import view as V
from repro.core.runs import Run, RunSet, stack_runs


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Remix:
    anchors: jnp.ndarray  # (G, KW) uint32
    cursors: jnp.ndarray  # (G, R) int32
    selectors: jnp.ndarray  # (G*D,) uint8
    n_entries: jnp.ndarray  # () int32 — real entries in the view
    d: int = dataclasses.field(metadata=dict(static=True))

    @property
    def g(self) -> int:
        return self.anchors.shape[0]

    @property
    def r(self) -> int:
        return self.cursors.shape[1]

    @property
    def n_slots(self) -> int:
        return self.selectors.shape[0]

    def storage_bytes(self, anchor_key_bytes: float | None = None) -> float:
        """Serialized size per paper §3.4: anchors + S*R cursors + 1B selectors.

        ``anchor_key_bytes`` overrides the per-anchor key size (e.g. the
        average user key length of a workload); defaults to KW*4.
        """
        akb = 4 * self.anchors.shape[1] if anchor_key_bytes is None else anchor_key_bytes
        s = 4  # cursor offset size (paper: 16-bit blk + 8-bit key ≈ 4 B impl)
        return self.g * (akb + s * self.r) + self.n_slots * 1


def build_remix(runs: Sequence[Run], d: int = 32) -> tuple[Remix, RunSet]:
    """Build a REMIX over ``runs``; returns (index, stacked run set)."""
    remix = remix_from_keys(
        [np.asarray(r.keys) for r in runs], [np.asarray(r.seq) for r in runs],
        d,
    )
    return remix, stack_runs(list(runs))


def remix_from_keys(
    run_keys: Sequence[np.ndarray], run_seqs: Sequence[np.ndarray], d: int
) -> Remix:
    """Build a REMIX from the runs' (Ni, KW) uint32 keys and (Ni,) uint32
    sequence numbers alone: the index is a pure function of those two
    columns, so no value, tombstone or stacked run array is needed."""
    run_keys = [np.asarray(k, np.uint32) for k in run_keys]
    layout = V.build_view(run_keys, [np.asarray(s, np.uint32)
                                     for s in run_seqs], d)
    return _remix_from_layout(layout, run_keys, len(run_keys))


def remix_from_order(
    runid: np.ndarray,
    pos: np.ndarray,
    newest: np.ndarray,
    run_keys: Sequence[np.ndarray],
    d: int,
) -> Remix:
    """Build a Remix from a precomputed (key asc, seq desc) merge order.

    Skips the global sort of :func:`build_remix`: callers that already
    know the merged order — e.g. the incremental rebuild that recovers it
    from an old REMIX's selector stream plus the new runs (§4.2,
    Snippet 1) — pay only the group layout cost. ``run_keys`` must list
    every run's (Ni, KW) uint32 keys in run-id order.
    """
    if d < len(run_keys):
        raise ValueError(
            f"group size D={d} must be >= number of runs R={len(run_keys)}"
        )
    layout = V.layout_from_order(runid, pos, newest, d)
    return _remix_from_layout(layout, [np.asarray(k) for k in run_keys],
                              len(run_keys))


def _remix_from_layout(
    layout: V.ViewLayout, run_keys, r: int
) -> Remix:
    d = layout.d
    g = layout.n_groups
    kw = run_keys[0].shape[1] if run_keys else K.KW
    group_starts = np.arange(g, dtype=np.int64) * d

    # cursor offsets: #entries of run r placed in slots < group start
    cursors = np.zeros((g, r), np.int32)
    for run in range(r):
        slots_r = np.flatnonzero(layout.entry_run == run)  # ascending
        cursors[:, run] = np.searchsorted(slots_r, group_starts, side="left")

    # anchor = key at the group's first slot; a group head is never a
    # placeholder (padding only fills group tails). Trailing fully-padded
    # groups (possible when the view is tiny) get the +inf sentinel.
    anchors = np.full((g, kw), K.UINT32_MAX, np.uint32)
    head_run = layout.entry_run[group_starts]
    head_pos = layout.entry_pos[group_starts]
    for i in range(g):
        if head_run[i] >= 0:
            anchors[i] = run_keys[head_run[i]][head_pos[i]]

    return Remix(
        anchors=jnp.asarray(anchors),
        cursors=jnp.asarray(cursors),
        selectors=jnp.asarray(layout.sel),
        n_entries=jnp.asarray(layout.n_entries, jnp.int32),
        d=d,
    )
