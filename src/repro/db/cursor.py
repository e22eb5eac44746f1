"""RemixCursor: the paper's cursor (§3.2 seek/peek/next/skip) over a
snapshot-consistent merged view.

One cursor unifies the store's three read paths behind a single ascending
stream of live ``(key, value)`` entries:

- the MemTable overlay (the snapshot's frozen entry dict, tombstones
  hiding older table entries),
- cold partitions (on-disk REMIX walk: one anchors search + bounded CKB
  seeks at ``seek``, then pure selector-stream decodes per window —
  :meth:`repro.db.partition.Partition.cold_cursor_window`),
- promoted partitions (the partition's budgeted device view: the seek
  fused with the first window in one jitted call, ``scan_live``, then
  comparison-free ``gather_view_live`` windows from the saved position;
  each window comes back as one device buffer, :func:`window_buffer`).
  A stream reads every window at the clock it opened at. Where no view
  serves the partition (views off, or no residency tier fits) the same
  program reads the legacy ``p.index()`` copy with ``core.query``.

The defining property vs repeated ``scan(start, n)`` calls: a cursor
seeks **once**. ``next``/``next_batch`` advance a persisted view
position, so a long or streaming scan pays the anchors search and
per-run seeks a single time instead of once per chunk
(``benchmarks/cursor_bench.py`` holds the ≥2x acceptance bar). ``skip``
counts live entries, draining windows without materializing values'
consumers. Because the snapshot pins its Version, iteration is immune to
concurrent flushes: a compaction publishing a new Version never changes
what an open cursor returns.

On a promoted partition every window is one launch, with one host
argument (the start key's words or the saved position, and the stream's
``now``), and one fetch: a lone Seek+NextN that fits its first window
pays a single device round trip. Under an active trace each stream
open is a ``cursor_seek`` span (``overlay_sort`` on the first,
``route``, then the fused seek and first window's ``launch``,
``device_wait`` and ``unpack``) and each window pull a
``cursor_window`` span (for a later device window ``launch``, one
``device_wait`` and ``unpack``; then ``merge``). The device calls
count in the store's ``cursor_seeks`` (stream opens on a promoted
partition), ``cursor_windows`` (every device window, the fused first
one included), ``device_launches`` and ``device_syncs`` — not in the
views' ``device_batches`` or ``scan_live_*``.
"""
from __future__ import annotations

import bisect
import dataclasses
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import keys as CK
from repro.core import query as Q
from repro.db import clock
from repro.db.memtable import entry_dead
from repro.db.sharded import partition_spans, route_one
from repro.obs import tracing as _tracing

_MAX_WIDTH = 4096  # widening cap over tombstone/old-version runs


@partial(jax.jit, static_argnames=("width", "seek", "rows", "interpret"))
def window_buffer(remix, runset, exp, at, *, width: int, seek: bool,
                  rows: bool = False, interpret: bool = False):
    """One cursor window as one device buffer, in one program.

    ``at`` (1, A) uint32 is the call's one host argument: with ``seek``
    the start key's words (the seek fused with the first window), else
    the saved view position; its last word is the stream's ``now``.
    Over a device view (``exp`` its TTL expiry words) the window is
    :func:`repro.kernels.ops.scan_live` or ``gather_view_live``, which
    evaluate liveness at ``now``; over the legacy arrays (``exp`` None,
    liveness baked into the run set) it is ``core.query``'s ``scan`` or
    ``gather_view``. The outputs are packed into one flat uint32 array —
    key words, value words, validity as 0/1, the view position the
    window starts at and, with ``rows`` (the index tier, whose values
    stay on the host), each slot's run id and row — so the host makes
    one fetch (:func:`unpack_window`) where it would make one per
    output."""
    words, now = at[:, :-1], at[0, -1]
    pos = words[:, 0].astype(jnp.int32)
    if exp is None:
        if seek:
            keys, vals, valid, pos = Q.scan(
                remix, runset, words, width=width,
                ingroup=Q.ingroup_for_backend(),
            )
        else:
            keys, vals, valid = Q.gather_view(remix, runset, pos, width)
        run_rows = ()
    else:
        from repro.kernels import ops

        if seek:
            keys, vals, valid, rid, row, pos = ops.scan_live(
                remix, runset, exp, words, now, width=width,
                interpret=interpret,
            )
        else:
            keys, vals, valid, rid, row = ops.gather_view_live(
                remix, runset, exp, pos, now, width, interpret=interpret,
            )
        run_rows = (rid, row) if rows else ()
    u32 = jnp.uint32
    return jnp.concatenate([
        keys.reshape(-1), vals.reshape(-1),
        valid.reshape(-1).astype(u32), pos.reshape(-1).astype(u32),
        *(x.reshape(-1).astype(u32) for x in run_rows),
    ])


def unpack_window(buf: np.ndarray, width: int, kw: int, vw: int):
    """One query's :func:`window_buffer`, fetched: views ``keys (W, KW)``,
    ``vals (W, VW)``, ``valid (W,)`` bool, the start position and the
    slots' ``(run id, row)`` as a (2, W) int32 view (empty without
    ``rows``)."""
    nk, nv = width * kw, width * vw
    keys = buf[:nk].reshape(width, kw)
    vals = buf[nk:nk + nv].reshape(width, vw)
    o = nk + nv + width
    valid = buf[nk + nv:o].astype(bool)
    return (keys, vals, valid, int(buf[o]),
            buf[o + 1:].view(np.int32).reshape(-1, width))


@dataclasses.dataclass
class _DeviceStream:
    """A promoted partition's table-entry stream: its device view (None:
    the legacy read path), the clock it opened at, the arrays its
    windows read (taken at the first: the view's, or ``p.index()``'s
    with liveness baked at that instant) and its saved view position."""

    p: object
    view: object | None
    now: int
    arrays: tuple | None = None
    pos: int | None = None


class RemixCursor:
    """Merged-view iterator over a :class:`repro.db.version.Snapshot`."""

    def __init__(self, snapshot, width: int = 64,
                 owns_snapshot: bool = False, interrupt=None):
        if width < 1:
            raise ValueError("cursor width must be >= 1")
        self.snap = snapshot
        self.store = snapshot.store
        self.base_width = int(width)
        self.vw = self.store.cfg.vw
        self._owns = owns_snapshot
        # cooperative cancellation hook (op layer): called once per
        # window pull; raising aborts the fill — a deadline-bounded scan
        # stops mid-stream instead of draining the whole range
        self._interrupt = interrupt
        # buffered live entries, as (keys, vals) array chunks: windows
        # with no interleaving overlay entries pass through zero-copy
        self._chunks: list[tuple[np.ndarray, np.ndarray]] = []
        self._buffered = 0
        self._done = True
        self._stream = None
        self._pulled = None  # a stream's first window, fused with its open

    # ---------------- positioning ----------------
    def seek(self, key: int) -> "RemixCursor":
        """Position at the lower bound of ``key`` in the merged view. The
        overlay's keys are sorted, the key routed and the first stream
        opened at the first pull (:meth:`_fill`)."""
        self._start = int(key)
        self._okeys = None
        self._first = True
        self._stream = None
        self._pulled = None
        self._width = self.base_width
        self._chunks = []
        self._buffered = 0
        self._done = False
        return self

    # ---------------- consumption ----------------
    def peek(self):
        """The next live entry ``(key, val)`` without advancing, or None."""
        self._fill(1)
        if not self._chunks:
            return None
        kk, vv = self._chunks[0]
        return int(kk[0]), vv[0]

    def next(self):
        """Return the next live entry ``(key, val)`` and advance, or None
        at end of view."""
        item = self.peek()
        if item is not None:
            self._drop(1)
        return item

    def skip(self, n: int) -> int:
        """Advance past ``n`` live entries; returns how many were skipped
        (fewer only at end of view)."""
        self._fill(n)
        got = min(n, self._buffered)
        self._drop(got)
        return got

    def next_batch(self, n: int) -> tuple[np.ndarray, np.ndarray]:
        """The next ``n`` live entries as ``(keys (M,) u64, vals (M, VW))``
        arrays, M <= n — the batched ``next`` that makes ``scan`` a thin
        wrapper over a cursor."""
        self._fill(n)
        take_k: list[np.ndarray] = []
        take_v: list[np.ndarray] = []
        need = n
        while need > 0 and self._chunks:
            kk, vv = self._chunks[0]
            if len(kk) <= need:
                self._chunks.pop(0)
            else:
                self._chunks[0] = (kk[need:], vv[need:])
                kk, vv = kk[:need], vv[:need]
            take_k.append(kk)
            take_v.append(vv)
            need -= len(kk)
            self._buffered -= len(kk)
        if not take_k:
            return (
                np.zeros(0, np.uint64),
                np.zeros((0, self.vw), np.uint32),
            )
        return np.concatenate(take_k), np.concatenate(take_v)

    def _drop(self, n: int) -> None:
        while n > 0 and self._chunks:
            kk, vv = self._chunks[0]
            if len(kk) <= n:
                self._chunks.pop(0)
                n -= len(kk)
                self._buffered -= len(kk)
            else:
                self._chunks[0] = (kk[n:], vv[n:])
                self._buffered -= n
                n = 0

    # ---------------- lifecycle ----------------
    def close(self) -> None:
        """Release the snapshot if this cursor owns it (see
        ``RemixDB.cursor``); cursors over caller-managed snapshots leave
        them open."""
        if self._owns:
            self.snap.close()

    def __enter__(self) -> "RemixCursor":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __iter__(self):
        while True:
            item = self.next()
            if item is None:
                return
            yield item

    # ---------------- internals ----------------
    def _seek_first(self) -> None:
        """The seek proper, at the first pull: the overlay's sorted keys,
        the partition holding the start key, and its stream."""
        with _tracing.span("overlay_sort"):
            if self.snap.shared:
                # the overlay is the live MemTable dict: materialize the
                # key list under the writer lock so a concurrent put's
                # dict resize can't tear the iteration
                with self.store._state_lock:
                    self._okeys = sorted(self.snap.overlay)
            else:
                self._okeys = sorted(self.snap.overlay)
            self._oi = bisect.bisect_left(self._okeys, self._start)
        parts = self.snap.partitions
        with _tracing.span("route"):
            self._spans = partition_spans([p.lo for p in parts])
            self._pi = route_one(parts, self._start)
        if self._pi < len(parts):
            self._open_stream()

    def _open_stream(self):
        """Start the table-entry stream of the current partition: one
        seek (cold: anchors + bounded CKB; promoted: the device seek,
        fused with the first window), after which every window is a pure
        position advance."""
        store = self.store
        with _tracing.span("route"):
            p = self.snap.partitions[self._pi]
            lo, _ = self._spans[self._pi]
            start = max(self._start, lo) if self._first else lo
            self._first = False
            self._width = self.base_width
            cold = store._cold_ok(p)
            dv = None if cold else store._device_view(p)
        if cold:
            self._stream = ("cold", p, p.cold_cursor_seek(start))
            return
        self._stream = _DeviceStream(p, dv, int(clock.now()))
        self._pulled = self._device_window(start)

    def _device_window(self, start: int | None = None):
        """One window of the promoted stream: one launch and one fetch.
        A stream with no position yet seeks ``start`` in the same call;
        every window reads at the ``now`` the stream opened at.
        Returns (keys u64, vals, partition_done)."""
        stream, store = self._stream, self.store
        dv = stream.view
        rows = dv is not None and dv.tier != "full"
        with _tracing.span("launch"):
            if stream.arrays is None:
                stream.arrays = ((dv.remix, dv.runset, dv.exp)
                                 if dv is not None
                                 else (*stream.p.index(), None))
            remix, runset, exp = stream.arrays
            seek = stream.pos is None
            if seek:
                at = CK.pack_u64(np.array([start], np.uint64))
                store._c_cursor_seeks.inc()
            else:
                at = np.array([[stream.pos]], np.uint32)
            at = np.append(at, np.uint32(stream.now))[None]
            # whole groups: the decode covers ceil(width / d) + 1 groups
            # whatever the width, so this adds no device work, and one
            # program per group count (not per width) keeps the fused
            # seek's compiles few
            width = -(-self._width // remix.d) * remix.d
            buf_d = window_buffer(
                remix, runset, exp, at, width=width, seek=seek, rows=rows,
                interpret=dv is not None and store.device_views.interpret,
            )
            store._c_cursor_windows.inc()
            store._c_launches.inc()
        (buf,) = _tracing.fetch(store._c_syncs, buf_d)
        with _tracing.span("unpack"):
            del buf_d  # frees the device buffer
            keys, vals, valid, pos, run_rows = unpack_window(
                buf, width, runset.keys.shape[-1], runset.vals.shape[-1],
            )
            if rows:
                rid, row = run_rows
                vals = dv.host_vals(rid[valid], row[valid])
            else:
                vals = vals[valid]
            kk, vv, clipped = self._clip(CK.unpack_u64(keys[valid]), vals)
            stream.pos = pos + width
        return kk, vv, clipped or stream.pos >= remix.n_slots

    def _next_window(self):
        """One window of live table entries from the current partition.
        Returns (keys u64, vals, partition_done)."""
        if not isinstance(self._stream, _DeviceStream):
            _, p, state = self._stream
            kk, vv, more = p.cold_cursor_window(
                state, self._width,
                prefetch_depth=self.store.cfg.prefetch_depth,
            )
            kk, vv, clipped = self._clip(kk, vv)
            done = clipped or not more
        elif self._pulled is not None:
            (kk, vv, done), self._pulled = self._pulled, None
        else:
            kk, vv, done = self._device_window()
        # adaptive widening, two cases sharing one rule: an all-invalid
        # window (tombstone/old-version run) must grow so long dead runs
        # cost O(log) decodes, and a productive stream grows as read-ahead
        # — the first window stays small (seek latency), sustained
        # consumption amortizes per-window overhead over ever larger
        # decodes. Re-seeking scans can't do this: read-ahead is only
        # free when the position survives the call.
        self._width = min(self._width * 2, _MAX_WIDTH)
        return kk, vv, done

    def _clip(self, kk, vv):
        """A window's entries inside the partition's key range and
        outside the snapshot's range tombstones, and whether the range's
        end cut it (the partition is drained)."""
        _, hi = self._spans[self._pi]
        # entries at/after the next partition's lower bound mean this
        # partition is drained
        cut = int(np.searchsorted(kk, np.uint64(min(hi, (1 << 64) - 1)),
                                  side="right" if hi >= 1 << 64 else "left"))
        clipped = cut < len(kk)
        kk, vv = kk[:cut], vv[:cut]
        # snapshot-visible range tombstones hide any remaining table
        # entries they cover (partial-coverage spans and promoted-path
        # windows; fully-covered cold spans were skipped structurally)
        if self.snap.ranges and len(kk):
            m = np.ones(len(kk), bool)
            for rlo, rhi, _ in self.snap.ranges:
                m &= ~((kk >= rlo) & (kk < rhi))
            kk, vv = kk[m], vv[m]
        return kk, vv, clipped

    def _push(self, kk: np.ndarray, vv: np.ndarray) -> None:
        if len(kk):
            self._chunks.append((kk, vv))
            self._buffered += len(kk)

    def _merge_emit(self, kk: np.ndarray, vv: np.ndarray,
                    bound: int) -> None:
        """Merge one table window with the overlay slice up to ``bound``
        (inclusive). Overlay wins ties; tombstones drop both. Appends
        live entries, ascending, to the buffer — the common case (no
        overlay entry in range) passes the window through untouched."""
        okeys, overlay = self._okeys, self.snap.overlay
        now = clock.now()
        oend = self._oi
        while oend < len(okeys) and okeys[oend] <= bound:
            oend += 1
        if oend == self._oi:  # fast path: pure table window
            self._push(kk, vv)
            return
        ti = 0
        out_k: list[int] = []
        out_v: list[np.ndarray] = []
        while True:
            okey = okeys[self._oi] if self._oi < oend else None
            tkey = int(kk[ti]) if ti < len(kk) else None
            if okey is None and tkey is None:
                break
            if tkey is None or (okey is not None and okey <= tkey):
                if okey == tkey:
                    ti += 1  # overlay shadows the table entry
                self._oi += 1
                e = overlay[okey]
                if not entry_dead(e, now):
                    out_k.append(okey)
                    out_v.append(np.asarray(e.val, np.uint32))
            else:
                out_k.append(tkey)
                out_v.append(vv[ti])
                ti += 1
        if out_k:
            self._push(
                np.array(out_k, np.uint64),
                np.stack(out_v).astype(np.uint32, copy=False),
            )

    def _fill(self, n: int) -> None:
        """Pull windows until ``n`` live entries are buffered or the view
        is exhausted."""
        parts = self.snap.partitions
        while self._buffered < n and not self._done:
            if self._interrupt is not None:
                self._interrupt()
            if self._okeys is None:
                with _tracing.span("cursor_seek"):
                    self._seek_first()
            elif self._stream is None and self._pi < len(parts):
                with _tracing.span("cursor_seek"):
                    self._open_stream()
            if self._pi >= len(parts):
                # every partition drained: flush the overlay tail
                with _tracing.span("merge"):
                    self._merge_emit(
                        np.zeros(0, np.uint64),
                        np.zeros((0, self.vw), np.uint32),
                        (1 << 64) - 1,
                    )
                self._done = True
                return
            with _tracing.span("cursor_window"):
                kk, vv, pdone = self._next_window()
                if pdone:
                    # partition exhausted: overlay entries below the next
                    # partition's range can all be emitted
                    bound = self._spans[self._pi][1] - 1
                    self._pi += 1
                    self._stream = None
                elif len(kk):
                    bound = int(kk[-1])
                else:
                    continue  # dead window mid-partition: none emittable
                with _tracing.span("merge"):
                    self._merge_emit(kk, vv, bound)
