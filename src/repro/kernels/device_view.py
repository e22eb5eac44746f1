"""Device-resident query views: persistent HBM buffers for promoted
partitions plus the fused batched execution driver (ROADMAP item: the
"fast as the hardware allows" read lane).

A :class:`DeviceView` holds one promoted partition's REMIX structural
arrays (anchors, selector stream, cursor offsets) and its stacked run
sections as device buffers, in one of two residency tiers:

- ``full``  — keys, values, tombstones and TTL expiry words all resident:
  a batched get/scan is one jitted Pallas composition (seek → selector
  decode → run/position resolve → window emission → key/value gather)
  with **exactly one host↔device sync** — the final result fetch.
- ``index`` — everything but the value sections resident (the KV-Tandem
  split: device index plane / host block-storage plane). The device
  resolves each batch slice's row windows while the host gathers the
  *previous* slice's value granules through the ``BlockCache`` — a
  double-buffered pipeline riding JAX's async dispatch, extending the
  Fig 10 group-ahead prefetch across the host/device boundary.

Liveness is evaluated at query time on device: uploaded tombstone words
carry real tombstones plus excised-span coverage (structural, can never
revive), and per-row TTL expiry words are compared against a traced
``now`` — bit-for-bit the host path's `_build_dead` set at the same
instant, with no rebuild when the clock passes an expiry.

The :class:`DeviceViewManager` owns an HBM byte budget: LRU eviction on
upload pressure, and release-time eviction tied to the VersionSet pin
lifecycle (``retain`` drops views whose partition left every live
Version). Views hold a strong reference to their partition, so a view
can never alias a recycled ``id()``.

Host sync points are counted in the module-level ``SYNCS`` counter —
``benchmarks/kernels_bench.py`` asserts the fused batch-256 get pipeline
pays exactly one per batch.
"""
from __future__ import annotations

import dataclasses
from collections import OrderedDict

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import keys as CK
from repro.kernels import ops

# host↔device sync points (device→host result fetches); module-level so
# benchmarks/tests can assert the "one sync per batch" contract
SYNCS = 0


def _fetch(*arrays):
    """The single blocking device→host transfer of a fused batch."""
    global SYNCS
    SYNCS += 1
    return jax.device_get(arrays)


def _pow2pad(n: int) -> int:
    b = 8
    while b < n:
        b <<= 1
    return b


@dataclasses.dataclass
class DeviceView:
    """One promoted partition's resident device buffers."""

    partition: object  # strong ref: pins identity until eviction
    tier: str  # "full" | "index"
    remix: object  # padded Remix (device)
    runset: object  # padded RunSet (device; dummy 1-word vals on "index")
    exp: jnp.ndarray  # (R, Nmax) uint32 TTL expiries (device)
    nbytes: int  # accounted HBM bytes
    vw: int  # real value width (host tables for "index")

    @property
    def tables(self):
        return self.partition.tables


def _view_nbytes(remix, runset, exp) -> int:
    arrs = (
        remix.anchors, remix.cursors, remix.selectors,
        runset.keys, runset.vals, runset.seq, runset.tomb, runset.lens,
        exp,
    )
    return int(sum(int(a.size) * a.dtype.itemsize for a in arrs))


class DeviceViewManager:
    """HBM residency manager for promoted partitions' device views.

    ``budget_bytes`` bounds the resident set (LRU on upload pressure);
    ``retain(live_ids)`` is the VersionSet release hook — views whose
    partition is in no live Version are dropped with their pins.
    A partition that fits neither tier counts ``device_fallback_total``
    and the caller answers from the legacy path instead.
    """

    def __init__(
        self,
        budget_bytes: int,
        slice_width: int = 64,
        registry=None,
        events=None,
    ):
        self.budget_bytes = int(budget_bytes)
        self.slice_width = max(1, int(slice_width))
        # decided once from the backend: interpreted on CPU, compiled on
        # TPU, and any other backend raises here rather than falling back
        self.interpret = ops.interpret_for_backend()
        self._views: "OrderedDict[int, DeviceView]" = OrderedDict()
        self._resident = 0
        if registry is None:
            from repro.obs.metrics import MetricsRegistry

            registry = MetricsRegistry(enabled=False)
        if events is None:
            from repro.obs.events import NULL_EVENTS

            events = NULL_EVENTS
        self.events = events
        self._c_batches = registry.counter("device_batches")
        self._c_rows = registry.counter("device_rows_gathered")
        self._c_fallback = registry.counter("device_fallback_total")
        registry.gauge("hbm_resident_bytes", fn=lambda: self._resident)

    # ---- residency ----
    @property
    def resident_bytes(self) -> int:
        return self._resident

    def __len__(self) -> int:
        return len(self._views)

    def view_for(self, p) -> DeviceView | None:
        """Resident view for partition ``p`` — uploading on first use —
        or None when no tier fits the budget (caller falls back)."""
        v = self._views.get(id(p))
        if v is not None:
            self._views.move_to_end(id(p))
            return v
        est_full = p.device_view_bytes(with_vals=True)
        if est_full <= self.budget_bytes:
            tier = "full"
        elif (
            p.device_view_bytes(with_vals=False) <= self.budget_bytes
            and p.tables
            and all(t.path is not None for t in p.tables)
        ):
            # value sections stay host-side, gathered via the BlockCache
            tier = "index"
        else:
            self._c_fallback.inc()
            return None
        remix, runset, exp = p.device_index(with_vals=tier == "full")
        nbytes = _view_nbytes(remix, runset, exp)
        self._evict_to(self.budget_bytes - nbytes)
        vw = p.tables[0].vw if p.tables else runset.vw
        v = DeviceView(
            partition=p, tier=tier, remix=remix, runset=runset,
            exp=exp, nbytes=nbytes, vw=int(vw),
        )
        self._views[id(p)] = v
        self._resident += nbytes
        self.events.emit(
            "device_upload", lo=int(p.lo), tier=tier, bytes=int(nbytes),
            tables=len(p.tables), interpret=self.interpret,
        )
        return v

    def _evict_to(self, target: int, reason: str = "budget") -> None:
        while self._views and self._resident > max(0, target):
            _, v = self._views.popitem(last=False)  # LRU
            self._drop(v, reason)

    def _drop(self, v: DeviceView, reason: str) -> None:
        self._resident -= v.nbytes
        self.events.emit(
            "device_evict", lo=int(v.partition.lo), tier=v.tier,
            bytes=int(v.nbytes), reason=reason,
        )

    def retain(self, live_ids: set) -> None:
        """VersionSet release hook: drop views whose partition left every
        live Version (the device-side leg of the pin lifecycle)."""
        for key in [k for k in self._views if k not in live_ids]:
            self._drop(self._views.pop(key), "version_release")

    def clear(self) -> None:
        for key in list(self._views):
            self._drop(self._views.pop(key), "clear")

    # ---- fused batched execution ----
    def get_batch(self, dv: DeviceView, keys_u64, now) -> tuple:
        """Batched point gets. Full tier: one fused device composition +
        one result fetch. Index tier: the same single round trip returns
        (found, run, row) and values come from the host block cache."""
        keys_u64 = np.asarray(keys_u64, np.uint64)
        q = len(keys_u64)
        pad = _pow2pad(q)
        kq = np.pad(keys_u64, (0, pad - q))
        qk = jnp.asarray(CK.pack_u64(kq))
        nw = jnp.uint32(int(now))
        fd, vd, rid_d, row_d = ops.get_live(
            dv.remix, dv.runset, dv.exp, qk, nw, interpret=self.interpret
        )
        self._c_batches.inc()
        if dv.tier == "full":
            found, vals = _fetch(fd, vd)  # THE one host sync
            found, vals = found[:q], vals[:q]
            self._c_rows.inc(int(found.sum()))
            return found, vals
        found, rid, row = _fetch(fd, rid_d, row_d)
        found, rid, row = found[:q], rid[:q], row[:q]
        vals = np.zeros((q, dv.vw), np.uint32)
        for r in np.unique(rid[found]):
            m = found & (rid == r)
            vals[m] = dv.tables[r].rows_scattered("vals", row[m])
        self._c_rows.inc(int(found.sum()))
        return found, vals

    def scan_windows(
        self, dv: DeviceView, starts_u64, width: int, now,
        with_vals: bool = True,
    ) -> list:
        """Batched scan-window resolution: per query ``(keys (M,) u64,
        vals (M, VW) | None)`` — live entries of a ``width``-slot view
        window, same semantics as the host `gather_view` path."""
        starts_u64 = np.asarray(starts_u64, np.uint64)
        q = len(starts_u64)
        nw = jnp.uint32(int(now))
        if dv.tier == "full" or not with_vals:
            pad = _pow2pad(q)
            sq = np.pad(starts_u64, (0, pad - q))
            qk = jnp.asarray(CK.pack_u64(sq))
            kd, vd, md, _, _, _ = ops.scan_live(
                dv.remix, dv.runset, dv.exp, qk, nw, width=width,
                interpret=self.interpret,
            )
            self._c_batches.inc()
            if with_vals:
                keys, vals, valid = _fetch(kd, vd, md)
            else:
                keys, valid = _fetch(kd, md)
                vals = None
            out = []
            rows = 0
            for i in range(q):
                m = valid[i]
                kk = CK.unpack_u64(keys[i][m])
                rows += len(kk)
                out.append((kk, vals[i][m] if with_vals else None))
            self._c_rows.inc(rows)
            return out
        return self._scan_pipelined(dv, starts_u64, width, nw)

    def _scan_pipelined(self, dv, starts_u64, width, nw) -> list:
        """Index tier: double-buffered batch-sliced pipeline. The device
        resolves row windows for slice i+1 (async dispatch) while the
        host gathers slice i's value granules through the BlockCache."""
        s = self.slice_width
        q = len(starts_u64)
        nsl = -(-q // s)
        padded = np.zeros(nsl * s, np.uint64)
        padded[:q] = starts_u64
        pad = _pow2pad(s)

        def launch(si):
            sq = np.pad(padded[si * s:(si + 1) * s], (0, pad - s))
            qk = jnp.asarray(CK.pack_u64(sq))
            return ops.scan_live(
                dv.remix, dv.runset, dv.exp, qk, nw, width=width,
                interpret=self.interpret,
            )

        out: list = []
        rows = 0
        pending = launch(0)
        for si in range(nsl):
            nxt = launch(si + 1) if si + 1 < nsl else None
            kd, _, md, rid_d, row_d, _ = pending
            keys, valid, rid, row = _fetch(kd, md, rid_d, row_d)
            self._c_batches.inc()
            nq = min(s, q - si * s)
            keys, valid = keys[:nq], valid[:nq]
            rid, row = rid[:nq], row[:nq]
            # slice value gather: group live rows per run, one scattered
            # (granule-deduped) fetch per touched table
            vals = np.zeros((nq, width, dv.vw), np.uint32)
            rid_f, row_f = rid[valid], row[valid]
            gath = np.zeros((len(rid_f), dv.vw), np.uint32)
            for r in np.unique(rid_f):
                m = rid_f == r
                gath[m] = dv.tables[r].rows_scattered("vals", row_f[m])
            vals[valid] = gath
            for i in range(nq):
                m = valid[i]
                kk = CK.unpack_u64(keys[i][m])
                rows += len(kk)
                out.append((kk, vals[i][m]))
            pending = nxt
        self._c_rows.inc(rows)
        return out
