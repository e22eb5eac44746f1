"""Cursor windows on promoted partitions against a plain model of the
merged view.

A promoted stream opens with its seek fused with its first window (one
launch, one fetch of one packed buffer) and pulls every later window
from the saved view position (again one launch, one fetch). These tests
hold scans and streaming cursors over several partitions, MemTable
overlay entries, point tombstones and range tombstones to a dict model
of the live entries, for both promoted read paths: the legacy
``p.index()`` copy read by ``core.query`` (``device_path="off"``) and the
budgeted device view read by the Pallas compositions (``"on"``,
interpreted on the CPU).
"""
import bisect

import numpy as np
import pytest

from repro.db.compaction import CompactionConfig
from repro.db.store import RemixDB, RemixDBConfig

LENGTHS = (1, 2, 9, 33, 64, 100, 150)
PATHS = ["off", "on"]
PATH_IDS = ["legacy", "view"]


class Model:
    """The live entries of the merged view: key -> value words."""

    def __init__(self):
        self.live: dict[int, np.ndarray] = {}

    def put(self, keys, vals):
        for k, v in zip(keys.tolist(), vals):
            self.live[int(k)] = v.copy()

    def delete(self, key):
        self.live.pop(int(key), None)

    def delete_range(self, lo, hi):
        for k in [k for k in self.live if lo <= k < hi]:
            del self.live[k]

    def scan(self, start, n):
        keys = sorted(self.live)
        i = bisect.bisect_left(keys, start)
        kk = keys[i:i + n]
        vv = [self.live[k] for k in kk]
        return (np.array(kk, np.uint64),
                np.array(vv, np.uint32).reshape(len(kk), 2))


def _vals(keys, version):
    keys = np.asarray(keys, np.uint64)
    lo = (keys & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    return np.stack([lo, np.full(len(keys), version, np.uint32)], 1)


@pytest.fixture(scope="module", params=PATHS, ids=PATH_IDS)
def store(request, tmp_path_factory):
    """A store of several promoted partitions, with flushed point and
    range tombstones, older versions under newer ones, and a MemTable
    overlay of new keys, updates, point deletes and a range delete."""
    cfg = RemixDBConfig(
        memtable_entries=2048,
        compaction=CompactionConfig(table_cap=128, t_max=3, split_m=2),
        hot_threshold=255, cold_reads=False, device_path=request.param,
    )
    db = RemixDB.open(str(tmp_path_factory.mktemp("db")), cfg)
    model = Model()
    keys = np.arange(0, 3 * 1536, 3, dtype=np.uint64)
    for version in range(3):
        sub = keys[version::2] if version else keys
        db.put_batch(sub, _vals(sub, version))
        model.put(sub, _vals(sub, version))
        db.flush()
    for k in keys[::7].tolist():  # flushed point tombstones
        db.delete(k)
        model.delete(k)
    db.delete_range(1200, 1500)  # a flushed range tombstone
    model.delete_range(1200, 1500)
    db.flush()
    assert len(db.partitions) > 2
    assert not any(db._cold_ok(p) for p in db.partitions)
    # the MemTable overlay: new keys between table keys, updates, point
    # deletes of table keys and an unflushed range delete
    new = np.arange(1, 3 * 1536, 41, dtype=np.uint64)
    db.put_batch(new, _vals(new, 7))
    model.put(new, _vals(new, 7))
    upd = keys[5::53]
    db.put_batch(upd, _vals(upd, 8))
    model.put(upd, _vals(upd, 8))
    for k in keys[11::61].tolist():
        db.delete(k)
        model.delete(k)
    db.delete_range(3000, 3300)
    model.delete_range(3000, 3300)
    assert len(db.mem)
    yield db, model
    db.close()


def _starts(db, model):
    """Per partition its first key, a middle key and its last key, and
    keys just below the next partition's range (a scan that crosses
    the boundary)."""
    live = sorted(model.live)
    out = []
    los = [p.lo for p in db.partitions] + [1 << 64]
    for lo, hi in zip(los, los[1:]):
        inside = [k for k in live if lo <= k < hi]
        if not inside:
            continue
        out += [inside[0], inside[len(inside) // 2], inside[-1],
                inside[max(0, len(inside) - 20)]]
    return out


def _assert_same(got, want):
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])


@pytest.mark.parametrize("n", LENGTHS)
def test_scan_matches_model(store, n):
    db, model = store
    for start in _starts(db, model):
        _assert_same(db.scan(start, n), model.scan(start, n))


def test_lone_scan_without_overlay_matches_model(tmp_path):
    """Flushed state only: the lone-scan path the served cells take."""
    cfg = RemixDBConfig(
        memtable_entries=1 << 30, cold_reads=False,
        compaction=CompactionConfig(table_cap=256, t_max=6),
    )
    db = RemixDB.open(str(tmp_path / "db"), cfg)
    model = Model()
    keys = np.arange(10, 20_000, 10, dtype=np.uint64)
    db.put_batch(keys, _vals(keys, 0))
    model.put(keys, _vals(keys, 0))
    db.flush()
    db.delete_range(500, 900)
    model.delete_range(500, 900)
    db.flush()
    assert not len(db.mem)
    for start in (0, 10, 11, 495, 9_995, 19_990, 19_991):
        for n in LENGTHS:
            _assert_same(db.scan(start, n), model.scan(start, n))
    db.close()


@pytest.mark.parametrize("width", [4, 16])
def test_streaming_cursor_matches_model(store, width):
    db, model = store
    takes = (1, 3, 40, 150, 400)
    for start in (0, _starts(db, model)[5]):
        with db.cursor(start=start, width=width) as cur:
            got = [cur.next_batch(n) for n in takes]
        kk = np.concatenate([k for k, _ in got])
        vv = np.concatenate([v for _, v in got])
        _assert_same((kk, vv), model.scan(start, sum(takes)))


def test_stream_of_k_windows_pays_k_launches_and_k_syncs(store):
    """One promoted partition, no overlay entry in range: the open is one
    launch and one fetch, each later window one more of each."""
    db, model = store
    names = ("device_launches", "device_syncs", "cursor_seeks",
             "cursor_windows")
    before = [db.registry.counter(c).value for c in names]
    p0_hi = db.partitions[1].lo
    with db.cursor(start=0, width=4) as cur:
        kk, _ = cur.next_batch(150)
    launches, syncs, seeks, windows = (
        db.registry.counter(c).value - v for c, v in zip(names, before))
    assert int(kk[-1]) < p0_hi  # the stream stayed in one partition
    assert (seeks, launches, syncs) == (1, windows, windows)
    assert windows >= 3
