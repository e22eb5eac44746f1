"""Integration tests for RemixDB and the baseline stores."""
import numpy as np
import pytest

from repro.db.baseline import BaselineConfig, LeveledStore, TieredStore
from repro.db.compaction import CompactionConfig
from repro.db.store import RemixDB, RemixDBConfig


def small_cfg(tmp_path, **kw):
    comp = CompactionConfig(table_cap=256, t_max=6)
    return RemixDBConfig(
        memtable_entries=kw.pop("memtable_entries", 512),
        compaction=comp,
        wal_dir=str(tmp_path),
        hot_threshold=kw.pop("hot_threshold", 255),
        **kw,
    )


def test_put_get_scan_roundtrip(tmp_path):
    db = RemixDB(small_cfg(tmp_path))
    rng = np.random.default_rng(0)
    keys = rng.choice(100_000, size=3000, replace=False).astype(np.uint64)
    vals = np.stack([keys & 0xFFFFFFFF, keys >> 32], axis=1).astype(np.uint32)
    db.put_batch(keys, vals)
    db.flush()
    # point lookups
    probe = np.concatenate([keys[:500], np.array([100_001, 100_002], np.uint64)])
    found, got = db.get_batch(probe)
    assert found[:500].all() and not found[500:].any()
    np.testing.assert_array_equal(got[:500, 0], (probe[:500] & 0xFFFFFFFF).astype(np.uint32))
    # range scan
    skeys = np.sort(keys)
    start = int(skeys[1000])
    kk, vv = db.scan(start, 64)
    np.testing.assert_array_equal(kk, skeys[1000:1064])


def test_overwrite_and_delete(tmp_path):
    db = RemixDB(small_cfg(tmp_path))
    db.put(5, [1, 1])
    db.put(6, [2, 2])
    db.flush()
    db.put(5, [9, 9])  # overwrite, newer version
    db.delete(6)
    db.flush()
    assert db.get(5) is not None and int(db.get(5)[0]) == 9
    assert db.get(6) is None
    kk, _ = db.scan(0, 10)
    assert list(kk) == [5]


def test_compaction_kinds_progress(tmp_path):
    cfg = small_cfg(tmp_path, memtable_entries=400)
    cfg.compaction = CompactionConfig(table_cap=128, t_max=4, split_m=2)
    db = RemixDB(cfg)
    rng = np.random.default_rng(1)
    for i in range(20):
        keys = rng.choice(50_000, size=400, replace=False).astype(np.uint64)
        vals = np.zeros((400, 2), np.uint32)
        db.put_batch(keys, vals)
        db.flush()
    kinds_seen = {k for st in db.compaction_log for k in st["kinds"]}
    assert "minor" in kinds_seen and ("major" in kinds_seen or "split" in kinds_seen)
    # store stays queryable and partitioned
    s = db.stats()
    assert s["partitions"] >= 1 and s["tables"] >= 1
    found, _ = db.get_batch(keys[:100])
    assert found.all()


def test_split_creates_partitions(tmp_path):
    cfg = small_cfg(tmp_path, memtable_entries=2048)
    cfg.compaction = CompactionConfig(table_cap=128, t_max=3, split_m=2)
    db = RemixDB(cfg)
    keys = np.arange(0, 4096, dtype=np.uint64)
    db.put_batch(keys, np.zeros((len(keys), 2), np.uint32))
    db.flush()
    for _ in range(3):  # force more data through to trigger splits
        db.put_batch(keys, np.zeros((len(keys), 2), np.uint32))
        db.flush()
    assert len(db.partitions) > 1
    # routing still exact across partition boundaries
    found, _ = db.get_batch(keys[::17])
    assert found.all()
    kk, _ = db.scan(0, 200)
    np.testing.assert_array_equal(kk, keys[:200])


def test_hot_keys_stay_buffered(tmp_path):
    cfg = small_cfg(tmp_path, hot_threshold=3, memtable_entries=1 << 30)
    db = RemixDB(cfg)
    for i in range(6):  # 6 updates to key 42 -> count 6 > 3
        db.put(42, [i, i])
    db.put(7, [7, 7])
    db.flush()
    # hot key 42 must not be in any table; cold key 7 must be
    in_tables = [int(k) for p in db.partitions for t in p.tables for k in t.keys]
    assert 7 in in_tables and 42 not in in_tables
    assert db.mem.get(42) is not None  # carried over, counter halved
    assert db.mem.get(42).count == 3
    assert int(db.get(42)[0]) == 5  # newest value survives


def test_wal_recovery(tmp_path):
    cfg = small_cfg(tmp_path, memtable_entries=1 << 30)
    db = RemixDB(cfg)
    for i in range(100):
        db.put(i, [i, 0])
    db.delete(50)
    db.wal.sync()
    mem = db.recover_memtable()  # simulate restart before flush
    assert len(mem) == 100
    assert mem.get(50).tomb and not mem.get(51).tomb
    assert int(mem.get(99).val[0]) == 99


def test_wal_gc_keeps_live_only(tmp_path):
    cfg = small_cfg(tmp_path, memtable_entries=1 << 30)
    db = RemixDB(cfg)
    for i in range(2000):
        db.put(i, [i, 0])
    blocks_before = db.wal.used_blocks() + len(db.wal._pending) // 100
    db.flush()  # everything cold -> flushed -> WAL GC drops all
    assert db.wal.used_blocks() == 0
    # hot path: re-put a few keys many times, flush, they survive GC
    cfg2 = small_cfg(tmp_path / "w2", hot_threshold=2, memtable_entries=1 << 30)
    db2 = RemixDB(cfg2)
    for _ in range(5):
        for k in (1, 2, 3):
            db2.put(k, [k, 0])
    db2.flush()
    live = {k for k, *_ in db2.wal.replay()}
    assert live == {1, 2, 3}


def test_virtual_log_block_remap(tmp_path):
    from repro.db.wal import WAL

    w = WAL(str(tmp_path / "wal.log"), vw=2)
    for i in range(500):
        w.append(i, i, False, np.array([i, 0], np.uint32))
    w.sync()
    # keep 80% of keys -> most blocks remapped valid, no rewrite
    live = set(range(0, 500, 5)).symmetric_difference(range(500))
    w.gc(set(live))
    recovered = {k for k, *_ in w.replay()}
    assert recovered == set(live)
    # keep 10% -> blocks freed + survivors rewritten
    live2 = set(range(0, 500, 10)) & live
    w.gc(live2)
    assert {k for k, *_ in w.replay()} == live2
    assert len(w.free) > 0 or w.used_blocks() < 30


def test_baseline_stores_agree_with_remixdb(tmp_path):
    rng = np.random.default_rng(3)
    keys = rng.choice(30_000, size=4000, replace=False).astype(np.uint64)
    vals = np.stack([keys & 0xFFFFFFFF, keys >> 32], 1).astype(np.uint32)
    bcfg = BaselineConfig(memtable_entries=512, table_cap=512)
    stores = [LeveledStore(bcfg), TieredStore(bcfg)]
    db = RemixDB(small_cfg(tmp_path, memtable_entries=512))
    for chunk in range(0, 4000, 1000):
        sl = slice(chunk, chunk + 1000)
        db.put_batch(keys[sl], vals[sl])
        for s in stores:
            s.put_batch(keys[sl], vals[sl])
    db.flush()
    for s in stores:
        s.flush()
    probe = np.concatenate([keys[::13], np.array([30_001], np.uint64)])
    f0, v0 = db.get_batch(probe)
    for s in stores:
        f, v = s.get_batch(probe)
        np.testing.assert_array_equal(f, f0)
        np.testing.assert_array_equal(v[f], v0[f0])
    skeys = np.sort(keys)
    start = int(skeys[100])
    k0, _ = db.scan(start, 50)
    for s in stores:
        k, _ = s.scan(start, 50)
        np.testing.assert_array_equal(k, k0)
    # tiered must write less than leveled (the paper's WA premise)
    assert stores[1].write_amplification() <= stores[0].write_amplification()


def test_scan_batch_matches_scan(tmp_path):
    rng = np.random.default_rng(9)
    keys = rng.choice(50_000, size=6000, replace=False).astype(np.uint64)
    db = RemixDB(small_cfg(tmp_path, memtable_entries=1024))
    lv = LeveledStore(BaselineConfig(memtable_entries=1024, table_cap=1024))
    vals = np.zeros((len(keys), 2), np.uint32)
    db.put_batch(keys, vals)
    lv.put_batch(keys, vals)
    db.flush()
    lv.flush()
    starts = rng.choice(np.sort(keys), 40)
    for s in (db, lv):
        bk, bm = s.scan_batch(starts, 20)
        for i, st in enumerate(starts):
            kk, _ = s.scan(int(st), 20)
            np.testing.assert_array_equal(bk[i][bm[i]], kk[:20])


def test_write_amplification_ordering(tmp_path):
    """Paper fig 16 premise: tiered < RemixDB (tiered + REMIX) < leveled."""
    rng = np.random.default_rng(4)
    n = 60_000
    keys = rng.permutation(n).astype(np.uint64)
    vals = np.zeros((n, 2), np.uint32)
    cfg = RemixDBConfig(
        memtable_entries=2048,
        wal_dir=str(tmp_path),
        compaction=CompactionConfig(table_cap=2048, t_max=10),
    )
    db = RemixDB(cfg)
    lv = LeveledStore(BaselineConfig(memtable_entries=2048, table_cap=2048))
    tr = TieredStore(BaselineConfig(memtable_entries=2048, table_cap=2048))
    for c in range(0, n, 2048):
        sl = slice(c, c + 2048)
        db.put_batch(keys[sl], vals[sl])
        lv.put_batch(keys[sl], vals[sl])
        tr.put_batch(keys[sl], vals[sl])
    db.flush()
    lv.flush()
    tr.flush()
    wa_db = db.table_bytes_written / max(1, db.user_bytes)
    wa_lv = lv.write_amplification()
    wa_tr = tr.write_amplification()
    assert wa_tr < wa_db < wa_lv, (wa_tr, wa_db, wa_lv)
