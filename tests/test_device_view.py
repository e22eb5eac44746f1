"""Device-resident query execution tests: differential parity between
the fused device path (interpret mode on CPU), the host promoted path
and the cold path — including tombstones, TTL expiry evaluated at query
time, and range-tombstone excised spans — plus residency-manager
behavior (budget tiers, LRU + version-release eviction, counters and
events) and the index-tier host/device gather pipeline."""
import dataclasses

import numpy as np
import pytest

from repro.db import clock
from repro.db.compaction import CompactionConfig
from repro.db.store import RemixDB, RemixDBConfig

T0 = 1_000_000.0
TTL = 50.0

SEEDS = [0, 1, 2, 3]
NIGHTLY_SEEDS = list(range(4, 20))


def _cfg(**kw):
    kw.setdefault("hot_threshold", 255)
    kw.setdefault("memtable_entries", 128)
    kw.setdefault("compaction", CompactionConfig(table_cap=128, t_max=3))
    return RemixDBConfig(vw=2, **kw)


def _metric(db, name):
    vals = [s["value"] for s in db.registry.snapshot()["metrics"]
            if s["name"] == name]
    assert vals, f"metric {name} not registered"
    return sum(vals)


def _populate(root, seed, n=500):
    """Mixed workload: puts, overwrites, deletes, TTL'd puts and one
    range delete — flushed to disk. Returns the touched key domain."""
    clock.set_source(lambda: T0)
    rng = np.random.default_rng(seed)
    keys = rng.choice(1 << 20, size=n, replace=False).astype(np.uint64)
    db = RemixDB.open(root, _cfg(device_path="off"))
    try:
        for i, k in enumerate(keys.tolist()):
            db.put(k, [i & 0xFFFF, i ^ 7])
        for k in keys[: n // 10].tolist():
            db.delete(k)
        for k in keys[n // 10: n // 5].tolist():
            db.put(k, [9, 9], ttl=TTL)  # expires at T0 + TTL
        lo = int(keys[n // 4])
        db.delete_range(lo, lo + 4096)
        db.flush()
    finally:
        db.close()
    return np.sort(keys)


def _probe_set(domain, rng):
    """Hits, deleted keys, TTL keys, excised keys and misses."""
    probe = np.concatenate(
        [domain, rng.choice(domain, 64, replace=False) + 1, [0, (1 << 21)]]
    ).astype(np.uint64)
    rng.shuffle(probe)
    return probe


def _row_eq(a, b):
    ka, va = a
    kb, vb = b
    np.testing.assert_array_equal(ka, kb)
    if va is None or vb is None:
        assert va is None and vb is None
    else:
        np.testing.assert_array_equal(va, vb)


def _assert_stores_agree(dev, host, domain, rng):
    probe = _probe_set(domain, rng)
    f_h, v_h = host.get_batch(probe)
    f_d, v_d = dev.get_batch(probe)
    np.testing.assert_array_equal(f_h, f_d)
    np.testing.assert_array_equal(v_h[f_h], v_d[f_d])
    starts = np.sort(rng.choice(domain, 24, replace=False))
    for n in (1, 7, 33):
        rows_h = [host.scan(int(s), n) for s in starts]
        rows_d = [dev.scan(int(s), n) for s in starts]
        for a, b in zip(rows_h, rows_d):
            _row_eq(a, b)
        k_h, m_h = host.scan_batch(starts, n)
        k_d, m_d = dev.scan_batch(starts, n)
        np.testing.assert_array_equal(m_h, m_d)
        np.testing.assert_array_equal(k_h[m_h], k_d[m_d])
    for k in probe[:48].tolist():
        a, b = host.get(k), dev.get(k)
        if a is None or b is None:
            assert a is None and b is None
        else:
            np.testing.assert_array_equal(a, b)
    return int(f_h.sum())


def _parity_one_seed(tmp_path, seed):
    root = str(tmp_path / "db")
    domain = _populate(root, seed)
    rng = np.random.default_rng(seed + 100)
    dev = RemixDB.open(root, _cfg(device_path="on", cold_reads=False))
    host = RemixDB.open(root, _cfg(device_path="off", cold_reads=False))
    cold = RemixDB.open(root, _cfg(device_path="off",
                                   promote_fraction=1e9))
    try:
        found_now = _assert_stores_agree(dev, host, domain, rng)
        _assert_stores_agree(dev, cold, domain, rng)
        assert dev.device_views is not None and len(dev.device_views) > 0
        # advance past every TTL: the device view is NOT re-uploaded —
        # expiry words are compared against the query clock on device
        clock.set_source(lambda: T0 + TTL + 10.0)
        found_later = _assert_stores_agree(dev, host, domain, rng)
        assert found_later < found_now  # the TTL'd rows really expired
    finally:
        clock.reset()
        dev.close(), host.close(), cold.close()


@pytest.mark.parametrize("seed", SEEDS)
def test_device_parity_differential(tmp_path, seed):
    _parity_one_seed(tmp_path, seed)


@pytest.mark.nightly
@pytest.mark.parametrize("seed", NIGHTLY_SEEDS)
def test_device_parity_differential_nightly(tmp_path, seed):
    _parity_one_seed(tmp_path, seed)


def test_index_tier_pipeline_parity(tmp_path):
    """Budget admits the index tier but not the value sections: the
    device resolves (run, row) windows, the host gathers value granules
    through the BlockCache in the double-buffered slice pipeline."""
    root = str(tmp_path / "db")
    domain = _populate(root, seed=7)
    host = RemixDB.open(root, _cfg(device_path="off", cold_reads=False))
    probe_cfg = RemixDB.open(root, _cfg(device_path="off"))
    full = min(p.device_view_bytes(True) for p in probe_cfg.partitions)
    idx = max(p.device_view_bytes(False) for p in probe_cfg.partitions)
    probe_cfg.close()
    assert idx < full  # the budget window below admits only the index tier
    dev = RemixDB.open(root, _cfg(device_path="on", cold_reads=False,
                                  device_budget_bytes=full - 1,
                                  device_slice=4))
    try:
        rng = np.random.default_rng(8)
        _assert_stores_agree(dev, host, domain, rng)
        tiers = {v.tier for v in dev.device_views._views.values()}
        assert tiers == {"index"}
        # a 24-query scan at slice width 4 crosses multiple slices: the
        # pipeline pays one sync per slice, never one per query
        starts = np.sort(rng.choice(domain, 24, replace=False))
        names = ("device_syncs", "cursor_seeks", "cursor_windows")
        s0 = [dev.registry.counter(n).value for n in names]
        dev.scan_batch(starts, 9)
        syncs, seeks, windows = (
            dev.registry.counter(n).value - v for n, v in zip(names, s0)
        )
        # rows the cursor answers pay one fetch per window, the seek
        # fused with the first
        assert seeks <= windows
        assert 0 < syncs - windows < len(starts)
    finally:
        clock.reset()
        dev.close(), host.close()


def test_budget_fallback_and_counters(tmp_path):
    """A budget no tier fits falls back to the legacy promoted path
    (counted), with identical results."""
    root = str(tmp_path / "db")
    domain = _populate(root, seed=11)
    host = RemixDB.open(root, _cfg(device_path="off", cold_reads=False))
    dev = RemixDB.open(root, _cfg(device_path="on", cold_reads=False,
                                  device_budget_bytes=16))
    try:
        rng = np.random.default_rng(12)
        _assert_stores_agree(dev, host, domain, rng)
        assert len(dev.device_views) == 0
        assert _metric(dev, "device_fallback_total") > 0
        assert _metric(dev, "device_batches") == 0
        assert _metric(dev, "hbm_resident_bytes") == 0
    finally:
        clock.reset()
        dev.close(), host.close()


def test_upload_metrics_and_events(tmp_path):
    root = str(tmp_path / "db")
    domain = _populate(root, seed=13)
    dev = RemixDB.open(root, _cfg(device_path="on", cold_reads=False))
    try:
        rng = np.random.default_rng(14)
        dev.get_batch(rng.choice(domain, 64, replace=False))
        assert _metric(dev, "device_batches") > 0
        assert _metric(dev, "device_rows_gathered") > 0
        resident = _metric(dev, "hbm_resident_bytes")
        assert resident == dev.device_views.resident_bytes > 0
        ups = dev.events.list("device_upload")
        assert ups and all(e.fields["bytes"] > 0 for e in ups)
        # rewrite every partition: the version release drops stale views
        clock.set_source(lambda: T0 + 1.0)
        for k in domain[::3].tolist():
            dev.put(k, [1, 2])
        dev.flush()
        evs = dev.events.list("device_evict")
        assert evs and any(
            e.fields["reason"] == "version_release" for e in evs
        )
    finally:
        clock.reset()
        dev.close()


def test_lru_eviction_under_budget_pressure(tmp_path):
    """A budget that fits one full view but not all partitions keeps the
    resident set within budget via LRU, with correct results throughout."""
    root = str(tmp_path / "db")
    domain = _populate(root, seed=17, n=800)
    probe_cfg = RemixDB.open(root, _cfg(device_path="off"))
    per = [p.device_view_bytes(True) for p in probe_cfg.partitions]
    probe_cfg.close()
    if len(per) < 2:
        pytest.skip("workload compacted into a single partition")
    budget = max(per)  # one view at a time
    host = RemixDB.open(root, _cfg(device_path="off", cold_reads=False))
    dev = RemixDB.open(root, _cfg(device_path="on", cold_reads=False,
                                  device_budget_bytes=budget))
    try:
        rng = np.random.default_rng(18)
        _assert_stores_agree(dev, host, domain, rng)
        assert dev.device_views.resident_bytes <= budget
    finally:
        clock.reset()
        dev.close(), host.close()


def test_store_rejects_bad_device_knobs(tmp_path):
    with pytest.raises(ValueError):
        RemixDB.open(str(tmp_path / "a"), _cfg(device_path="maybe"))
    with pytest.raises(ValueError):
        RemixDB.open(str(tmp_path / "b"), _cfg(device_slice=0))


# ------------------------------ DeviceViewManager.get_batch, both tiers
GET_T0 = 2_000_000  # the query clock of the get_batch cases


@pytest.fixture(scope="module", params=["full", "index"])
def get_store(request, tmp_path_factory):
    """One on-disk partition: live rows, a later run of tombstones, and
    TTL rows whose expiry is ``GET_T0`` (dead at it) or ``GET_T0 + 1``
    (live), served from a view in the requested tier. Yields the store,
    the view, the reference and the probe keys (every key and misses)."""
    from test_model_store import ModelStore

    tier = request.param
    root = str(tmp_path_factory.mktemp(f"get_{tier}") / "db")
    rng = np.random.default_rng(5)
    keys = rng.choice(1 << 20, 48, replace=False).astype(np.uint64)
    model = ModelStore()
    clock.set_source(lambda: GET_T0 - 10)
    db = RemixDB.open(root, _cfg(device_path="off"))
    try:
        for i, k in enumerate(keys.tolist()):
            db.put(k, [i, i ^ 0x55])
            model.put(k, [i, i ^ 0x55])
        db.flush()
        for k in keys[:8].tolist():
            db.delete(k)
            model.delete(k)
        db.flush()
        for j, k in enumerate(keys[8:16].tolist()):
            ttl = 10 + (j & 1)  # expires at GET_T0, or one second later
            db.put(k, [j, 7], ttl=ttl)
            model.put(k, [j, 7], GET_T0 - 10 + ttl)
        db.flush()
        assert len(db.partitions) == 1
        p = db.partitions[0]
        full, idx = p.device_view_bytes(True), p.device_view_bytes(False)
    finally:
        db.close()
        clock.reset()
    kw = {} if tier == "full" else {"device_budget_bytes": full - 1}
    assert idx < full - 1
    db = RemixDB.open(root, _cfg(device_path="on", cold_reads=False, **kw))
    dv = db.device_views.view_for(db.partitions[0])
    assert dv.tier == tier
    probe = np.concatenate([keys, (1 << 20) + keys[:8]])
    rng.shuffle(probe)
    yield db, dv, model, probe
    db.close()


def _check_gets(db, dv, model, batch, now):
    found, vals = db.device_views.get_batch(dv, batch, now)
    assert found.dtype == bool and found.shape == (len(batch),)
    assert vals.shape == (len(batch), 2)
    want = [model.get(k, now) for k in batch.tolist()]
    np.testing.assert_array_equal(found, [w is not None for w in want])
    np.testing.assert_array_equal(
        vals[found], np.array([w for w in want if w is not None],
                              np.uint32).reshape(-1, 2))
    return found


@pytest.mark.parametrize("q", [1, 7, 8, 9])
def test_get_batch_matches_model(get_store, q):
    """Every probe key, in batches of ``q`` under, at and over the
    least padded launch of 8, agrees bit for bit with the reference:
    tombstones hide, an expiry equal to ``now`` is dead and one a second
    later is live."""
    db, dv, model, probe = get_store
    n = len(probe)
    for i in range(0, n, q):
        _check_gets(db, dv, model, probe[(i + np.arange(q)) % n], GET_T0)


def test_get_live_now_is_traced(get_store):
    """A new clock value reuses the compiled lookup: ``now`` is a traced
    argument, and liveness still follows it."""
    from repro.kernels import ops

    db, dv, model, probe = get_store
    _check_gets(db, dv, model, probe, GET_T0)
    size = ops.get_live._cache_size()
    live = [int(_check_gets(db, dv, model, probe, t).sum())
            for t in (GET_T0 - 3, GET_T0 + 7)]
    assert ops.get_live._cache_size() == size
    assert live[0] == live[1] + 8  # every TTL row expired in between


def test_lone_get_one_launch_one_fetch(get_store, monkeypatch):
    """A lone get launches once, syncs once and fetches one array."""
    from repro.kernels import device_view

    db, dv, model, probe = get_store
    fetched = []
    real = device_view.fetch

    def spy(syncs, *arrays):
        fetched.append(len(arrays))
        return real(syncs, *arrays)

    monkeypatch.setattr(device_view, "fetch", spy)
    names = ("device_launches", "device_syncs")
    key = next(k for k in probe.tolist() if model.get(k, GET_T0))
    clock.set_source(lambda: GET_T0)
    try:
        s0 = [db.registry.counter(n).value for n in names]
        v = db.get(key)
        delta = [db.registry.counter(n).value - v0
                 for n, v0 in zip(names, s0)]
    finally:
        clock.reset()
    assert delta == [1, 1]
    assert fetched == [1]
    np.testing.assert_array_equal(v, model.get(key, GET_T0))


# ------------------------------- one device copy per promoted partition
def test_views_leave_the_legacy_copy_unbuilt(tmp_path, monkeypatch):
    """With views on, gets, lone and batched scans, streaming cursors,
    flushes and every kind of compaction never build a partition's
    legacy padded copy (``Partition.index``); each persisted REMIX is
    the one a scratch build over the partition's runs gives."""
    from repro.core.remix import build_remix
    from repro.core.runs import make_run
    from repro.db.partition import Partition
    from repro.io.remix_io import load_remix

    calls = []
    real = Partition.index
    monkeypatch.setattr(Partition, "index",
                        lambda self: calls.append(self) or real(self))
    db = RemixDB.open(str(tmp_path / "db"), _cfg(
        device_path="on", cold_reads=False,
        compaction=CompactionConfig(table_cap=128, t_max=3, split_m=2),
    ))
    model: dict[int, list] = {}
    rng = np.random.default_rng(21)
    try:
        for rnd in range(8):
            keys = rng.choice(1 << 16, 160, replace=False).astype(np.uint64)
            vals = np.stack([keys & np.uint64(0xFFFF),
                             np.full(len(keys), rnd, np.uint64)], 1)
            db.put_batch(keys, vals.astype(np.uint32))
            model.update({int(k): [int(k) & 0xFFFF, rnd] for k in keys})
            db.flush()
            live = sorted(model)
            probe = rng.choice(live, 24).astype(np.uint64)
            found, got = db.get_batch(probe)
            assert found.all()
            np.testing.assert_array_equal(got, [model[k] for k in
                                                probe.tolist()])
            for s in probe[:4].tolist():
                kk, _ = db.scan(s, 40)
                i = live.index(s)
                np.testing.assert_array_equal(kk, live[i:i + 40])
            kk, mm = db.scan_batch(np.sort(probe), 9)
            assert mm.any()
            with db.cursor(start=0, width=4) as cur:
                kk, _ = cur.next_batch(300)
            np.testing.assert_array_equal(kk, live[:300])
        assert {"minor", "split"} <= db._comp_kinds
        assert calls == []
        assert len(db.partitions) > 1
        assert all(p._remix is None for p in db.partitions)
        assert len(db.device_views) > 0
        for p in db.partitions:
            want, _ = build_remix(
                [make_run(t.keys, t.vals, seq=t.seq, sort=False)
                 for t in p.tables],
                d=max(db.cfg.d, len(p.tables)),
            )
            got = load_remix(db.storage.remix_path(p.remix_name))
            for f in ("anchors", "cursors", "selectors", "n_entries"):
                np.testing.assert_array_equal(
                    np.asarray(getattr(got, f)), np.asarray(getattr(want, f)),
                    err_msg=f,
                )
    finally:
        db.close()


def test_view_cursor_reads_at_its_opening_now(get_store):
    """A stream over a view (either tier) answers at the clock it opened
    at: TTL rows that expire between two of its windows still come back,
    as the reference gives them at the opening instant."""
    db, dv, model, _ = get_store
    opened, later = GET_T0 - 1, GET_T0 + 5
    names = ("cursor_seeks", "cursor_windows", "device_fallback_total")
    s0 = [_metric(db, n) for n in names]
    clock.set_source(lambda: opened)
    try:
        with db.cursor(start=0, width=4) as cur:
            first = cur.next_batch(1)
            clock.set_source(lambda: later)
            rest = cur.next_batch(1000)
    finally:
        clock.reset()
    seeks, windows, fallback = (_metric(db, n) - v for n, v in zip(names, s0))
    assert (seeks, fallback) == (1, 0) and windows >= 2
    assert db.partitions[0]._remix is None  # the view answered
    kk = np.concatenate([first[0], rest[0]])
    vv = np.concatenate([first[1], rest[1]])
    want = model.items(opened)
    np.testing.assert_array_equal(kk, [k for k, _ in want])
    np.testing.assert_array_equal(vv, [v for _, v in want])
    # some of what the later windows returned had expired by then
    assert any(model.get(k, later) is None for k in rest[0].tolist())
