"""Coalesced read groups in the executor, and batched scans over a live
MemTable overlay (``RemixDB._scan_group_at``).

The executor tests hold the store's one worker at a gate (a lookup that
blocks inside the store), queue a seeded mix of requests from 8 threads,
then open the gate: the worker then drains the queue in order, so which
requests form a group, and what each must return, follow from the queue
alone. Answers are checked against ``ModelStore`` replayed in queue
order. The store tests compare a batched scan group over a non-empty
overlay with the cursor's answer to each query alone on the same view.
"""
import random
import threading
import time

import numpy as np
import pytest

from repro.db import clock
from repro.db.ops import Batch, Op, OpStatus
from repro.db.store import RemixDB, RemixDBConfig
from test_model_store import ModelStore

VW = 2
N0 = 300  # loaded keys: 7, 14, ..., 7 * N0
GATE = 7  # the lookup that holds the worker
T0 = 1_000_000


@pytest.fixture(autouse=True)
def _fixed_clock():
    clock.set_source(lambda: float(T0))
    yield
    clock.reset()


def _vals(keys, salt=0):
    keys = np.asarray(keys, np.uint64)
    return np.stack([(keys & 0xFFFFFFFF) ^ salt, keys >> 32],
                    1).astype(np.uint32)


def _store(tmp_path, device_path="on", cold_reads=False, workers=1):
    db = RemixDB.open(
        str(tmp_path / "db"),
        RemixDBConfig(vw=VW, memtable_entries=1 << 30,
                      device_path=device_path, cold_reads=cold_reads,
                      submit_workers=workers),
    )
    keys = np.arange(1, N0 + 1, dtype=np.uint64) * 7
    db.put_batch(keys, _vals(keys))
    db.flush()
    assert not len(db.mem)
    model = ModelStore()
    for k, v in zip(keys.tolist(), _vals(keys)):
        model.put(k, v)
    return db, model


def _counter(db, name, **labels):
    return db.registry.counter(name, **labels).value


def _model_scan(model, start, n):
    return [(k, v) for k, v in model.items(T0) if k >= start][:n]


def _as_pairs(keys, vals):
    return [(int(k), tuple(int(x) for x in v)) for k, v in zip(keys, vals)]


# ------------------------------------------------------------ executor
class _Gate:
    """Blocks the worker inside the store on a lookup of ``GATE``."""

    def __init__(self, db, monkeypatch):
        self.entered, self.open = threading.Event(), threading.Event()
        orig = db._get_at

        def gated(view, key):
            if int(key) == GATE:
                self.entered.set()
                assert self.open.wait(60)
            return orig(view, key)

        monkeypatch.setattr(db, "_get_at", gated)
        self.fut = db.submit(Batch([Op.get(GATE)]))
        assert self.entered.wait(60)


def _recorded_groups(ex, monkeypatch) -> list:
    groups = []
    orig = ex._run_group

    def rec(jobs, t_take):
        groups.append([j[0] for j in jobs])
        return orig(jobs, t_take)

    monkeypatch.setattr(ex, "_run_group", rec)
    return groups


def _queued(ex, n, timeout=60.0) -> list:
    """The queued jobs' futures in queue order, once ``n`` are queued."""
    t_end = time.monotonic() + timeout
    while time.monotonic() < t_end:
        with ex._qcv:
            if len(ex._queue) >= n:
                return [job[0] for job in ex._queue]
        time.sleep(0.005)
    raise AssertionError("requests never reached the queue")


def _request(rng, fresh):
    r = rng.random()
    if r < 0.55:
        return ("scan", 7 * rng.randrange(1, N0 + 5) - rng.randrange(3),
                rng.randrange(1, 12))
    if r < 0.8:
        return ("get", 7 * rng.randrange(1, N0 + 5) + rng.choice([0, 3]))
    return ("insert", fresh())


def _op(req):
    if req[0] == "scan":
        return Op.scan(req[1], req[2])
    if req[0] == "get":
        return Op.get(req[1])
    return Op.put(req[1], _vals([req[1]], 0xABC)[0])


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_gated_groups_answer_as_queued(tmp_path, monkeypatch, seed):
    """8 threads queue a seeded mix behind the gate; every answer equals
    the model after the writes queued before it, groups hold only
    consecutive read-only requests, and some groups form."""
    db, model = _store(tmp_path)
    ex = db.engine()
    groups = _recorded_groups(ex, monkeypatch)
    gate = _Gate(db, monkeypatch)
    lock = threading.Lock()
    fresh_keys = iter(range(7 * 40 + 3, 1 << 40, 7 * 13))
    submitted = {}  # future -> request
    per_thread = 6

    def fresh():
        with lock:
            return next(fresh_keys)

    start = threading.Barrier(8)

    def client(t):
        rng = random.Random(seed * 100 + t)
        start.wait(30)
        for _ in range(per_thread):
            req = _request(rng, fresh)
            fut = db.submit(Batch([_op(req)]))
            with lock:
                submitted[fut] = req

    threads = [threading.Thread(target=client, args=(t,)) for t in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
        assert not t.is_alive()
    order = _queued(ex, 8 * per_thread)
    g0 = _counter(db, "coalesced_groups")
    gate.open.set()
    assert gate.fut.result(60).ok
    for fut in order:
        res = fut.result(60)[0]
        assert res.ok
        req = submitted[fut]
        if req[0] == "insert":
            model.put(req[1], _vals([req[1]], 0xABC)[0])
        elif req[0] == "get":
            want = model.get(req[1], T0)
            got = tuple(int(x) for x in res.value) if res.found else None
            assert got == want, req
        else:
            assert _as_pairs(res.keys, res.vals) == \
                _model_scan(model, req[1], req[2]), req
    pos = {f: i for i, f in enumerate(order)}
    assert groups and _counter(db, "coalesced_groups") - g0 == len(groups)
    for g in groups:
        idx = [pos[f] for f in g]
        assert idx == list(range(idx[0], idx[0] + len(g)))
        assert all(submitted[f][0] != "insert" for f in g)
        assert len(g) <= 64
    assert _counter(db, "coalesced_requests") >= 2 * len(groups)
    db.close()


def test_cancelled_or_late_member_fails_alone(tmp_path, monkeypatch):
    db, model = _store(tmp_path)
    ex = db.engine()
    groups = _recorded_groups(ex, monkeypatch)
    gate = _Gate(db, monkeypatch)
    futs = [
        db.submit(Batch([Op.scan(70, 5)])),
        db.submit(Batch([Op.scan(140, 5, deadline_ms=1)])),
        db.submit(Batch([Op.scan(210, 5)])),
        db.submit(Batch([Op.get(280)])),
        db.submit(Batch([Op.scan(350, 5)])),
    ]
    orig = ex._count_coalesced

    def cancel_third(stages, owners):
        # the group is running: cancel() flags the member's ops instead
        assert not futs[2].cancel()
        return orig(stages, owners)

    monkeypatch.setattr(ex, "_count_coalesced", cancel_third)
    time.sleep(0.01)  # past the second scan's deadline
    gate.open.set()
    res = [f.result(60)[0] for f in futs]
    assert [r.status for r in res] == [
        OpStatus.OK, OpStatus.DEADLINE_EXCEEDED, OpStatus.CANCELLED,
        OpStatus.OK, OpStatus.OK]
    assert groups == [futs]
    assert _as_pairs(res[0].keys, res[0].vals) == _model_scan(model, 70, 5)
    assert _as_pairs(res[4].keys, res[4].vals) == _model_scan(model, 350, 5)
    assert tuple(int(x) for x in res[3].value) == model.get(280, T0)
    db.close()


def test_write_batch_ends_a_group(tmp_path, monkeypatch):
    db, model = _store(tmp_path)
    ex = db.engine()
    groups = _recorded_groups(ex, monkeypatch)
    gate = _Gate(db, monkeypatch)
    new = 7 * 20 + 3
    a = [db.submit(Batch([Op.scan(140, 4)])) for _ in range(3)]
    w = db.submit(Batch([Op.put(new, _vals([new])[0])]))
    b = [db.submit(Batch([Op.scan(140, 4)])) for _ in range(3)]
    gate.open.set()
    before = [f.result(60)[0] for f in a]
    assert w.result(60).ok
    after = [f.result(60)[0] for f in b]
    assert groups == [a, b]
    assert all(int(r.keys[1]) == 147 for r in before)
    assert all(int(r.keys[1]) == new for r in after)
    db.close()


def test_one_client_forms_no_group(tmp_path):
    db, _ = _store(tmp_path, workers=2)
    rng = random.Random(5)
    fresh_keys = iter(range(7 * 50 + 3, 1 << 40, 7 * 11))
    for _ in range(40):
        req = _request(rng, lambda: next(fresh_keys))
        assert db.submit(Batch([_op(req)])).result(60).ok
    assert _counter(db, "coalesced_groups") == 0
    assert _counter(db, "coalesced_requests") == 0
    db.close()


def test_concurrent_reads_see_acknowledged_inserts(tmp_path):
    """8 threads of inserts, scans and lookups on 2 workers: every insert
    acknowledged before a read was submitted is in its answer."""
    db, _ = _store(tmp_path, workers=2)
    acked = []  # (t_ack, key)
    lock = threading.Lock()
    errors = []

    def client(t):
        rng = random.Random(t)
        try:
            for j in range(12):
                key = 7 * (10 + 24 * t + 2 * j) + 3
                if j % 3 == 0:
                    assert db.submit(
                        Batch([Op.put(key, _vals([key])[0])])).result(60).ok
                    with lock:
                        acked.append((time.monotonic(), key))
                    continue
                with lock:
                    seen = list(acked)
                start = 7 * rng.randrange(5, 250)
                t_sub = time.monotonic()
                res = db.submit(Batch([Op.scan(start, 10),
                                       Op.get(seen[-1][1] if seen else 7)])
                                ).result(60)
                assert res.ok
                kk = res[0].keys
                last = int(kk[-1])
                want = {k for ta, k in seen if ta < t_sub
                        and start <= k <= last}
                assert want <= set(kk.tolist())
                assert res[1].found
        except BaseException as e:  # reported after the join
            errors.append(e)

    threads = [threading.Thread(target=client, args=(t,)) for t in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
        assert not t.is_alive()
    assert not errors, errors[0]
    db.close()


# --------------------------------------------------------------- store
def _overlay(db, model, rng):
    """Inserts of new keys, overwrites and point deletes of loaded keys,
    and entries whose TTL has passed, all in the MemTable."""
    for _ in range(40):
        k = 7 * rng.randrange(1, N0 + 3) + rng.choice([0, 0, 2, 5])
        r = rng.random()
        if r < 0.5:
            v = _vals([k], 0x5A5)[0]
            db.put(k, v)
            model.put(k, v)
        elif r < 0.75:
            db.delete(k)
            model.delete(k)
        else:
            v = _vals([k], 0x77)[0]
            db.put(k, v, ttl=5)
            model.put(k, v, exp=T0 + 5)
    assert len(db.mem)


@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("path", ["device", "index", "cold"])
def test_group_scan_over_overlay_equals_cursor(tmp_path, seed, path):
    db, model = _store(tmp_path, device_path="on" if path == "device"
                       else "off", cold_reads=path == "cold")
    rng = random.Random(seed)
    _overlay(db, model, rng)
    clock.set_source(lambda: float(T0 + 10))  # the TTL entries expire
    q = 12
    starts = np.array([7 * rng.randrange(1, N0 + 2) - rng.randrange(4)
                       for _ in range(q)], np.uint64)
    ns = np.array([rng.randrange(1, 30) for _ in range(q)], np.int64)
    fb = _counter(db, "scan_cursor_fallbacks", reason="overlay")
    with db._view() as view:
        got = db._scan_group_at(view, starts, ns)
        keys_only = db._scan_group_at(view, starts, ns, with_vals=False)
        want = [db._scan_at(view, int(s), int(n))
                for s, n in zip(starts, ns)]
    assert _counter(db, "scan_cursor_fallbacks", reason="overlay") == fb
    for (gk, gv), (ko, kv), (wk, wv), s, n in zip(got, keys_only, want,
                                                 starts, ns):
        assert gk.dtype == wk.dtype and gv.dtype == wv.dtype
        np.testing.assert_array_equal(gk, wk)
        np.testing.assert_array_equal(gv, wv)
        np.testing.assert_array_equal(ko, wk)
        assert kv is None
        model_now = [(k, v) for k, v in model.items(T0 + 10)
                     if k >= int(s)][:int(n)]
        assert _as_pairs(gk, gv) == model_now
    db.close()


def test_overlay_group_is_one_device_call(tmp_path):
    db, _ = _store(tmp_path)
    db.put(7 * 30 + 2, _vals([7 * 30 + 2])[0])
    names = ("scan_live_calls", "scan_live_queries", "device_syncs")
    before = [_counter(db, n) for n in names]
    fb = {r: _counter(db, "scan_cursor_fallbacks", reason=r)
          for r in ("overlay", "underfull")}
    res = db.submit(Batch([Op.scan(7 * k, 9) for k in (3, 28, 90)])).result()
    assert res.ok and int(res[1].keys[3]) == 7 * 30 + 2
    assert [_counter(db, n) - b for n, b in zip(names, before)] == [1, 3, 1]
    assert {r: _counter(db, "scan_cursor_fallbacks", reason=r) - v
            for r, v in fb.items()} == {"overlay": 0, "underfull": 0}
    db.close()


def test_underfull_and_range_fallbacks_still_fire(tmp_path):
    db, model = _store(tmp_path)
    db.put(7 * 30 + 2, _vals([7 * 30 + 2])[0])
    model.put(7 * 30 + 2, _vals([7 * 30 + 2])[0])
    under = _counter(db, "scan_cursor_fallbacks", reason="underfull")
    ranges = _counter(db, "scan_cursor_fallbacks", reason="overlay")
    # a window past the last loaded key comes back short: the cursor
    res = db.submit(Batch([Op.scan(7 * 3, 9),
                           Op.scan(7 * (N0 - 2), 9)])).result()
    assert res.ok
    assert _counter(db, "scan_cursor_fallbacks",
                    reason="underfull") - under == 1
    assert _as_pairs(res[1].keys, res[1].vals) == \
        _model_scan(model, 7 * (N0 - 2), 9)
    # an unflushed range tombstone: every scan of the group by cursor
    db.delete_range(7 * 29, 7 * 32)
    model.delete_range(7 * 29, 7 * 32)
    res = db.submit(Batch([Op.scan(7 * 3, 9), Op.scan(7 * 27, 9)])).result()
    assert res.ok
    assert _counter(db, "scan_cursor_fallbacks",
                    reason="overlay") - ranges == 2
    assert _as_pairs(res[1].keys, res[1].vals) == \
        _model_scan(model, 7 * 27, 9)
    db.close()
