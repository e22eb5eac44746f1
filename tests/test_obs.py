"""Observability layer tests (repro.obs + its wiring into every tier).

Covered here:
  - metrics registry: counters/gauges/multi-gauges/histograms, labels,
    snapshot/merge/diff/Prometheus rendering, the disabled null path
  - histogram percentile estimates vs numpy ground truth (log-bucketed
    bounds: relative error bounded by the bucket growth factor)
  - stats() backward compatibility: every pre-existing stats() dict
    (store, cache, executor, WAL, versions) keeps its exact keys and
    counts through the registry-backed rewrite
  - op-lifecycle tracing: a traced mixed cross-shard batch yields a
    well-formed span tree whose leaf spans cover >= 90% of the batch
    wall time, exportable as valid Chrome trace_event JSON
  - structured event log: flush -> wal_gc -> version_publish ->
    compaction ordering, ring bounding, the JSONL sink
  - CKB interval-memo bounding: entry-budget eviction + gauges
  - thread-safety smoke: concurrent increments/observes land exactly
"""
import json
import os
import threading

import numpy as np
import pytest

from repro.obs.events import EventLog, NULL_EVENTS
from repro.obs.metrics import (
    MetricsRegistry,
    diff_snapshots,
    merge_snapshots,
    render_prometheus,
)
from repro.obs.tracing import Sampler, Trace


# ---------------------------------------------------------------- metrics
def test_counter_gauge_basics():
    reg = MetricsRegistry(labels=dict(node="a"))
    c = reg.counter("reqs", kind="get")
    c.inc()
    c.inc(4)
    assert c.value == 5
    assert reg.counter("reqs", kind="get") is c  # get-or-create
    g = reg.gauge("depth")
    g.set(7)
    g.dec(2)
    assert g.value == 5
    cb = reg.gauge("live", fn=lambda: 42)
    assert cb.value == 42
    samples = reg.snapshot()["metrics"]
    names = {(s["name"], tuple(sorted(s["labels"].items()))) for s in samples}
    assert ("reqs", (("kind", "get"), ("node", "a"))) in names
    with pytest.raises(ValueError):
        c.inc(-1)


def test_disabled_registry_is_null():
    reg = MetricsRegistry(enabled=False)
    c = reg.counter("x")
    c.inc(100)
    assert c.value == 0
    assert reg.gauge("y", fn=lambda: 9).value == 0
    reg.histogram("z").observe(1.0)
    assert reg.snapshot() == {"metrics": []}


def test_histogram_percentiles_vs_numpy():
    rng = np.random.default_rng(7)
    obs = rng.lognormal(mean=-7.0, sigma=1.2, size=20_000)
    reg = MetricsRegistry()
    h = reg.histogram("lat")
    for v in obs:
        h.observe(float(v))
    # bucket bounds grow by 2**0.25 per step: a geometric-midpoint
    # estimate is off by at most ~ sqrt(growth)-1 ~ 9% relative
    for q in (0.50, 0.90, 0.95, 0.99):
        est = h.percentile(q)
        ref = float(np.percentile(obs, 100 * q))
        assert abs(est - ref) / ref < 0.1, (q, est, ref)
    s = h.summary()
    assert s["count"] == len(obs)
    assert s["min"] <= s["p50"] <= s["p95"] <= s["p99"] <= s["max"]
    assert np.isclose(s["sum"], obs.sum(), rtol=1e-6)


def test_histogram_extremes_clamped():
    reg = MetricsRegistry()
    h = reg.histogram("b", kind="bytes")
    h.observe(3)
    assert h.percentile(0.5) == pytest.approx(3.0, rel=0.5)
    assert h.percentile(0.99) <= h.summary()["max"]
    assert reg.histogram("empty").percentile(0.99) == 0.0


def test_snapshot_merge_diff_prometheus():
    r1, r2 = MetricsRegistry(), MetricsRegistry()
    r1.counter("hits").inc(3)
    r2.counter("hits").inc(5)
    merged = merge_snapshots(
        (r1.snapshot(), dict(shard="0")), (r2.snapshot(), dict(shard="1"))
    )
    vals = {s["labels"]["shard"]: s["value"] for s in merged["metrics"]}
    assert vals == {"0": 3, "1": 5}
    before = r1.snapshot()
    r1.counter("hits").inc(2)
    r1.histogram("lat").observe(0.5)
    d = diff_snapshots(before, r1.snapshot())["diff"]
    by_name = {row["name"]: row for row in d}
    assert by_name["hits"]["delta"] == 2
    assert by_name["lat"]["status"] == "added"
    text = render_prometheus(r1.snapshot())
    assert "# TYPE hits counter" in text
    assert 'lat_bucket{le="+Inf"} 1' in text
    assert "lat_count 1" in text


def test_registry_threaded_smoke():
    reg = MetricsRegistry()
    c = reg.counter("n")
    h = reg.histogram("lat")
    n_threads, per = 8, 2000

    def work():
        for i in range(per):
            c.inc()
            h.observe(1e-4 * (1 + i % 7))

    ts = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert c.value == n_threads * per
    assert h.summary()["count"] == n_threads * per


# ---------------------------------------------------------------- events
def test_event_log_ring_and_sink(tmp_path):
    path = tmp_path / "events.jsonl"
    log = EventLog(capacity=4, jsonl_path=str(path))
    for i in range(6):
        log.emit("tick", i=i)
    evs = log.list()
    assert [e.fields["i"] for e in evs] == [2, 3, 4, 5]  # ring dropped 0,1
    assert evs[0].seq == 3 and evs[-1].seq == 6  # seq keeps counting
    st = log.stats()
    assert st["emitted"] == 6 and st["dropped"] == 2 and st["buffered"] == 4
    log.close()
    lines = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert len(lines) == 6  # the sink saw every event, ring or not
    assert lines[0]["kind"] == "tick" and lines[0]["i"] == 0
    assert NULL_EVENTS.emit("x") is None and NULL_EVENTS.list() == []
    with pytest.raises(ValueError):
        EventLog(capacity=0)


# ---------------------------------------------------------------- tracing
def test_trace_tree_and_chrome_export():
    from repro.obs.tracing import now

    tr = Trace("batch")
    with tr.span("plan"):
        pass
    with tr.span("read", shard=0):
        t0 = now()
        tr.leaf("disk_read", t0, now(), bytes=512)
    tr.finish()
    assert tr.well_formed()
    names = [s.name for s in tr.spans()]
    assert names == ["batch", "plan", "read", "disk_read"]
    doc = json.loads(tr.to_chrome_json())
    evs = doc["traceEvents"]
    assert all(e["ph"] == "X" for e in evs)
    assert {e["name"] for e in evs} == set(names)
    by = {e["name"]: e for e in evs}
    assert by["disk_read"]["args"]["bytes"] == 512
    assert by["batch"]["ts"] == 0


def test_sampler_rate():
    s = Sampler(0.25)
    picks = [s.should_sample() for _ in range(12)]
    assert picks == [True, False, False, False] * 3
    assert not any(Sampler(0.0).should_sample() for _ in range(8))
    assert all(Sampler(1.0).should_sample() for _ in range(8))
    with pytest.raises(ValueError):
        Sampler(1.5)


# ------------------------------------------------- stats() compatibility
def _fill(db, lo=1, n=300, step=7):
    keys = np.arange(lo, lo + n, dtype=np.uint64) * step
    vals = np.stack([keys & 0xFFFFFFFF, keys >> 32], 1).astype(np.uint32)
    db.put_batch(keys, vals)
    return keys


def test_store_stats_keys_unchanged(tmp_path):
    from repro.db.store import RemixDB, RemixDBConfig

    db = RemixDB.open(
        str(tmp_path / "db"), RemixDBConfig(memtable_entries=1 << 30)
    )
    keys = _fill(db)
    db.flush()
    db.get(int(keys[0]))
    s = db.stats()
    assert set(s) == {
        "partitions", "tables", "entries", "resident_tables", "memtable",
        "wa", "wal_blocks", "disk_bytes_read", "cold", "versions",
        "compaction", "health", "engine", "cache",
    }
    assert set(s["health"]) == {
        "status", "unavailable", "quarantine_files", "partitions", "io",
        "corruption_detected", "scrub", "repair",
    }
    assert s["health"]["status"] == "ok"
    assert s["health"]["partitions"][0]["degraded"] is False
    assert set(s["compaction"]) == {
        "rounds", "bytes_written", "kinds", "log_rounds", "in_flight"
    }
    assert s["compaction"]["rounds"] == 1
    assert s["compaction"]["kinds"] == {"minor": 1}
    assert s["compaction"]["bytes_written"] == db.table_bytes_written > 0
    assert set(s["cold"]) == {"gets", "scans"}
    assert set(s["versions"]) == {"current", "live", "pinned"}
    assert set(s["cache"]) >= {
        "hits", "misses", "evictions", "entries", "cached_bytes",
        "capacity_bytes",
    }
    # wa is the registry-backed ratio of the same two counters as before
    assert s["wa"] == pytest.approx(
        (db.table_bytes_written + db.wal.bytes_written)
        / max(1, db.user_bytes)
    )
    eng = s["engine"]
    assert set(eng) == {
        "batches", "completed", "cancelled_batches", "ops",
        "deadline_exceeded", "cancelled_ops", "errors", "io_errors",
        "queue_depth", "workers", "admission", "shards",
    }
    assert eng["io_errors"] == 0
    assert eng["ops"] == {
        "get": 1, "multiget": 0, "scan": 0, "put": 1, "delete": 0,
        "delete_range": 0, "cas": 0,
    }
    assert set(eng["admission"]) == {
        "max_bytes", "inflight_bytes", "peak_bytes", "admitted", "waits"
    }
    db.close()


def test_metrics_snapshot_and_disabled_store(tmp_path):
    from repro.db.store import RemixDB, RemixDBConfig

    db = RemixDB.open(
        str(tmp_path / "on"), RemixDBConfig(memtable_entries=1 << 30)
    )
    _fill(db)
    db.flush()
    snap = db.metrics()
    names = {s["name"] for s in snap["metrics"]}
    assert {"db_user_bytes", "db_table_bytes_written", "wal_bytes_written",
            "cache_hits", "versions_published",
            "db_flush_seconds"} <= names
    text = render_prometheus(snap)
    assert "db_flush_seconds_count 1" in text
    db.close()
    off = RemixDB(RemixDBConfig(metrics=False, memtable_entries=1 << 30))
    _fill(off)
    off.flush()
    # registry-backed fields read zero; structure stays intact
    assert off.metrics() == {"metrics": []}
    assert off.events.list() == []
    assert off.stats()["compaction"]["rounds"] == 0
    off.close()


# ------------------------------------------------------- tracing (store)
def test_traced_cross_shard_batch(tmp_path):
    from repro.db.ops import Batch
    from repro.db.store import RemixDB, RemixDBConfig
    from repro.serve.engine import KVServeEngine

    split = 1 << 32
    dirs = []
    for i, lo in enumerate((0, split)):
        d = str(tmp_path / f"s{i}")
        db = RemixDB.open(d, RemixDBConfig(memtable_entries=1 << 30))
        _fill(db, lo=lo + 1, n=200, step=1)
        db.flush()
        db.close()
        dirs.append(d)
    eng = KVServeEngine([(0, dirs[0]), (split, dirs[1])])
    b = (
        Batch(trace=True)
        .get(5)
        .get(split + 10)
        .multiget(np.arange(20, 30, dtype=np.uint64))
        .scan(split + 50, 16)
        .put(9, [1, 2])
        .delete(split + 60)
    )
    res = eng.submit(b, sync=True).result()
    assert res.ok
    tr = res.trace
    assert tr is not None and tr.well_formed()
    names = [s.name for s in tr.spans()]
    assert names[0] == "batch" and "plan" in names
    assert any(n == "shard0:read" for n in names)
    assert any(n == "shard1:read" for n in names)
    assert any(n.endswith(":commit") for n in names)
    # leaf spans account for >= 90% of the batch wall time
    assert tr.leaf_coverage() >= 0.9, tr.leaf_coverage()
    doc = json.loads(tr.to_chrome_json())
    assert doc["displayTimeUnit"] == "ms"
    assert len(doc["traceEvents"]) == len(names)
    assert all(
        e["ph"] == "X" and e["dur"] >= 0 and e["ts"] >= 0
        for e in doc["traceEvents"]
    )
    # untraced batches carry no trace at rate 0
    res2 = eng.submit(Batch().get(5), sync=True).result()
    assert res2.trace is None
    eng.close()


def test_trace_sample_rate(tmp_path):
    from repro.db.ops import Batch
    from repro.db.store import RemixDB, RemixDBConfig

    db = RemixDB(
        RemixDBConfig(memtable_entries=1 << 30, trace_sample_rate=0.5)
    )
    _fill(db, n=50)  # the fill batch consumes the sampler's first pick
    traces = []
    for i in range(4):
        r = db.submit(Batch().get(7), sync=True).result()
        traces.append(r.trace)
    assert [t is not None for t in traces] == [False, True, False, True]
    assert traces[1].sampled  # sampled, not explicitly requested
    assert db.engine().last_trace is traces[3]
    db.close()


# --------------------------------------------------------------- events
def test_store_event_lifecycle(tmp_path):
    from repro.db.store import RemixDB, RemixDBConfig

    sink = tmp_path / "ev.jsonl"
    db = RemixDB.open(
        str(tmp_path / "db"),
        RemixDBConfig(memtable_entries=1 << 30,
                      event_log_path=str(sink)),
    )
    _fill(db)
    db.flush()
    kinds = [e.kind for e in db.events.list()]
    # one flush round, in causal order
    for a, b in (
        ("flush", "wal_gc"),
        ("wal_gc", "wal_checkpoint"),
        ("wal_checkpoint", "version_publish"),
        ("version_publish", "compaction"),
    ):
        assert kinds.index(a) < kinds.index(b), kinds
    flush_ev = db.events.list(kind="flush")[0]
    assert flush_ev.fields["entries"] == 300
    comp = db.events.list(kind="compaction")[0]
    assert comp.fields["kinds"] == {"minor": 1}
    assert comp.fields["bytes_written"] > 0
    db.close()
    lines = [json.loads(ln) for ln in sink.read_text().splitlines()]
    assert [ln["kind"] for ln in lines] == kinds
    # reopen: recovery emits its own event
    db2 = RemixDB.open(
        str(tmp_path / "db"), RemixDBConfig(memtable_entries=1 << 30)
    )
    assert [e.kind for e in db2.events.list()] == ["recover"]
    db2.close()


def test_executor_failure_event(tmp_path):
    from repro.db.ops import Batch, Op
    from repro.db.store import RemixDB, RemixDBConfig

    db = RemixDB(RemixDBConfig(memtable_entries=1 << 30))
    _fill(db, n=20)
    eng = db.engine()

    class Boom(Exception):
        pass

    orig = eng.plan
    eng.plan = lambda batch: (_ for _ in ()).throw(Boom("planner down"))
    try:
        res = db.submit(Batch().get(1), sync=True).result()
        # plan-level failure -> per-op ERROR results, not a dead future
        assert not res.ok
        with pytest.raises(Boom):
            res.results[0].raise_if_error()
    finally:
        eng.plan = orig
    errs = db.events.list(kind="batch_error")
    assert len(errs) == 1 and "Boom" in errs[0].fields["error"]
    assert eng.registry.counter("engine_batch_failures").value == 1
    db.close()


# ------------------------------------------------------------- CKB memo
def test_ckb_memo_bounded(tmp_path):
    from repro.db.store import RemixDB, RemixDBConfig

    # tiny cache budget -> tiny memo budget (capacity_bytes // 64)
    db = RemixDB.open(
        str(tmp_path / "db"),
        RemixDBConfig(memtable_entries=1 << 30, cache_bytes=16 << 10,
                      promote_fraction=1e9),
    )
    keys = _fill(db, n=4000, step=3)
    db.flush()
    db.close()
    db = RemixDB.open(
        str(tmp_path / "db"),
        RemixDBConfig(memtable_entries=1 << 30, cache_bytes=16 << 10,
                      promote_fraction=1e9),
    )
    rng = np.random.default_rng(3)
    for _ in range(6):
        qs = rng.choice(keys, 64, replace=False).astype(np.uint64)
        f, _ = db.get_batch(qs)
        assert f.all()
    budget = (16 << 10) // 64
    entries = db._ckb_memo("entries")
    assert 0 < entries <= budget + 64  # <= budget rounded up to one row
    assert db._ckb_memo("evictions") > 0
    snap = db.metrics()
    vals = {
        s["name"]: s["value"]
        for s in snap["metrics"]
        if s["name"].startswith("ckb_memo")
    }
    assert vals["ckb_memo_entries"] == entries
    assert vals["ckb_memo_evictions"] == db._ckb_memo("evictions")
    assert vals["ckb_memo_bytes"] > 0
    db.close()


def test_write_surface_counters_and_drop_event(tmp_path):
    """The new write-surface instruments: delete_range / cas_conflict /
    ttl_expired_dropped counters, plus the range_tombstone_drop event
    from a fold that retires whole tables."""
    from repro.db import clock
    from repro.db.compaction import CompactionConfig
    from repro.db.store import RemixDB, RemixDBConfig

    t = [1000.0]
    clock.set_source(lambda: t[0])
    db = RemixDB.open(
        str(tmp_path / "db"),
        RemixDBConfig(
            memtable_entries=128,
            compaction=CompactionConfig(table_cap=128, t_max=2),
            hot_threshold=255,
        ),
    )

    def counter(name):
        return sum(
            s["value"]
            for s in db.registry.snapshot()["metrics"]
            if s["name"] == name
        )

    try:
        keys = np.arange(0, 100, dtype=np.uint64)
        db.put_batch(
            keys, np.stack([keys, keys], 1).astype(np.uint32), ttl=30
        )
        db.flush()
        # two range deletes
        db.delete_range(10, 40)
        db.delete_range(50, 60)
        assert counter("delete_range") == 2
        # one CAS conflict, one success: only the conflict counts
        ok, _ = db.cas(5, np.array([9, 9], np.uint32),
                       np.array([1, 1], np.uint32))
        assert not ok
        ok, _ = db.cas(5, np.array([5, 5], np.uint32),
                       np.array([1, 1], np.uint32))
        assert ok
        assert counter("cas_conflict") == 1
        # whole-table drop: everything is covered by one range
        db.delete_range(0, 1000)
        db.flush()
        drops = db.events.list(kind="range_tombstone_drop")
        assert drops and drops[0].fields["tables"] >= 1
        assert counter("range_tombstone_drop") >= 1
        # expire TTL rows, churn a merge over them, and watch the GC
        t[0] = 1031.0
        for i in range(6):
            db.put_batch(
                keys, np.full((100, 2), i + 1, np.uint32), ttl=1
            )
            t[0] += 5.0
            db.flush()
        assert counter("ttl_expired_dropped") > 0
    finally:
        clock.reset()
        db.close()


# ------------------------------------------ durability counters & events
def test_scrub_counters_and_events(tmp_path):
    """The scrub/repair lifecycle lands in the registry and event log:
    a clean pass ticks scrub_passes/scrub_bytes_read only; an injected
    REMIX corruption adds corruption_detected + repair_remix_rebuilt and
    emits corruption -> repair -> scrub events in causal order."""
    import glob as _glob

    from repro.db.store import RemixDB, RemixDBConfig
    from repro.io.faults import flip_bytes

    db = RemixDB.open(
        str(tmp_path / "db"), RemixDBConfig(memtable_entries=1 << 30)
    )
    _fill(db)
    db.flush()
    rep = db.scrub(full=True)
    assert rep["clean"] and rep["bytes_read"] > 0
    c = lambda n: db.registry.counter(n).value
    assert c("scrub_passes") == 1
    assert c("scrub_bytes_read") == rep["bytes_read"]
    assert c("corruption_detected") == 0
    db.close()

    rx = sorted(_glob.glob(str(tmp_path / "db" / "remix" / "*.rmx")))
    flip_bytes(rx[0], 64, 4)
    db2 = RemixDB.open(
        str(tmp_path / "db"), RemixDBConfig(memtable_entries=1 << 30)
    )
    rep = db2.scrub(full=True)
    assert not rep["clean"] and rep["repaired"]
    c = lambda n: db2.registry.counter(n).value
    assert c("corruption_detected") >= 1
    assert c("repair_remix_rebuilt") == 1
    assert c("repair_table_quarantined") == 0
    kinds = [e.kind for e in db2.events.list()]
    assert kinds.index("corruption") < kinds.index("repair") \
        < kinds.index("scrub")
    ev = db2.events.list(kind="corruption")[-1]
    assert ev.fields["target"] == "remix"
    # the new names surface through metrics() for Prometheus rendering
    names = {s["name"] for s in db2.metrics()["metrics"]}
    assert {"scrub_passes", "scrub_bytes_read", "corruption_detected",
            "repair_remix_rebuilt", "repair_table_quarantined",
            "quarantine_purged", "io_retry", "io_giveup"} <= names
    assert db2.scrub(full=True)["clean"]
    db2.close()


# ----------------------------------------- tracing on the device read path
def _device_store(tmp_path, n=300):
    """A one-partition store served through its device view (the
    kernels interpreted on the CPU), with an empty MemTable."""
    from repro.db.store import RemixDB, RemixDBConfig

    db = RemixDB.open(
        str(tmp_path / "dev"),
        RemixDBConfig(memtable_entries=1 << 30, device_path="on",
                      cold_reads=False),
    )
    keys = _fill(db, n=n)
    db.flush()
    assert len(db.partitions) == 1 and not len(db.mem)
    return db, keys


def _children(span):
    return [c.name for c in span.children]


@pytest.mark.parametrize("kind", ["get", "scan"])
def test_device_read_span_tree(tmp_path, kind):
    from repro.db.ops import Batch

    db, keys = _device_store(tmp_path)

    def batch(i):
        k = int(keys[(37 * i) % 200])
        b = Batch(trace=True)
        return b.get(k) if kind == "get" else b.scan(k, 20)

    for i in range(3):  # compiles every shape the batches use
        assert db.submit(batch(i)).result().ok
    traces = []
    for i in range(7):
        res = db.submit(batch(i)).result()
        assert res.ok
        traces.append(res.trace)
    for tr in traces:
        assert tr.well_formed()
        assert _children(tr.root) == [
            "admission", "queue", "plan", "stage0:read", "finish"]
        (read,) = tr.find("shard0:read")
        if kind == "get":
            assert _children(read) == [
                "pin", "overlay_probe", "route", "launch", "device_wait",
                "unpack"]
        else:
            assert _children(read) == [
                "scan_args", "pin", "route", "cursor", "scan_results"]
            (cursor,) = tr.find("cursor")
            assert _children(cursor) == ["cursor_seek", "cursor_window"]
            seek, window = cursor.children
            assert _children(seek) == [
                "overlay_sort", "route", "route", "launch", "device_wait",
                "unpack"]
            # the seek and the first window are one call and one fetch:
            # the window pulled at open only merges
            assert _children(window) == ["merge"]
        # the submitting thread and the worker each get their own row
        rows = {e["name"]: e["tid"] for e in tr.to_chrome()["traceEvents"]}
        assert rows["batch"] == rows["admission"] == 1
        assert rows["shard0:read"] == rows["finish"] == 2
    # at most 10% of the read is unnamed, in the median request (one
    # request may meet a collector pause or a preempted thread)
    reads = [tr.find("shard0:read")[0] for tr in traces]
    unnamed = sorted(r.self_time() / r.duration for r in reads)
    assert unnamed[len(unnamed) // 2] <= 0.10, unnamed
    db.close()


def test_live_spans_on_the_profiler_clock(tmp_path):
    """Every live span of a traced batch is a host event of the same
    name in a ``jax.profiler`` trace, on the recording thread's line,
    nested as in the Trace and as long within 10% or 50 us."""
    import glob

    import jax
    from jax.profiler import ProfileData

    from repro.db.ops import Batch

    db, keys = _device_store(tmp_path)
    b = lambda: Batch(trace=True).get(int(keys[5])).scan(int(keys[9]), 8)
    assert db.submit(b()).result().ok
    prof = str(tmp_path / "prof")
    jax.profiler.start_trace(prof)
    try:
        res = db.submit(b()).result()
    finally:
        jax.profiler.stop_trace()
    assert res.ok
    db.close()
    (path,) = glob.glob(f"{prof}/plugins/profile/*/*.xplane.pb")
    (host,) = [p for p in ProfileData.from_file(path).planes
               if p.name == "/host:CPU"]
    live = [s for s in res.trace.spans()[1:] if s.name != "queue"]
    names = {s.name for s in live}
    events: dict[str, list] = {}
    for li, line in enumerate(host.lines):
        for e in line.events:
            if e.name in names:
                events.setdefault(e.name, []).append(
                    (e.start_ns, e.duration_ns, li))
    ev_of = {}
    for name in names:
        spans = sorted((s for s in live if s.name == name),
                       key=lambda s: s.t0)
        evs = sorted(events.get(name, []))
        assert len(evs) == len(spans), name
        ev_of.update({id(s): e for s, e in zip(spans, evs)})
    for s in live:
        t0, dur, line = ev_of[id(s)]
        assert abs(dur / 1e9 - s.duration) <= max(0.1 * s.duration, 50e-6)
        for c in s.children:
            if id(c) in ev_of:
                c0, cd, cl = ev_of[id(c)]
                assert cl == line and t0 <= c0 and c0 + cd <= t0 + dur


def test_untraced_batch_constructs_no_annotation(tmp_path, monkeypatch):
    from repro.db.ops import Batch
    from repro.obs import tracing

    class Refused:
        def __init__(self, *a, **kw):
            raise AssertionError("annotation built on the untraced path")

    db, keys = _device_store(tmp_path)
    monkeypatch.setattr(tracing, "_Annotation", Refused)
    for b in (Batch().get(int(keys[3])), Batch().scan(int(keys[4]), 9),
              Batch().scan(int(keys[4]), 9).scan(int(keys[40]), 9),
              Batch().put(11, [1, 2])):
        res = db.submit(b).result()
        assert res.ok and res.trace is None
    with pytest.raises(AssertionError, match="untraced path"):
        with Trace().span("x"):
            pass
    db.close()


def test_device_boundary_counters(tmp_path):
    from repro.db.ops import Batch

    db, keys = _device_store(tmp_path)
    names = ("device_launches", "device_syncs", "device_batches",
             "cursor_seeks", "cursor_windows")
    fb = {r: db.registry.counter("scan_cursor_fallbacks", reason=r)
          for r in ("lone", "overlay", "underfull")}

    def delta(b):
        before = [db.registry.counter(n).value for n in names]
        f0 = {r: c.value for r, c in fb.items()}
        assert db.submit(b).result().ok
        out = {n: db.registry.counter(n).value - v
               for n, v in zip(names, before)}
        out.update({r: c.value - f0[r] for r, c in fb.items()})
        return {k: v for k, v in out.items() if v}

    k = [int(x) for x in keys]
    # a lone get: one fused launch, one sync
    assert delta(Batch().get(k[3])) == {
        "device_launches": 1, "device_syncs": 1, "device_batches": 1}
    # a lone scan: the cursor's seek fused with its one window, one
    # launch and one fetch
    assert delta(Batch().scan(k[3], 9)) == {
        "device_launches": 1, "device_syncs": 1, "cursor_seeks": 1,
        "cursor_windows": 1, "lone": 1}
    # a batched scan: one fused window launch and its sync
    assert delta(Batch().scan(k[3], 9).scan(k[50], 9)) == {
        "device_launches": 1, "device_syncs": 1, "device_batches": 1}
    # a window past the last key is under-full: the cursor answers it,
    # widening its windows to the end of the view
    d = delta(Batch().scan(k[3], 9).scan(k[-2], 9))
    assert (d["underfull"], d["cursor_seeks"], d["device_batches"]) \
        == (1, 1, 1)
    assert d["device_launches"] == 1 + d["cursor_windows"]
    assert d["device_syncs"] == 1 + d["cursor_windows"]
    # a non-empty MemTable overlay: still one fused window launch and
    # its sync, the overlay merged into the windows on the host
    db.put(5, [1, 2])
    assert delta(Batch().scan(k[3], 9).scan(k[50], 9)) == {
        "device_launches": 1, "device_syncs": 1, "device_batches": 1}
    # an unflushed range tombstone: every scan of the group by cursor
    db.delete_range(1, 2)
    assert delta(Batch().scan(k[3], 9).scan(k[50], 9)) == {
        "device_launches": 2, "device_syncs": 2, "cursor_seeks": 2,
        "cursor_windows": 2, "overlay": 2}
    db.close()


def test_kernel_stages_in_lowered_get_live():
    import jax
    import jax.numpy as jnp

    from repro.core.remix import Remix
    from repro.core.runs import RunSet
    from repro.kernels import ops

    d, r, n, g, vw, kw, q = 8, 2, 64, 16, 2, 2, 8
    s = jax.ShapeDtypeStruct
    remix = Remix(anchors=s((g, kw), jnp.uint32),
                  cursors=s((g, r), jnp.int32),
                  selectors=s((g * d,), jnp.uint8),
                  n_entries=s((), jnp.int32), d=d)
    runset = RunSet(keys=s((r, n, kw), jnp.uint32),
                    vals=s((r, n, vw), jnp.uint32),
                    seq=s((r, n), jnp.uint32), tomb=s((r, n), jnp.bool_),
                    lens=s((r,), jnp.int32))
    lowered = ops.get_live.lower(
        remix, runset, s((r, n), jnp.uint32), s((q, kw), jnp.uint32),
        s((), jnp.uint32), interpret=True,
    )
    # the result is one packed uint32 buffer of 1 + VW + 2 words a query
    out = lowered.out_info
    assert (out.shape, out.dtype) == ((q * (1 + vw + 2),), jnp.uint32)
    text = lowered.as_text(debug_info=True)
    for scope in ("remix_seek", "remix_gather", "liveness"):
        assert scope in text, scope


# -------------------------------- the benchmark's readers of these spans
def _reader(name):
    import importlib.util
    import pathlib
    import sys

    root = pathlib.Path(__file__).resolve().parents[1]
    if str(root) not in sys.path:
        sys.path.insert(0, str(root))
    spec = importlib.util.spec_from_file_location(
        f"reader_{name}", root / "bench" / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _ctx(instrumented: bool):
    """Three kept reads (one a MemTable hit with no device work), one
    read the harness did not keep, and two inserts, one of which synced;
    each span of ``t`` ms."""
    from types import SimpleNamespace as NS

    def done(is_write, spans):
        tr = None
        if spans is not None:
            tr = Trace()
            for name, ms in spans:
                tr.leaf(name, 0.0, ms / 1e3)
        return NS(req=NS(is_write=is_write),
                  result=None if spans is None else NS(trace=tr))

    if instrumented:
        reads = [[("launch", 1.0), ("device_wait", 2.0)],
                 [("launch", 0.5), ("launch", 0.5), ("device_wait", 1.0),
                  ("device_wait", 3.0)],
                 [("pin", 0.1)]]
        writes = [[("wal_append", 0.2), ("wal_sync", 1.2)],
                  [("wal_append", 0.1)]]
        counters = {"device_syncs": 9}
    else:
        reads = [[("shard0:read", 2.0)]] * 3
        writes = [[("shard0:commit", 0.3)]] * 2
        counters = {"device_batches": 2}
    done = ([done(False, s) for s in reads] + [done(False, None)]
            + [done(True, s) for s in writes])
    return NS(done=done, counters=counters)


@pytest.mark.parametrize("name, want", [
    ("launch_ms", 2.0 / 3),
    ("device_wait_ms", 6.0 / 3),
    ("host_syncs_per_read_op", 9 / 4),
    ("wal_sync_ms", 1.2 / 2),
])
def test_span_metric_readers(name, want):
    read = _reader(name)
    assert read(_ctx(True)) == pytest.approx(want)
    # a program without the spans and the counter reads nothing
    assert read(_ctx(False)) is None
