"""The trace reduction, on synthetic events and on a small trace recorded
on one v5e chip (``data/trace_small.json``: 6 ms of the device planes of
a run that sent batches of 64 Seek+Next50 scans through the fused device
scan, op names cut at their layout)."""
import json
from pathlib import Path

import pytest

from bench.trace_reduce import module_time, reduce_events, union_s

DEV = "/device:TPU:0"
HOST = "/host:CPU"


def ev(plane, line, name, t0_us, dur_us):
    return (plane, line, name, t0_us * 1e3, dur_us * 1e3)


def test_busy_is_the_union_and_gaps_are_named_by_the_host():
    events = [
        ev(DEV, "XLA Ops", "fusion.1", 0, 10),
        ev(DEV, "XLA Ops", "fusion.2", 5, 10),  # overlaps: busy 0-15
        ev(DEV, "XLA Ops", "custom-call", 40, 10),  # busy 40-50
        ev(DEV, "XLA Modules", "jit_scan_live(123)", 0, 15),
        ev(DEV, "XLA Modules", "jit_scan_live(123)", 40, 10),
        ev(DEV, "XLA Modules", "jit_get_live(7)", 60, 5),
        ev(DEV, "XLA Ops", "fusion.3", 60, 5),
        ev(HOST, "python", "bench.request:scan", 0, 100),
        ev(HOST, "python", "bench.submit", 16, 20),  # covers gap 15-40
    ]
    out = reduce_events(events, window_s=100e-6)
    assert out["busy_s"] == pytest.approx(30e-6)
    assert out["window_s"] == 100e-6
    assert module_time(out, "scan_live") == (pytest.approx(25e-6), 2)
    assert module_time(out, "get_live") == (pytest.approx(5e-6), 1)
    gaps = dict((n, s) for n, s in out["idle_gaps"])
    assert gaps["bench.submit"] == pytest.approx(25e-6)
    assert gaps["bench.request:scan"] == pytest.approx(10e-6)
    assert out["device_ops"][0] == ["fusion.1", pytest.approx(10e-6)]


def test_no_device_events_reads_as_nothing():
    assert reduce_events([ev(HOST, "python", "x", 0, 5)], 1.0) is None


def test_union_of_nested_and_disjoint_intervals():
    total, merged = union_s([(0, 10), (2, 3), (20, 30), (25, 40)])
    assert total == pytest.approx(30e-9)
    assert merged == [[0, 10], [20, 40]]


RECORDED = Path(__file__).parent / "data" / "trace_small.json"


def test_recorded_chip_trace():
    events = [tuple(e) for e in json.loads(RECORDED.read_text())]
    dev = [e for e in events if e[0].startswith("/device")]
    t0 = min(e[3] for e in dev)
    t1 = max(e[3] + e[4] for e in dev)
    out = reduce_events(events, (t1 - t0) / 1e9)
    assert out["devices"] == 1
    assert 0 < out["busy_s"] <= out["window_s"]
    ops = [(e[3], e[3] + e[4]) for e in dev if e[1] == "XLA Ops"]
    assert out["busy_s"] == pytest.approx(union_s(ops)[0])
    s, calls = module_time(out, "scan_live")
    assert calls == 2 and 0 < s <= out["window_s"]
    assert out["device_ops"][0][0].startswith("%copy")
