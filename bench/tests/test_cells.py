"""Each cell's whole run at a tiny size on the CPU (kernels interpreted),
its refusal to run anywhere but on a TPU, the control of each cell's
comparison, and the faults the comparison has to catch."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import harness
from bench.control import CONTROLS
from bench.harness import ROOT, load_cell, run_cell

SEED = 2**31 + 11
SHORT, HEAVY = "alex-ycsb.short-range", "alex-ycsb.read-heavy"
CELLS = [SHORT, HEAVY]
TINY = {"records": 4096, "store.memtable_entries": 512,
        "store.table_cap": 256, "mix.new_keys": 256}


def tiny(name, insert_share=None):
    ov = dict(TINY)
    reqs = load_cell(name)["mix"]["requests"]
    if name == SHORT:
        # short scans keep the cursor's compiled widths few on the CPU
        reqs[0]["length"] = {"uniform": [1, 6]}
    if insert_share is not None:
        reqs[1]["share"] = insert_share
    ov["mix.requests"] = reqs
    return ov


def run(name, trace=False, seed=SEED, insert_share=None, **kw):
    return run_cell(name, seed, 1.5, trace,
                    overrides=tiny(name, insert_share), **kw)


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("trace", [False, True])
def test_cell_runs_correct_with_its_metrics(name, trace):
    cell = load_cell(name)
    out = run(name, trace)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    assert out["checked_ops"] > 0
    assert list(out)[-1] == "checks"
    assert out["device"]["platform"] == "cpu"
    want = cell["per_layer"] if trace else cell["end_to_end"]
    got = set(out["metrics"])
    # device-trace metrics need a device plane, which the CPU has not
    host = {m["name"] for m in want if m["source"] != "device_trace"}
    assert host <= got <= {m["name"] for m in want}
    for m in out["metrics"].values():
        assert m["value"] > 0


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("seed", [3, 4, 5])
def test_control_fails_the_comparison(name, seed):
    control = CONTROLS[load_cell(name)["mix"]["control"]]
    out = run(name, seed=seed, insert_share=0.5, control=control)
    assert out["correct"]  # the program itself compares clean
    assert out["control"]["checked"] == out["checked_ops"]
    assert out["control"]["wrong"] > 0


def test_fault_lookup_answer_altered_where_produced(monkeypatch):
    """The fused device lookup returns one value word changed."""
    from repro.kernels.device_view import DeviceViewManager

    orig = DeviceViewManager.get_batch

    def bad(self, *a, **kw):
        found, vals = orig(self, *a, **kw)
        vals = vals.copy()
        vals[:, 0] ^= 1
        return found, vals

    monkeypatch.setattr(DeviceViewManager, "get_batch", bad)
    out = run(HEAVY)
    assert not out["correct"] and out["checks"]["wrong_answers"]["value"]


def test_fault_scan_answer_altered_where_produced(monkeypatch):
    """The cursor's batched next returns one value word changed."""
    from repro.db.cursor import RemixCursor

    orig = RemixCursor.next_batch

    def bad(self, n):
        k, v = orig(self, n)
        if len(v):
            v = v.copy()
            v[-1, -1] ^= 1
        return k, v

    monkeypatch.setattr(RemixCursor, "next_batch", bad)
    out = run(SHORT)
    assert not out["correct"] and out["checks"]["wrong_answers"]["value"]


def test_fault_half_of_each_scan_left_out(monkeypatch):
    """A scan of n keys returns only its first half."""
    from repro.db.cursor import RemixCursor

    orig = RemixCursor.next_batch

    def bad(self, n):
        k, v = orig(self, n)
        h = (len(k) + 1) // 2
        return k[:h], v[:h]

    monkeypatch.setattr(RemixCursor, "next_batch", bad)
    out = run(SHORT)
    assert not out["correct"] and out["checks"]["wrong_answers"]["value"]


@pytest.mark.parametrize("name", CELLS)
def test_fault_write_leaves_the_state_unchanged(monkeypatch, name):
    """Inserts are acknowledged but never applied."""
    orig = harness.open_engine

    def opened(*a, **kw):
        eng = orig(*a, **kw)
        eng.shards[0]._apply_writes = lambda *a, **kw: None
        return eng

    monkeypatch.setattr(harness, "open_engine", opened)
    out = run(name, insert_share=0.5)
    assert not out["correct"] and out["checks"]["wrong_answers"]["value"]


@pytest.mark.parametrize("name", CELLS)
def test_run_refuses_a_cpu_backend(tmp_path, name):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload", name,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "platform=cpu" in proc.stderr and "refused" in proc.stderr
    assert proc.stdout.strip() == ""


def test_run_refuses_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", SHORT, "--seed",
         "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0 and proc.stdout.strip() == ""


def test_benchmark_json_names_a_reader_for_every_metric():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").exists()
    for w in spec["workloads"]:
        cell = load_cell(w["name"])
        assert cell["mix"]["control"] in CONTROLS
        assert {"setup_s"} < {m["name"] for m in cell["end_to_end"]}
        assert cell["per_layer"]
    assert np.all([c["file"].startswith("bench/") for c in spec["configs"]])
