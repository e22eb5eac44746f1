"""The whole run of ``alex-ycsb.short-range-32c`` at the tiny size
``test_cells.py`` uses, with 8 clients, on the CPU (kernels interpreted):
its answers compare clean, its new per-layer metrics read above 0, and
its control fails the comparison."""
import pytest

from bench.control import CONTROLS
from bench.harness import load_cell, run_cell

CELL = "alex-ycsb.short-range-32c"
SEED = 2**31 + 11
TINY = {"records": 4096, "store.memtable_entries": 512,
        "store.table_cap": 256, "mix.new_keys": 256, "mix.clients": 8}


def run(trace=False, seed=SEED, insert_share=None, **kw):
    ov = dict(TINY)
    reqs = load_cell(CELL)["mix"]["requests"]
    # short scans keep the compiled widths few on the CPU
    reqs[0]["length"] = {"uniform": [1, 6]}
    if insert_share is not None:
        reqs[1]["share"] = insert_share
    ov["mix.requests"] = reqs
    return run_cell(CELL, seed, 1.5, trace, overrides=ov, **kw)


def test_cell_reports_its_metrics():
    cell = load_cell(CELL)
    assert cell["workload"]["config"] == cell["cfg"]["name"] == "ycsb-e-32c"
    assert cell["mix"]["clients"] == cell["cfg"]["client_threads"] == 32
    assert {m["name"] for m in cell["end_to_end"]} == {
        "ops_per_s", "read_p95_ms", "setup_s"}
    assert {m["name"] for m in cell["per_layer"]} == {
        "requests_per_group", "syncs_per_scan_op", "overlay_merge_ms",
        "scan_live_roofline"}


@pytest.mark.parametrize("trace", [False, True])
def test_cell_runs_correct_with_its_metrics(trace):
    out = run(trace)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    assert out["checked_ops"] > 0
    assert out["device"]["platform"] == "cpu"
    got = out["metrics"]
    if trace:
        # the device trace's roofline needs a device plane, which the
        # CPU has not; the program's counters and spans are all there
        assert set(got) == {"requests_per_group", "syncs_per_scan_op",
                            "overlay_merge_ms"}
        assert got["requests_per_group"]["value"] >= 2
        assert got["syncs_per_scan_op"]["value"] < 1
    else:
        assert set(got) == {"ops_per_s", "read_p95_ms", "setup_s"}
    for m in got.values():
        assert m["value"] > 0


@pytest.mark.parametrize("seed", [3, 4])
def test_control_fails_the_comparison(seed):
    control = CONTROLS[load_cell(CELL)["mix"]["control"]]
    out = run(seed=seed, insert_share=0.5, control=control)
    assert out["correct"]  # the program itself compares clean
    assert out["control"]["checked"] == out["checked_ops"]
    assert out["control"]["wrong"] > 0
