"""chip_smoke.py at a tiny size on the CPU, with the kernels interpreted:
the same load → reopen → promote → mixed submit() traffic → reference
parity and counter checks the chip run makes, plus its refusal to run
anywhere but on a TPU."""
import os
import subprocess
import sys

import numpy as np

from repro.db.compaction import CompactionConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


def test_smoke_body_matches_reference_on_cpu():
    out = chip_smoke.run_smoke(
        4096, seed=3, rounds=4, min_resident=1, memtable_entries=512,
        compaction=CompactionConfig(table_cap=256),
    )
    assert out["partitions"] > 1 and out["max_runs"] > 1
    assert out["interpret"] is True
    assert out["device_batches"] > 0
    assert out["device_fallback_total"] == 0
    assert out["hbm_resident_bytes"] > 0
    assert out["upload_tiers"] == ["full"]
    # promotion batches + 4 rounds of (get, scan batch, lone scan) + writes
    assert out["batches"] >= out["promote_batches"] + 3 * 4 + 4


def test_smoke_reference_scan_merges_overwrites():
    keys = np.array([10, 20, 30, 40], np.uint64)
    vals = np.arange(16, dtype=np.uint32).reshape(4, 4)
    ref = chip_smoke.Reference(keys, vals)
    ref.over[20] = None
    ref.over[25] = np.full(4, 7, np.uint32)
    k, v = ref.scan(15, 3)
    assert k.tolist() == [25, 30, 40]
    assert v[0].tolist() == [7] * 4 and v[1].tolist() == vals[2].tolist()
    f, g = ref.get(np.array([20, 25, 40, 41], np.uint64))
    assert f.tolist() == [False, True, True, False]
    assert g[2].tolist() == vals[3].tolist()


def test_main_refuses_cpu_backend(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py"), "--keys", "4096"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert "refused" in proc.stdout
    assert '"ok"' not in proc.stdout
    assert "smoke load" not in proc.stdout
