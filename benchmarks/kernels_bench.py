"""Kernel microbenchmarks + the fused device-resident query pipeline.

Two layers:

- **micro**: Pallas kernels (interpreted on CPU, compiled on TPU) vs the
  pure-jnp reference
  — anchor search, the fused seek composition, and the REMIX build
  throughput (compaction-side cost the WA accounting charges).
- **device pipeline**: a promoted single-partition store answers a
  256-key batch through the persistent device view
  (``device_path="on"``): seek → selector decode → run/position resolve
  → gather, all device-side, with **exactly one host sync per batch**
  (asserted via ``repro.kernels.device_view.SYNCS``) and bit-identical
  results to the legacy host promoted path (asserted). On a real
  accelerator backend the fused pipeline must beat the host vectorized
  path **>= 5x** at batch 256; on CPU (interpret mode — what CI runs)
  the speedup is reported but not asserted.

Also emits ``BENCH_kernels.json`` — the device-pipeline perf trajectory
file CI's kernels-smoke job keeps populated from a tiny store.

Run directly (``python -m benchmarks.kernels_bench [--tiny] [--json PATH]``)
or via ``python -m benchmarks.run --only kernels``.
"""
from __future__ import annotations

import argparse
import json
import os
import tempfile
import time

import numpy as np

import jax

from benchmarks.cache_bench import build_store
from benchmarks.common import CSV, make_tables, qkeys, time_batched
from repro.core.remix import build_remix
from repro.db.store import RemixDB, RemixDBConfig
from repro.kernels import device_view, ops
from repro.kernels.anchor_search import anchor_search
from repro.kernels.ref import anchor_search_ref

MIN_DEVICE_SPEEDUP = 5.0  # acceptance bar at batch 256, real devices only
BATCH = 256
ITERS = 5

# full-size store (default) vs CI smoke store (--tiny)
SIZES = dict(full=(8, 1 << 16), tiny=(4, 1 << 12))


def bench_micro(csv: CSV) -> None:
    rng = np.random.default_rng(3)
    runs, keys = make_tables(8, 16384, locality="weak")
    t0 = time.perf_counter()
    remix, runset = build_remix(runs, d=32)
    csv.emit("kernels_remix_build", (time.perf_counter() - t0) * 1e6,
             f"{8*16384} entries")
    qk = qkeys(rng, int(keys[-1]), 1024)
    interp = ops.interpret_for_backend()
    mode = f"interpret={interp}"
    t = time_batched(
        lambda q: anchor_search(remix.anchors, q, interpret=interp), qk
    )
    csv.emit("kernels_anchor_search_pallas", t / 1024 * 1e6, mode)
    t = time_batched(lambda q: anchor_search_ref(remix.anchors, q), qk)
    csv.emit("kernels_anchor_search_ref", t / 1024 * 1e6, "")
    t = time_batched(lambda q: ops.seek(remix, runset, q, interpret=interp),
                     qk)
    csv.emit("kernels_seek_fused", t / 1024 * 1e6, mode)


def _probe(domain: np.ndarray, rng, q: int) -> np.ndarray:
    hits = rng.choice(domain, q - q // 8, replace=False).astype(np.uint64)
    miss = rng.choice(domain, q // 8, replace=False).astype(np.uint64) + 1
    out = np.concatenate([hits, miss])
    rng.shuffle(out)
    return out


def _time_batches(db, probe) -> float:
    db.get_batch(probe)  # warm: upload / jit compile / cache fill
    t0 = time.perf_counter()
    for _ in range(ITERS):
        db.get_batch(probe)
    return (time.perf_counter() - t0) / ITERS


def bench_device_pipeline(root: str, domain: np.ndarray, csv: CSV) -> dict:
    """Fused promoted-get pipeline: sync-count contract, host parity,
    and device-vs-host throughput at batch 256."""
    rng = np.random.default_rng(11)
    probe = _probe(domain, rng, BATCH)
    db_h = RemixDB.open(root, RemixDBConfig(cold_reads=False,
                                            device_path="off"))
    db_d = RemixDB.open(root, RemixDBConfig(cold_reads=False,
                                            device_path="on"))

    f_h, v_h = db_h.get_batch(probe)
    f_d, v_d = db_d.get_batch(probe)  # also uploads the device view
    assert np.array_equal(f_h, f_d), "device/host found-mask mismatch"
    assert np.array_equal(v_h[f_h], v_d[f_d]), "device/host value mismatch"
    assert len(db_d.device_views) == 1  # single-partition store, resident

    s0 = device_view.SYNCS
    for _ in range(ITERS):
        db_d.get_batch(probe)
    syncs = (device_view.SYNCS - s0) / ITERS
    assert syncs == 1.0, (
        f"fused batch-{BATCH} get paid {syncs} host syncs per batch, want 1"
    )

    host_s = _time_batches(db_h, probe)
    dev_s = _time_batches(db_d, probe)
    speedup = host_s / dev_s
    backend = jax.default_backend()
    if backend not in ("cpu",):
        assert speedup >= MIN_DEVICE_SPEEDUP, (
            f"device pipeline {speedup:.1f}x < {MIN_DEVICE_SPEEDUP}x "
            f"on {backend}"
        )
    csv.emit("kernels_device_get_batch256", dev_s / BATCH * 1e6,
             f"syncs_per_batch=1;backend={backend}")
    csv.emit("kernels_host_get_batch256", host_s / BATCH * 1e6, "")
    csv.emit("kernels_device_speedup", 0.0, f"{speedup:.2f}x")

    # scan windows through the same fused path
    starts = np.sort(rng.choice(domain[:-200], 64, replace=False))
    db_h.scan_batch(starts, 16), db_d.scan_batch(starts, 16)
    t0 = time.perf_counter()
    for _ in range(ITERS):
        db_d.scan_batch(starts, 16)
    dscan = (time.perf_counter() - t0) / ITERS
    t0 = time.perf_counter()
    for _ in range(ITERS):
        db_h.scan_batch(starts, 16)
    hscan = (time.perf_counter() - t0) / ITERS
    csv.emit("kernels_device_scan64x16", dscan / 64 * 1e6, "")
    csv.emit("kernels_host_scan64x16", hscan / 64 * 1e6, "")

    out = dict(
        backend=backend,
        batch=BATCH,
        syncs_per_batch=syncs,
        device_get_us_per_key=round(dev_s / BATCH * 1e6, 3),
        host_get_us_per_key=round(host_s / BATCH * 1e6, 3),
        get_speedup=round(speedup, 2),
        device_scan_us_per_query=round(dscan / 64 * 1e6, 2),
        host_scan_us_per_query=round(hscan / 64 * 1e6, 2),
        hbm_resident_bytes=int(db_d.device_views.resident_bytes),
    )
    db_h.close(), db_d.close()
    return out


def run(csv: CSV, tiny: bool = False, json_path: str | None = None) -> None:
    bench_micro(csv)
    r_tables, n_per_table = SIZES["tiny" if tiny else "full"]
    with tempfile.TemporaryDirectory(prefix="kernels-bench-") as tmp:
        root = os.path.join(tmp, "db")
        domain = build_store(
            root, r_tables=r_tables, n_per_table=n_per_table
        )
        pipeline = bench_device_pipeline(root, domain, csv)
    out = json_path or os.environ.get(
        "BENCH_KERNELS_JSON", os.path.join("results", "BENCH_kernels.json")
    )
    os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
    with open(out, "w") as f:
        json.dump(
            dict(
                bench="kernels",
                unix_time=int(time.time()),
                store=dict(r_tables=r_tables, n_per_table=n_per_table),
                pipeline=pipeline,
            ),
            f,
            indent=2,
        )
        f.write("\n")
    print(f"# wrote {out}")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke store (4 tables x 4096 entries)")
    ap.add_argument("--json", default=None, help="BENCH_kernels.json path")
    args = ap.parse_args()
    c = CSV()
    print("name,us_per_call,derived")
    run(c, tiny=args.tiny, json_path=args.json)
