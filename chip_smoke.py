"""Smoke run of the served RemixDB read path on one TPU chip.

Builds a persistent store from a seed (64-bit keys, 4-word values, the
widths of ``repro.configs.remixdb``), loading it through ``put_batch`` and
``flush`` under the default compaction policy, so it holds several
partitions with multi-run REMIXes. It then reopens the store behind a
one-shard ``KVServeEngine``, drives reads through ``submit()`` until
every partition's view is resident in HBM, and runs a few hundred mixed
batches through ``submit()``:

- batch-256 multi-gets, about 1/8 of them misses;
- batch-64 Seek+Next50 scans;
- lone Seek+Next50 scans, which take the streaming cursor path;
- puts and deletes, each group followed by a flush.

Every answer is compared with a plain numpy reference built from the same
seed, and every op must come back ``OK``. The store's own counters must
show device batches, no fallback off the device, at least 512 MiB of
resident views, kernels compiled (not interpreted), and exactly one host
sync per partition of each get batch.

    python chip_smoke.py [--keys N] [--seed S] [--rounds R]

It needs a TPU: on any other backend it exits non-zero before loading
anything and prints no result line. The times it prints come from one
smoke pass and are not benchmark numbers. Its last line of standard
output is ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

VW = 4  # value words (repro.configs.remixdb)
FULL_KEYS = 1 << 24  # the store size this smoke run stands for
# 2^24 keys took 773 s to load on a v5e host (about 46 us per key of WAL,
# MemTable and compaction work) and about 950 s in all; 3 * 2^22 keeps
# the run near 700 s, well inside its 20-minute bound
DEFAULT_KEYS = 3 << 22
MIN_RESIDENT = 512 << 20
GET_BATCH = 256
SCAN_BATCH = 64
SCAN_N = 50
WRITE_GROUPS = 4  # put/delete + flush groups spread over the rounds


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


class Reference:
    """The plain reference: the loaded keys as one sorted array (values
    aligned) plus a dict of later overwrites and deletes (None)."""

    def __init__(self, keys: np.ndarray, vals: np.ndarray):
        order = np.argsort(keys)
        self.keys, self.vals = keys[order], vals[order]
        self.over: dict[int, np.ndarray | None] = {}

    def get(self, q: np.ndarray):
        i = np.minimum(np.searchsorted(self.keys, q), len(self.keys) - 1)
        found = self.keys[i] == q
        vals = np.where(found[:, None], self.vals[i], 0).astype(np.uint32)
        for j, k in enumerate(q.tolist()):
            if k in self.over:
                v = self.over[k]
                found[j] = v is not None
                vals[j] = 0 if v is None else v
        return found, vals

    def scan(self, start: int, n: int):
        i0 = int(np.searchsorted(self.keys, np.uint64(start)))
        # n + len(over) base rows always hold the first n survivors
        i1 = i0 + n + len(self.over)
        rows = {
            k: v for k, v in zip(self.keys[i0:i1].tolist(), self.vals[i0:i1])
            if k not in self.over
        }
        rows.update({k: v for k, v in self.over.items()
                     if k >= start and v is not None})
        ks = sorted(rows)[:n]
        return (np.array(ks, np.uint64),
                np.array([rows[k] for k in ks], np.uint32).reshape(-1, VW))


def make_data(n: int, seed: int):
    """``n`` distinct random 64-bit keys in load order, random values."""
    rng = np.random.default_rng(seed)
    keys = np.unique(rng.integers(1, 1 << 63, size=n + n // 64 + 16,
                                  dtype=np.uint64))
    keys = rng.permutation(keys)[:n]
    check(len(keys) == n, "key generator produced too few distinct keys")
    vals = rng.integers(0, 1 << 32, size=(n, VW), dtype=np.uint32)
    return keys, vals


def _metric(db, name: str):
    return sum(s["value"] for s in db.registry.snapshot()["metrics"]
               if s["name"] == name)


def run_smoke(n_keys: int, *, seed: int = 0, rounds: int = 64,
              min_resident: int = MIN_RESIDENT, memtable_entries=None,
              compaction=None) -> dict:
    """Load, reopen, promote and serve; raises AssertionError on any
    wrong answer, non-OK op or counter outside its bound. Returns the
    measured counters and smoke timings."""
    import jax

    import repro.kernels.device_view as device_view
    from repro.db.compaction import CompactionConfig
    from repro.db.ops import Batch, Op, OpStatus
    from repro.db.sharded import route_host
    from repro.db.store import RemixDB, RemixDBConfig
    from repro.serve.engine import KVServeEngine

    cfg = RemixDBConfig(vw=VW, compaction=compaction or CompactionConfig())
    if memtable_entries is not None:
        cfg = dataclasses.replace(cfg, memtable_entries=memtable_entries)
    rng = np.random.default_rng(seed + 1)
    keys, vals = make_data(n_keys, seed)
    ref = Reference(keys, vals)
    out: dict = dict(keys=n_keys)

    with tempfile.TemporaryDirectory(prefix="remixdb-smoke-") as root:
        # ---- load: put_batch fills the MemTable, which flushes itself
        t0 = time.perf_counter()
        db = RemixDB.open(root, dataclasses.replace(cfg, device_path="off"))
        step = cfg.memtable_entries
        for i in range(0, n_keys, step):
            db.put_batch(keys[i:i + step], vals[i:i + step])
        db.flush()
        parts = db.partitions
        need = sum(p.device_view_bytes(with_vals=True) for p in parts)
        out.update(partitions=len(parts),
                   max_runs=max(len(p.tables) for p in parts),
                   tables=sum(len(p.tables) for p in parts))
        db.close()
        del db, parts
        gc.collect()  # drop the load store's device arrays
        out["load_s"] = time.perf_counter() - t0
        log(f"smoke load: {n_keys} keys in {out['load_s']:.1f} s -> "
            f"{out['partitions']} partitions, {out['tables']} tables, "
            f"up to {out['max_runs']} runs per partition")
        check(out["partitions"] > 1 and out["max_runs"] > 1,
              "load built no multi-run, multi-partition store")

        # ---- reopen behind the serving engine; room for every view
        # twice over, so views rebuilt after a flush fit beside old ones
        budget = 2 * need
        limit = (jax.devices()[0].memory_stats() or {}).get("bytes_limit")
        check(limit is None or budget <= 0.75 * limit,
              f"views need {need} B; the device holds {limit} B")
        serve_cfg = dataclasses.replace(
            cfg, device_path="on", cold_reads=False,
            device_budget_bytes=budget,
        )
        eng = KVServeEngine([(0, root)], config=serve_cfg)
        db = eng.shards[0]
        dvm = db.device_views
        log("smoke promotion route: cold_reads=False (each partition's "
            "view uploads on its first read; cold reads would first have "
            "to serve half of each partition's bytes from the host)")
        times: dict[str, list] = {}

        def submit(kind, ops):
            t = time.perf_counter()
            res = eng.submit(Batch(ops)).result()
            times.setdefault(kind, []).append(time.perf_counter() - t)
            bad = [r for r in res if r.status is not OpStatus.OK]
            check(not bad, f"{kind}: {len(bad)} ops not OK: "
                  f"{bad[0].status} {bad[0].error}" if bad else "")
            return res

        def mget(q=None):
            if q is None:
                q = rng.permutation(np.concatenate([
                    rng.choice(keys, GET_BATCH - GET_BATCH // 8),
                    rng.integers(1, 1 << 63, GET_BATCH // 8, dtype=np.uint64),
                ]))
            dev = np.array([db.mem.get(k) is None for k in q.tolist()])
            lows = [p.lo for p in db.partitions]
            want_syncs = len(np.unique(route_host(lows, q[dev])))
            s0 = device_view.SYNCS
            r = submit("get", [Op.multiget(q)])[0]
            syncs = device_view.SYNCS - s0
            check(syncs == want_syncs, f"get batch paid {syncs} host syncs "
                  f"for {want_syncs} partitions")
            f, v = ref.get(q)
            check(np.array_equal(r.found, f), "multiget found != reference")
            check(np.array_equal(r.vals[f], v[f]),
                  "multiget values != reference")

        def scans(kind, starts):
            res = submit(kind, [Op.scan(int(s), SCAN_N) for s in starts])
            for s, r in zip(starts.tolist(), res):
                k, v = ref.scan(s, SCAN_N)
                check(np.array_equal(r.keys, k), f"{kind} keys != reference")
                check(np.array_equal(r.vals, v), f"{kind} vals != reference")

        def scan_starts(n):
            return np.concatenate([
                rng.choice(keys, n - n // 2),
                rng.integers(1, 1 << 63, n // 2, dtype=np.uint64),
            ])

        def writes():
            new = rng.integers(1, 1 << 63, 48, dtype=np.uint64)
            upd = rng.choice(keys, 48)
            dels = rng.choice(keys, 32)
            pk = np.concatenate([new, upd])
            pv = rng.integers(0, 1 << 32, (len(pk), VW), dtype=np.uint32)
            submit("write", [Op.put(pk, pv), Op.delete(dels)])
            for k, v in zip(pk.tolist(), pv):
                ref.over[k] = v
            for k in dels.tolist():
                ref.over[k] = None
            t = time.perf_counter()
            eng.flush()
            times.setdefault("flush", []).append(time.perf_counter() - t)

        def promote():
            """Read each partition's lowest key until every partition's
            view is resident (random keys rarely reach a narrow one)."""
            max_batches = len(db.partitions) + 8
            trail = []  # resident views after each round
            for _ in range(max_batches):
                if len(dvm) >= len(db.partitions):
                    return trail
                lows = np.array([p.lo for p in db.partitions], np.uint64)
                for i in range(0, len(lows), GET_BATCH):
                    mget(lows[i:i + GET_BATCH])
                trail.append(len(dvm))
            check(False, f"partitions not resident after {max_batches} "
                  f"rounds of get batches")

        try:
            t0 = time.perf_counter()
            trail = promote()
            out["promote_batches"] = len(times["get"])
            out["promote_s"] = time.perf_counter() - t0
            out["first_get_s"] = times["get"][0]
            log(f"smoke promotion: {len(dvm)} partitions resident after "
                f"{out['promote_batches']} get batches, "
                f"{out['promote_s']:.1f} s (first batch, compiling: "
                f"{out['first_get_s']:.2f} s); resident after each round: "
                f"{trail}")
            n_promote = out["promote_batches"]

            # ---- mixed traffic
            write_at = {rounds * j // WRITE_GROUPS + rounds // 8
                        for j in range(WRITE_GROUPS)}
            for i in range(rounds):
                mget()
                scans("scan", scan_starts(SCAN_BATCH))
                scans("lone_scan", scan_starts(1))
                if i in write_at:
                    writes()
            trail = promote()  # views of partitions a flush replaced
            log(f"smoke re-promotion after the last flush: resident after "
                f"each round: {trail}")

            out["batches"] = sum(len(v) for k, v in times.items()
                                 if k != "flush")
            out["first_scan_s"] = times["scan"][0]
            steady = dict(get=times["get"][n_promote + 1:],
                          scan=times["scan"][1:],
                          lone_scan=times["lone_scan"][1:])
            out["steady_s"] = {k: float(np.median(v))
                               for k, v in steady.items() if v}
            out["device_batches"] = _metric(db, "device_batches")
            out["device_fallback_total"] = _metric(
                db, "device_fallback_total")
            out["hbm_resident_bytes"] = _metric(db, "hbm_resident_bytes")
            out["interpret"] = dvm.interpret
            ups = db.events.list("device_upload")
            out["upload_tiers"] = sorted({e.fields["tier"] for e in ups})
            check(all(e.fields["interpret"] == dvm.interpret for e in ups),
                  "a view uploaded under another interpret mode")
        finally:
            eng.close()
            db.close()
    stats = jax.devices()[0].memory_stats() or {}
    out["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
    log(f"smoke batches: {out['batches']} through submit() "
        f"(first scan batch, compiling: {out['first_scan_s']:.2f} s); "
        f"median seconds per batch after the first: {out['steady_s']}")
    log(f"smoke counters: device_batches={out['device_batches']} "
        f"device_fallback_total={out['device_fallback_total']} "
        f"hbm_resident_bytes={out['hbm_resident_bytes']} "
        f"interpret={out['interpret']} tiers={out['upload_tiers']} "
        f"peak_bytes_in_use={out['peak_bytes_in_use']}")
    log("smoke parity: every answer matched the numpy reference; "
        "0 non-OK ops")
    check(out["device_batches"] > 0, "no batch ran on the device")
    check(out["device_fallback_total"] == 0,
          "a partition fell back off the device")
    check(out["hbm_resident_bytes"] >= min_resident,
          f"resident views below {min_resident} B")
    check(out["upload_tiers"] == ["full"], "a view was not full-tier")
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--keys", type=int, default=DEFAULT_KEYS)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rounds", type=int, default=64)
    args = ap.parse_args()

    # libtpu would otherwise write its logs under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from repro.compile_cache import setup_compile_cache

    cache = setup_compile_cache()
    import jax

    devs = jax.devices()
    dev = dict(platform=devs[0].platform, kind=devs[0].device_kind,
               count=len(devs))
    warm = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    log(f"smoke device: platform={dev['platform']} kind={dev['kind']} "
        f"count={dev['count']} compile_cache={cache} "
        f"({warm} entries at start)")
    if jax.default_backend() != "tpu":
        log(f"smoke refused: backend {jax.default_backend()!r} is not a TPU")
        return 2
    if args.keys < FULL_KEYS:
        log(f"smoke cut: {args.keys} keys instead of {FULL_KEYS}: 2^24 "
            f"keys took 773 s to load on a v5e host and about 950 s in "
            f"all, too close to the 20 minutes this run is held to")
    out = run_smoke(args.keys, seed=args.seed, rounds=args.rounds)
    check(out["interpret"] is False, "kernels ran in interpret mode")
    print(json.dumps(dict(ok=True, device=dev)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
