"""One run of one cell: load, serve through ``submit()``, measure, compare.

:func:`run_cell` is the whole run that ``bench/run.py`` makes, in order:

1. builds the configuration's records from the seed;
2. loads them through the program's ``put_batch``/``flush`` under
   ``sync_policy="none"``, flushes until no entry is carried in the
   MemTable, and closes (which fsyncs);
3. reopens the store under its stated policy behind a one-shard
   ``KVServeEngine``;
4. reads each partition's lowest key until every partition's device
   view is resident;
5. warms every shape the mix can use (``Traffic.warmup``);
6. runs the closed loop for ``seconds``: ``clients`` threads, each with
   one request outstanding, each request one ``Batch`` through
   ``submit()``, timed on the client side from submission to result;
7. reads the peak device memory, frees the program's state, and compares
   the sampled answers with the plain reference (``bench/reference.py``);
8. reads each metric the cell reports with its reader in
   ``bench/metrics/<name>.py``.

With ``trace`` every request is a ``Batch(trace=True)`` and the first
``PROFILE_S`` seconds of the window run under the JAX profiler (a trace
of the whole window would hold millions of device ops); the reduction is
``bench/trace_reduce.py``.
"""
from __future__ import annotations

import dataclasses
import gc
import glob
import importlib.util
import json
import re
import shutil
import sys
import tempfile
import threading
import time
from pathlib import Path

import numpy as np

from bench import data as D
from bench.reference import Reference
from bench.trace_reduce import read_xplane, reduce_events
from bench.traffic import Request, Traffic

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
READ_SPAN = re.compile(r"^shard\d+:read$")
COMMIT_SPAN = re.compile(r"^shard\d+:commit$")
PROFILE_S = 5.0  # seconds of the window the device trace covers


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


# ---------------- the cell, from BENCHMARK.json ----------------
def load_cell(name: str, root: Path = ROOT) -> dict:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    wl = next((w for w in spec["workloads"] if w["name"] == name), None)
    if wl is None:
        raise ValueError(f"no workload {name!r} in BENCHMARK.json")
    entry = next(c for c in spec["configs"] if c["name"] == wl["config"])
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if name in m.get("workloads", [name])
                 and ("workloads" in m or m["moves"] in names)]
    return dict(
        workload=wl,
        cfg=json.loads((root / entry["file"]).read_text()),
        mix=json.loads((root / "bench" / "traffic"
                        / f"{wl['traffic']}.json").read_text()),
        end_to_end=e2e,
        per_layer=per_layer,
    )


def read_metric(name: str, ctx) -> float | None:
    """The metric's value from its reader, ``bench/metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


# ---------------- compilations, counted by JAX's monitoring ----------------
class CompileCounter:
    """Lowerings (jit cache misses) and backend compiles so far."""

    _instance = None

    def __init__(self):
        import jax.monitoring as mon

        self.lowered = 0
        self.compiled = 0
        mon.register_event_duration_secs_listener(self._on)

    @classmethod
    def get(cls) -> "CompileCounter":
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.lowered += 1
        elif event == "/jax/core/compile/backend_compile_duration":
            self.compiled += 1

    def snapshot(self) -> tuple[int, int]:
        return self.lowered, self.compiled


# ---------------- the store ----------------
def store_config(cfg: dict, **over):
    from repro.db.compaction import CompactionConfig
    from repro.db.store import RemixDBConfig

    st = cfg["store"]
    kw = dict(vw=int(st["vw"]), d=int(st["d"]),
              memtable_entries=int(st["memtable_entries"]),
              compaction=CompactionConfig(table_cap=int(st["table_cap"])),
              sync_policy=st["sync_policy"])
    kw.update(over)
    return RemixDBConfig(**kw)


def load_store(cfg: dict, records, order, vals, root_dir: str) -> dict:
    """Load every record through ``put_batch``; returns the store's shape."""
    from repro.db.store import RemixDB

    t0 = time.perf_counter()
    db = RemixDB.open(root_dir, store_config(cfg, device_path="off",
                                             sync_policy="none"))
    step = int(cfg["store"]["memtable_entries"])
    for i in range(0, len(order), step):
        idx = order[i:i + step]
        db.put_batch(records[idx], vals[idx])
    db.flush()
    for _ in range(8):  # entries an aborted compaction carried over
        if not len(db.mem):
            break
        db.flush()
    parts = db.partitions
    out = dict(
        partitions=len(parts),
        tables=sum(len(p.tables) for p in parts),
        max_runs=max(len(p.tables) for p in parts),
        carried=len(db.mem),
        view_bytes=sum(p.device_view_bytes(with_vals=True) for p in parts),
    )
    db.close()
    del db, parts
    gc.collect()
    out["load_s"] = time.perf_counter() - t0
    return out


def open_engine(cfg: dict, root_dir: str, view_bytes: int):
    import jax

    from repro.serve.engine import KVServeEngine

    sv = cfg["serve"]
    budget = int(sv["budget_x_views"] * view_bytes)
    limit = (jax.devices()[0].memory_stats() or {}).get("bytes_limit")
    if limit is not None and budget > 0.75 * limit:
        raise RuntimeError(f"views need {view_bytes} B; the device holds "
                           f"{limit} B")
    serve_cfg = store_config(
        cfg, device_path=sv["device_path"], cold_reads=bool(sv["cold_reads"]),
        device_budget_bytes=budget, cache_bytes=int(sv["cache_bytes"]),
        submit_workers=int(sv["submit_workers"]),
    )
    return KVServeEngine([(0, root_dir)], cache_bytes=int(sv["cache_bytes"]),
                         config=serve_cfg,
                         submit_workers=int(sv["submit_workers"]))


def submit_ok(eng, ops) -> object:
    from repro.db.ops import Batch

    res = eng.submit(Batch(ops)).result()
    bad = [r for r in res if not r.ok]
    if bad:
        raise RuntimeError(f"set-up op not OK: {bad[0].status} {bad[0].error}")
    return res


def promote(eng) -> int:
    """Read each partition's lowest key until every view is resident;
    returns the get batches it took."""
    from repro.db.ops import Op

    db = eng.shards[0]
    dvm = db.device_views
    if dvm is None:
        return 0
    batches = 0
    for _ in range(len(db.partitions) + 8):
        if len(dvm) >= len(db.partitions):
            return batches
        lows = np.array([p.lo for p in db.partitions], np.uint64)
        for i in range(0, len(lows), 256):
            submit_ok(eng, [Op.multiget(lows[i:i + 256])])
            batches += 1
    raise RuntimeError("partitions not resident after promotion rounds")


def counters(eng) -> dict[str, float]:
    out: dict[str, float] = {}
    for db in eng.shards:
        for s in db.registry.snapshot()["metrics"]:
            if isinstance(s.get("value"), (int, float)):
                out[s["name"]] = out.get(s["name"], 0) + s["value"]
    return out


# ---------------- the closed loop ----------------
@dataclasses.dataclass
class Done:
    req: Request
    t_sub: float
    t_done: float
    ok: bool
    result: object = None  # BatchResult, kept for compared requests
    spans: tuple | None = None  # (root_s, read_s, commit_s) when traced


def span_times(trace) -> tuple[float, float, float]:
    read = commit = 0.0
    for s in trace.spans():
        if READ_SPAN.match(s.name):
            read += s.duration
        elif COMMIT_SPAN.match(s.name):
            commit += s.duration
    return trace.root.duration, read, commit


def run_request(eng, req: Request, traced: bool = False) -> Done:
    import jax

    from repro.db.ops import Batch

    ops = req.ops()
    with jax.profiler.TraceAnnotation(f"bench.request:{req.kind}"):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.submit"):
            fut = eng.submit(Batch(ops, trace=traced))
        res = fut.result()
        t1 = time.perf_counter()
    ok = all(r.ok for r in res)
    keep = req.check or req.is_write
    return Done(req, t0, t1, ok, res if keep else None,
                span_times(res.trace) if traced else None)


def closed_loop(eng, traffic: Traffic, clients: int, seconds: float,
                traced: bool, profile_s: float = 0.0) -> tuple:
    """``clients`` threads, each with one request outstanding, until
    ``seconds`` have passed; every request started is waited for. With
    ``profile_s``, the JAX profiler records the window's first
    ``profile_s`` seconds. Returns the requests, the window's start and
    end, and the profiled seconds."""
    import jax

    out: list[list[Done]] = [[] for _ in range(clients)]
    errors: list[BaseException] = []
    if profile_s:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        prof_dir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(prof_dir, profiler_options=opts)
    t_start = time.perf_counter()
    deadline = t_start + seconds

    def client(mine: list):
        try:
            while time.perf_counter() < deadline:
                mine.append(run_request(eng, traffic.next(), traced))
        except BaseException as e:  # reported after the join
            errors.append(e)

    threads = [threading.Thread(target=client, args=(out[i],), daemon=True)
               for i in range(clients)]
    for t in threads:
        t.start()
    profiled = None
    if profile_s:
        time.sleep(max(0.0, t_start + profile_s - time.perf_counter()))
        profiled = (prof_dir, time.perf_counter() - t_start)
        jax.profiler.stop_trace()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]
    done = [d for mine in out for d in mine]
    t_end = max([d.t_done for d in done], default=time.perf_counter())
    return done, t_start, t_end, profiled


# ---------------- the comparison ----------------
def compare(ref: Reference, done: list[Done], answers=None) -> dict:
    """Counts of compared ops and of wrong answers among them. ``answers``
    stands in for the program's answers (the control)."""
    for d in done:
        if d.req.is_write:
            ack = d.t_done if d.ok else float("inf")
            ref.record_write(d.req.key, d.req.val, d.t_sub, ack)
    checked = wrong = 0
    for d in done:
        if d.req.is_write or not d.req.check or d.result is None:
            continue
        r = (answers(d) if answers is not None else d.result.results)[0]
        q = d.req
        checked += 1
        if q.kind == "scan":
            ok = ref.check_scan(q.key, q.n, d.t_sub, d.t_done, r.keys, r.vals)
        else:
            ok = ref.check_get(q.key, d.t_sub, d.t_done, r.found, r.value)
        wrong += not ok
    return dict(checked=checked, wrong=wrong)


# ---------------- one run ----------------
@dataclasses.dataclass
class Context:
    """What the metric readers read."""

    done: list
    window_s: float
    setup_s: float
    load_s: float
    counters: dict  # deltas over the window
    lows: list
    views: dict  # partition index -> (G, D, KW, VW) of its device view
    device: dict | None  # trace_reduce.reduce_events output
    device_kind: str
    peaks: dict  # bench/peaks.json, by device kind


def bytes_written() -> int | None:
    """Bytes this process has sent to storage so far (Linux)."""
    try:
        with open("/proc/self/io") as f:
            for line in f:
                if line.startswith("write_bytes:"):
                    return int(line.split()[1])
    except OSError:
        return None
    return None


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return dict(platform=devs[0].platform, kind=devs[0].device_kind,
                count=len(devs))


def chips_ok(name: str) -> bool:
    """Print the device; False (with the reason) unless it is a TPU with
    as many chips as the cell asks for."""
    import jax

    dev = device_info()
    log(f"device: platform={dev['platform']} kind={dev['kind']} "
        f"count={dev['count']}")
    need = int(load_cell(name)["workload"]["chips"])
    if jax.default_backend() != "tpu":
        log(f"refused: backend {jax.default_backend()!r} is not a TPU")
        return False
    if dev["count"] < need:
        log(f"refused: {dev['count']} chips, the cell needs {need}")
        return False
    return True


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_process: float | None = None, overrides: dict | None = None,
             control=None, root: Path = ROOT) -> dict:
    """One run of cell ``name``; returns the result line's fields (and,
    with ``control``, the control's comparison under ``control``).

    ``overrides`` merges into the configuration and the mix (tests run
    the same path at a tiny size). ``control(ref)`` returns a function
    that answers a request in the program's place."""
    import jax

    t_process = time.perf_counter() if t_process is None else t_process
    cell = load_cell(name, root)
    cfg, mix = cell["cfg"], cell["mix"]
    for k, v in (overrides or {}).items():
        sect, _, key = k.rpartition(".")
        (mix if sect == "mix" else cfg[sect] if sect else cfg)[key] = v
    counter = CompileCounter.get()
    records, order, vals = D.make_data(cfg, seed)
    traffic = Traffic(mix, cfg, records, seed)
    with tempfile.TemporaryDirectory(prefix="bench-store-") as store_dir:
        shape = load_store(cfg, records, order, vals, store_dir)
        log(f"load: {len(records)} records in {shape['load_s']:.1f} s -> "
            f"{shape['partitions']} partitions, {shape['tables']} tables, "
            f"up to {shape['max_runs']} runs, {shape['carried']} carried, "
            f"{shape['view_bytes']} B of views")
        eng = open_engine(cfg, store_dir, shape["view_bytes"])
        db = eng.shards[0]
        try:
            n_promote = promote(eng)
            ref = Reference(records, vals)
            lows = [int(p.lo) for p in db.partitions]
            cuts = np.searchsorted(ref.keys, np.array(lows[1:], np.uint64))
            parts = [k for k in np.split(ref.keys, cuts) if len(k)]
            warm = traffic.warmup(parts)
            warm_done = [run_request(eng, r) for r in warm]
            bad = [d for d in warm_done if not d.ok]
            if bad:
                raise RuntimeError(f"{len(bad)} warm-up requests not OK")
            log(f"set-up: promoted in {n_promote} get batches, "
                f"{len(db.device_views or ())} views resident, "
                f"{len(warm)} warm-up requests, compiles so far "
                f"(lowered, backend) {counter.snapshot()}")
            c0 = counters(eng)
            k0 = counter.snapshot()
            setup_s = time.perf_counter() - t_process
            done, t_start, t_end, profiled = closed_loop(
                eng, traffic, int(mix["clients"]), seconds, trace,
                min(seconds, PROFILE_S) if trace else 0.0)
            k1 = counter.snapshot()
            c1 = counters(eng)
            views = {}
            for i, p in enumerate(db.partitions):
                dv = db.device_views.view_for(p) if db.device_views else None
                if dv is not None:
                    views[i] = (int(dv.remix.anchors.shape[0]),
                                int(dv.remix.d),
                                int(dv.runset.keys.shape[2]), int(dv.vw))
            stats = jax.devices()[0].memory_stats() or {}
        finally:
            eng.close()
            db.close()
        del eng, db
        gc.collect()
    log(f"window: {len(done)} requests in {t_end - t_start:.3f} s; "
        f"compiles inside the window (lowered, backend): "
        f"({k1[0] - k0[0]}, {k1[1] - k0[1]}); bytes written by the run: "
        f"{bytes_written()}")
    dev = None
    if profiled is not None:
        prof_dir, profiled_s = profiled
        files = glob.glob(f"{prof_dir}/plugins/profile/*/*.xplane.pb")
        dev = reduce_events(read_xplane(files[0]), profiled_s)
        shutil.rmtree(prof_dir, ignore_errors=True)

    cmp = compare(ref, warm_done + done)
    ctx = Context(
        done=done, window_s=t_end - t_start, setup_s=setup_s,
        load_s=shape["load_s"],
        counters={k: c1.get(k, 0) - c0.get(k, 0) for k in c1},
        lows=lows, views=views, device=dev,
        device_kind=device_info()["kind"],
        peaks=json.loads((BENCH / "peaks.json").read_text())["devices"],
    )
    wanted = cell["per_layer"] if trace else cell["end_to_end"]
    metrics = {}
    for m in wanted:
        v = read_metric(m["name"], ctx)
        if v is not None:
            metrics[m["name"]] = dict(value=float(v), unit=m["unit"])
    failed = sum(not d.ok for d in done)  # one op per request
    checks = {
        "wrong_answers": dict(value=cmp["wrong"], limit=0),
        "failed_ops": dict(value=failed, limit=0),
    }
    correct = cmp["checked"] > 0 and all(
        c["value"] <= c["limit"] for c in checks.values())
    device = dict(device_info(),
                  memory_peak_bytes=stats.get("peak_bytes_in_use"))
    out = dict(correct=correct, attempted=len(done), failed=failed,
               metrics=metrics, device=device)
    if dev is not None:
        device.update(busy_s=dev["busy_s"], window_s=dev["window_s"])
        out["breakdown"] = dict(device_ops=dev["device_ops"],
                                idle_gaps=dev["idle_gaps"])
    if control is not None:
        ctl = compare(Reference(records, vals), warm_done + done,
                      answers=control(ref))
        out["control"] = dict(checked=ctl["checked"], wrong=ctl["wrong"])
    out["checked_ops"] = cmp["checked"]
    out["checks"] = checks
    return out
