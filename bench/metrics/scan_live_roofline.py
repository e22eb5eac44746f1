"""scan_live_roofline: the least time the chip's memory bandwidth allows
for the bytes the profiled ``scan_live`` calls must move, over their
device time in the trace, in percent.

Each useful query of a call reads its key's seek and the ``n`` slots it
asked for (``bench/kernel_bytes.py``, ``live_read_bytes(1, n, ...)``)
over the view of the partition its start falls in; the padding queries
and the slots past ``n`` are not counted. The mean over the window's
scan requests, times the useful queries per call (Δ``scan_live_queries``
÷ Δ``scan_live_calls``), stands for each ``scan_live`` execution the
trace holds. A program without those counters reads nothing.
"""
import numpy as np

from bench.kernel_bytes import live_read_bytes
from bench.trace_reduce import module_time


def read(ctx):
    if ctx.device is None:
        return None
    dev_s, execs = module_time(ctx.device, "scan_live")
    calls = ctx.counters.get("scan_live_calls", 0)
    scans = [d.req for d in ctx.done if d.req.kind == "scan"]
    if not dev_s or not execs or not calls or not scans:
        return None
    keys = np.array([r.key for r in scans], np.uint64)
    pidx = np.searchsorted(np.array(ctx.lows, np.uint64), keys,
                           side="right") - 1
    per = [live_read_bytes(1, r.n, *ctx.views[int(pi)])
           for r, pi in zip(scans, pidx) if int(pi) in ctx.views]
    if not per:
        return None
    per_call = float(np.mean(per)) * ctx.counters["scan_live_queries"] / calls
    bw = ctx.peaks[ctx.device_kind]["hbm_bytes_per_s"]
    return 100.0 * (per_call * execs / bw) / dev_s
