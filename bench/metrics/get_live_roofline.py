"""get_live_roofline: the least time the chip's memory bandwidth allows
for the bytes the profiled ``get_live`` calls must move, over their device
time in the trace, in percent.

Each lone lookup that misses the MemTable is one ``get_live`` call of one
useful query at window width 1 (``bench/kernel_bytes.py``) over the view
of the partition its key falls in; the mean bytes of the window's lookups
stand for each ``get_live`` execution the trace holds.
"""
import numpy as np

from bench.kernel_bytes import live_read_bytes
from bench.trace_reduce import module_time


def read(ctx):
    if ctx.device is None:
        return None
    dev_s, calls = module_time(ctx.device, "get_live")
    keys = np.array([d.req.key for d in ctx.done if d.req.kind == "get"],
                    np.uint64)
    if not dev_s or not calls or not len(keys):
        return None
    pidx = np.searchsorted(np.array(ctx.lows, np.uint64), keys,
                           side="right") - 1
    per = [live_read_bytes(1, 1, *ctx.views[int(pi)]) for pi in pidx]
    bw = ctx.peaks[ctx.device_kind]["hbm_bytes_per_s"]
    return 100.0 * (float(np.mean(per)) * calls / bw) / dev_s
