"""read_p95_ms: 95th percentile, over every read request of the window,
of the time from submission to result on the client side."""
import numpy as np


def read(ctx):
    lat = [d.t_done - d.t_sub for d in ctx.done if not d.req.is_write]
    return float(np.percentile(lat, 95)) * 1e3 if lat else None
