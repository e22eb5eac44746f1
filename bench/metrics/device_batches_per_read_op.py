"""device_batches_per_read_op: launches of the fused device read
(``device_batches``) in the window, over the read ops of the window."""


def read(ctx):
    ops = sum(not d.req.is_write for d in ctx.done)
    launches = ctx.counters.get("device_batches", 0)
    return launches / ops if ops and launches else None
