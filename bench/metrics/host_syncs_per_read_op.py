"""host_syncs_per_read_op: blocking device-to-host fetches of every read
path in the window (the store's ``device_syncs`` counter), over the read
ops of the window."""


def read(ctx):
    ops = sum(not d.req.is_write for d in ctx.done)
    if not ops or "device_syncs" not in ctx.counters:
        return None
    return ctx.counters["device_syncs"] / ops
