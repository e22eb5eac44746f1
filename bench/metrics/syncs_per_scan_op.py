"""syncs_per_scan_op: blocking device-to-host fetches of every read path
in the window (the store's ``device_syncs`` counter), over the window's
scan requests. One fetch answers a whole coalesced group of scans; a lone
scan by cursor pays one of its own."""


def read(ctx):
    scans = sum(d.req.kind == "scan" for d in ctx.done)
    if not scans or "device_syncs" not in ctx.counters:
        return None
    return ctx.counters["device_syncs"] / scans
