"""read_ms: mean ``shard*:read`` span time per traced read request (the
store read path: overlay probe, routing, device views and cursors)."""


def read(ctx):
    t = [d.spans[1] for d in ctx.done
         if d.spans is not None and not d.req.is_write]
    return sum(t) / len(t) * 1e3 if t else None
