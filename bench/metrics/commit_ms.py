"""commit_ms: mean ``shard*:commit`` span time per traced write request
(WAL append under the sync policy, then the MemTable apply)."""


def read(ctx):
    t = [d.spans[2] for d in ctx.done
         if d.spans is not None and d.req.is_write]
    return sum(t) / len(t) * 1e3 if t else None
