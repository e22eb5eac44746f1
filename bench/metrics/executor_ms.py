"""executor_ms: mean per traced request of the executor's own time, the
``batch`` span less its ``shard*:read`` and ``shard*:commit`` children
(admission, queueing, planning and fan-in)."""


def read(ctx):
    t = [root - rd - cm for root, rd, cm in
         (d.spans for d in ctx.done if d.spans is not None)]
    return sum(t) / len(t) * 1e3 if t else None
