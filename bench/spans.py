"""Span sums over the traced requests the harness keeps.

The harness keeps the result, and with it the trace, of every request
it compares or that writes (``req.check or req.is_write``): every
lookup of ``alex-ycsb.read-heavy``, half the scans of
``alex-ycsb.short-range`` and every insert of both.
"""


def mean_ms(ctx, name: str, writes: bool = False,
            marker: str | None = None) -> float | None:
    """Mean over the kept traced read requests (write requests with
    ``writes``) of the summed durations of their ``name`` spans, in ms.
    None when no kept trace holds a span named ``marker`` (``name`` by
    default): a program without that instrumentation reads nothing."""
    marker = marker or name
    sums, seen = [], False
    for d in ctx.done:
        tr = getattr(d.result, "trace", None)
        if d.req.is_write != writes or tr is None:
            continue
        spans = tr.spans()
        seen = seen or any(s.name == marker for s in spans)
        sums.append(sum(s.duration for s in spans if s.name == name))
    return sum(sums) / len(sums) * 1e3 if seen else None
