"""overlay_merge_ms: mean per traced scan request of its summed
``overlay_merge`` spans: merging the sorted MemTable overlay into a
batched scan's windows on the host. A coalesced group's spans are on
every member's trace, so this is the merge time a request waited for.
Read from the requests the harness keeps (``bench/spans.py``); a program
without the span reads nothing."""
from bench.spans import mean_ms


def read(ctx):
    return mean_ms(ctx, "overlay_merge")
