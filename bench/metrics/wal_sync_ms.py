"""wal_sync_ms: mean per traced insert of its summed ``wal_sync`` spans,
the WAL's fsync under the ``block`` policy (most inserts pay none; one
that fills a 4 KB block pays one). Every insert is kept
(``bench/spans.py``); a program whose inserts carry no ``wal_append``
span reads nothing."""
from bench.spans import mean_ms


def read(ctx):
    return mean_ms(ctx, "wal_sync", writes=True, marker="wal_append")
