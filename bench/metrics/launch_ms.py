"""launch_ms: mean per traced read request of its summed ``launch`` spans:
packing the query keys, their host-to-device copy and the ``now`` scalar,
and the dispatch of the jitted read, for the device views, the reads over
``p.index()`` and the cursor. Read from the requests the harness keeps
(``bench/spans.py``): every lookup, half the scans."""
from bench.spans import mean_ms


def read(ctx):
    return mean_ms(ctx, "launch")
