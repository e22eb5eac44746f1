"""device_wait_ms: mean per traced read request of its summed
``device_wait`` spans, each one blocking device-to-host fetch of a read
path (the host waits for the device and the copy). Read from the
requests the harness keeps (``bench/spans.py``): every lookup, half the
scans."""
from bench.spans import mean_ms


def read(ctx):
    return mean_ms(ctx, "device_wait")
