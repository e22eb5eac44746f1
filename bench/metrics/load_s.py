"""load_s: the benchmark's own clock around the load through put_batch and
flush, to the close that fsyncs it."""


def read(ctx):
    return ctx.load_s
