"""ops_per_s: requests that came back OK in the window, over the window's
seconds. Each request is one op."""


def read(ctx):
    ops = sum(d.ok for d in ctx.done)
    return ops / ctx.window_s if ctx.window_s > 0 else None
