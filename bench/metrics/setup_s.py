"""setup_s: seconds from process start to the start of the measured window
(data, load, reopen, promotion, warm-up and compiles)."""


def read(ctx):
    return ctx.setup_s
