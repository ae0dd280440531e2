"""setup_s: process start to the first timed call, host clock (s)."""


def read(ctx, name):
    return ctx.setup_s
