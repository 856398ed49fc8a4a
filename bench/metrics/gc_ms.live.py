"""Python's collector: the time of its passes (every generation) in the window, ms per tick."""


def read(ctx):
    return ctx.gc_ms()
