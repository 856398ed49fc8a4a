"""Device time of the change-point program (``jit_changepoint_pallas``: the prefix sums, the SSE scan and the argmin), ms per tick.  Nothing where the trace holds no such program."""

PROGRAM = "changepoint_pallas"


def read(ctx):
    took = ctx.trace.module_s(PROGRAM)
    return 1e3 * took / ctx.ticks if took > 0 else None
