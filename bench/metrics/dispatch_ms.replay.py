"""Self time of the dispatch: host staging, the launch and the copy back (``mux.dispatch``), ms per tick."""


def read(ctx):
    return ctx.phase_ms("mux.dispatch")
