"""Self time of drain and coalesce (``mux.coalesce``), ms per tick."""


def read(ctx):
    return ctx.phase_ms("mux.coalesce")
