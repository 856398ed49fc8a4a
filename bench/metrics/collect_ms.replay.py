"""Self time of commit and collect (``mux.commit`` and ``mux.collect``), ms per tick."""


def read(ctx):
    return ctx.phase_ms("mux.commit", "mux.collect")
