"""Self time of the anomaly monitor (``mux.anomaly``), ms per tick."""


def read(ctx):
    return ctx.phase_ms("mux.anomaly")
