"""Self time of the mux's tick planner (``mux.plan``), ms per tick."""


def read(ctx):
    return ctx.phase_ms("mux.plan")
