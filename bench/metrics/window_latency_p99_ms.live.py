"""The 99th percentile of every window's latency in the window, ms: the
tail that the collector's passes and the host's stalls set."""


def read(ctx):
    return ctx.host_value("window_latency_p99_ms")
