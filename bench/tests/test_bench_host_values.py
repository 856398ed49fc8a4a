"""Numbers the harness takes on the host's clock over the whole window,
read as per-layer metrics: ``window_latency_p99_ms.live`` is the tail of
every window's latency, as the untraced run computes it."""

import numpy as np
import pytest

from bench import harness, tracing
from bench import traffic as T

FLEET = T.Fleet.from_config({"streams": 4, "windows": [16],
                             "stride_per_window": 1.0,
                             "capacity_records": 256})


def context(host):
    logs = [harness.TickLog(0.0, 0.0, np.full(4, k + 1), (), False)
            for k in range(3)]
    red = tracing.Reduced(0.001, 1.0, {}, {}, {}, 1)
    return tracing.Context(red, [], logs, np.zeros(4, np.int64), FLEET,
                           None, 0.0, 1.0, host=host)


def test_p99_reader_reads_the_window_tail():
    lat = harness.window_latencies(
        [harness.TickLog(0.0, 1.0 + 0.5 * k, np.full(4, 2 * (k + 1)), (),
                         False) for k in range(50)],
        np.zeros(4, np.int64), lambda s, js: (np.asarray(js) + 1) * 0.1)
    p99 = 1e3 * float(np.quantile(lat, .99))
    read = harness.load_reader("window_latency_p99_ms.live")
    assert read(context({"window_latency_p99_ms": p99})) == pytest.approx(p99)


@pytest.mark.parametrize("host", [None, {}, {"records_per_s": 5.0}])
def test_p99_reader_reads_nothing_without_a_tail(host):
    """A run that timed no window (a replay cell) has no tail to read."""
    read = harness.load_reader("window_latency_p99_ms.live")
    assert read(context(host)) is None
