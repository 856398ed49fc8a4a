"""The end-to-end arithmetic on synthetic tick logs: the rate counts each
record once over all the time, and the tail is over every window, so one
stalled tick moves it."""

import numpy as np

from bench import harness
from bench import traffic as T


def logs_of(ends, per_tick, streams=4):
    out, v = [], np.zeros(streams, np.int64)
    for e in ends:
        v = v + per_tick
        out.append(harness.TickLog(0.0, e, v.copy(), (), False))
    return out


def test_covered_counts_each_record_once():
    fleet = T.Fleet.from_config({"streams": 2, "windows": [8, 16],
                                 "stride_per_window": 0.5,
                                 "capacity_windows": 4})
    v0 = np.array([0, 0])
    # First windows cover a whole window; later ones one stride each.
    assert harness.covered_records(fleet, v0, np.array([1, 1])) == 8 + 16
    assert harness.covered_records(fleet, np.array([1, 1]),
                                   np.array([4, 2])) == 3 * 4 + 8


def test_tail_is_over_every_window_and_sees_a_stall():
    due = lambda s, js: (np.asarray(js) + 1) * 0.1  # noqa: E731
    steady = harness.window_latencies(
        logs_of([1.0 + 0.5 * k for k in range(40)], 5), np.zeros(4, np.int64),
        due)
    assert steady.size == 40 * 5 * 4
    ends = [1.0 + 0.5 * k for k in range(40)]
    ends[20:] = [e + 3.0 for e in ends[20:]]  # a 3 s stall at tick 20
    stalled = harness.window_latencies(
        logs_of(ends, 5), np.zeros(4, np.int64), due)
    assert np.quantile(stalled, 0.99) > np.quantile(steady, 0.99) + 2.0
    assert np.quantile(stalled, 0.99) >= np.quantile(stalled, 0.5)


def test_rate_is_all_work_over_all_time():
    fleet = T.Fleet.from_config({"streams": 4, "windows": [16],
                                 "stride_per_window": 1.0,
                                 "capacity_records": 256})
    logs = logs_of([1.0, 2.0, 9.0], 2)
    covered = harness.covered_records(fleet, np.zeros(4, np.int64),
                                      logs[-1].vetted)
    assert covered == 3 * 2 * 4 * 16
    # The slow last tick counts: the rate is not a mean of per-tick rates.
    assert covered / logs[-1].end_s < np.mean([2 * 4 * 16 / 1.0] * 3)
