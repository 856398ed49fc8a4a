"""The program's own counters against what the harness counts from its
tick logs, on a seeded run at a tiny size on the CPU: ring-pressure ticks
(``MuxStats.pressure_ticks`` against the ticks ``feed`` took, as
``TickRecorder`` logs them) and windows the monitor scanned fewer than
``confirm`` times (``AnomalyMonitor.underscanned`` against
``harness.monitor_scans``)."""

import numpy as np

from bench import harness

SEED = 2 ** 31 + 29
MONITOR = {"ring": 8, "omega": 2, "min_points": 0, "min_confidence": 0.25,
           "min_ratio": 2.0, "confirm": 3}


def test_program_counters_match_the_harness():
    cell = harness.load_cell("vet16k.replay")
    # A ring of 4 windows fed 6 strides a tick: every tick's feed ticks
    # under pressure, and a monitor ring of 8 windows sees each window two
    # or three times.  No warm ticks: the recorder marks pressure ticks in
    # the window only, the program counts them all.
    cell = cell._replace(
        config={**cell.config, "backend": "numpy", "streams": 6,
                "windows": [8], "stride_per_window": 1.0,
                "capacity_windows": 4,
                "monitor": MONITOR, "check": {"sample_windows": 8},
                "limits": {**cell.config["limits"], "flags_unmatched": 0}},
        traffic={**cell.traffic, "pool_records": 1 << 14,
                 "strides_per_tick": 6, "warm_ticks": 0})
    built = []

    def build(cfg):
        built.append(harness.build_mux(cfg))
        return built[-1]
    harness.run_cell(cell, seed=SEED, seconds=1.0, build=build)
    (mux,) = built
    logs = mux.tick.logs  # the TickRecorder in the mux's place
    pressure = sum(lg.pressure for lg in logs)
    assert mux.stats.pressure_ticks == pressure > 0
    scans = harness.monitor_scans(logs, np.zeros(len(mux), np.int64),
                                  MONITOR["ring"])
    too_few = int((scans < MONITOR["confirm"]).sum())
    assert mux.monitor.underscanned == too_few > 0
    assert too_few < scans.size
